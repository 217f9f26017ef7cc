"""D2H and completer: mean `req.deliver`, from the fetch's return to this
request's set_result: widen, scatter, slices, and the batch's requests resolved
before it."""
from _timeline import phase_mean_ms


def read(ctx):
    return phase_mean_ms(ctx, "req.deliver")
