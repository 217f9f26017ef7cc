"""Service to batcher: mean `req.resume`, from set_result on the completer
thread to the request's handler running again."""
from _timeline import phase_mean_ms


def read(ctx):
    return phase_mean_ms(ctx, "req.resume")
