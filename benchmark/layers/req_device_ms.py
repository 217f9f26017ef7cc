"""Jitted step: mean `req.device`, from the readback's issue to the fetch's
return: device execution and D2H as the host sees them."""
from _timeline import phase_mean_ms


def read(ctx):
    return phase_mean_ms(ctx, "req.device")
