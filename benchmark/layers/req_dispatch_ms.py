"""Batcher assembly: mean `req.dispatch`, from the device stage's start to the
readback's issue: input digest, pack, upload, jit call."""
from _timeline import phase_mean_ms


def read(ctx):
    return phase_mean_ms(ctx, "req.dispatch")
