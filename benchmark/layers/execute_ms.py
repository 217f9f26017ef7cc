"""Service to batcher: mean predict.execute, which is queue wait + batch +
readback as the request's handler sees it."""
from _lib import phase_mean_us


def read(ctx):
    mean = phase_mean_us(ctx, "predict.execute")
    return None if mean is None else mean / 1e3
