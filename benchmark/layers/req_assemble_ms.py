"""Batcher assembly: mean `req.assemble`, from the group's close to the device
stage's start: pad, dedup, the staged wait behind the dispatch thread, the
in-flight-window wait."""
from _timeline import phase_mean_ms


def read(ctx):
    return phase_mean_ms(ctx, "req.assemble")
