"""H2D (ops/transfer.py): mean batch.cache per batch: the input digest and,
on a miss, the pack and the upload. Every row is new, so every batch misses."""
from _lib import phase_mean_us


def read(ctx):
    return phase_mean_us(ctx, "batch.cache")
