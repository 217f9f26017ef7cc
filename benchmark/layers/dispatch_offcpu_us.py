"""Batcher assembly: the part of a batch's `batch.dispatch` span its thread
was not on a core, in us: mean `offcpu.batch.dispatch` (capture only). The
native assembly and the upload release the interpreter lock; taking it back
is in here, as is a core denied, and the wait for the runtime's own threads
inside the jit call. A small sample's sum below zero (a coarse CPU clock's
tick in a short span) reads 0."""
from _lib import phase_mean_us


def read(ctx):
    mean = phase_mean_us(ctx, "offcpu.batch.dispatch")
    return None if mean is None else max(mean, 0.0)
