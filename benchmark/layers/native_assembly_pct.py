"""Batcher assembly: the share of the window's batches that the native
assembler built (one pass from the requests' arrays to the upload's words):
batch.fusedpack is emitted once for each, batch.dispatch once a batch. A
program that never emits batch.fusedpack reads 0.0; a window without a
batch reads nothing."""
from _lib import phase_count


def read(ctx):
    batches = phase_count(ctx, "batch.dispatch")
    return 100.0 * phase_count(ctx, "batch.fusedpack") / batches if batches else None
