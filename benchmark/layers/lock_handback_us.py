"""Batcher assembly: mean `batch.fusedpack` (the dispatch thread's clock around
`native.assemble_batch`) less mean `batch.fusedpack_native` (the pass's own
time by its own clock, the interpreter lock released), in us a batch: the
wrapper's argument tables, ctypes, and the dispatch thread's wait to take the
interpreter lock back. The one place the program reads that wait exactly."""
from _lib import phase_mean_us


def read(ctx):
    outer, inner = phase_mean_us(ctx, "batch.fusedpack"), phase_mean_us(ctx, "batch.fusedpack_native")
    return None if outer is None or inner is None else outer - inner
