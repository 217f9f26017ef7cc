"""Batcher coalescing: the share of the window's batches that a request's own
handler thread closed and staged (`serving/batcher.py::_crosses_direct_locked`:
no collector, no coalesce window, no dispatch thread). The program counts them
by the phase `batch.direct`, which it puts on `/monitoring` at count 0 when the
batcher starts; `batch.dispatch` counts every batch. A program without the
phase, as the commit before ISSUE 42 is, reads nothing; a window without a
batch reads nothing."""
from _lib import phase_count


def read(ctx):
    batches = phase_count(ctx, "batch.dispatch")
    if "batch.direct" not in ctx["phases"] or not batches:
        return None
    return 100.0 * phase_count(ctx, "batch.direct") / batches
