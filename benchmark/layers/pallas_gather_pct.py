"""Kernels (the embedding gather): the share of the window's batches that ran
an entry whose gather is the Pallas kernel with row copies in flight
(`models/embeddings.py::gather_choice`): the program counts them by the phase
`batch.gather_kernel`, `batch.dispatch` counts every batch, and the servable's
`startup.gather` stamp names the kernel. A program without the stamp, as the
commit before ISSUE 39 is, reads nothing; one whose stamp says `xla` reads
0.0; a window without a batch reads nothing."""
from _lib import phase_count


def read(ctx):
    stamps = (ctx["runtime"].get("startup") or {}).get("gather") or {}
    batches = phase_count(ctx, "batch.dispatch")
    if not stamps or not batches:
        return None
    return 100.0 * phase_count(ctx, "batch.gather_kernel") / batches
