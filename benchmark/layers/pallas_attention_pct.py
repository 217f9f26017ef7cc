"""Kernels (the attention at all positions): the share of the window's batches
that ran an entry whose attention is the Pallas kernel that keeps the score
tile in VMEM (`models/sequence.py::attention_choice`): the program counts them
by the phase `batch.attention_kernel`, `batch.dispatch` counts every batch,
and the servable's `startup.attention` stamp names the kernel. A program
without the stamp, as the commit before ISSUE 48 is, reads nothing; one whose
stamp says `xla` reads 0.0; a window without a batch reads nothing."""
from _lib import phase_count


def read(ctx):
    stamps = (ctx["runtime"].get("startup") or {}).get("attention") or {}
    batches = phase_count(ctx, "batch.dispatch")
    if not stamps or not batches:
        return None
    return 100.0 * phase_count(ctx, "batch.attention_kernel") / batches
