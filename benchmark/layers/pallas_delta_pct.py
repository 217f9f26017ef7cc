"""Kernels (the gated delta rule): the share of the window's batches that ran
an entry whose rule walks its chunks in the Pallas kernel
(`models/olmo_hybrid.py::delta_choice`, `ops/delta_kernel.py`): the program
counts them by the phase `batch.delta_kernel`, `batch.dispatch` counts every
batch, and the servable's `startup.delta_rule` stamp names the path. A program
without the stamp, as the commit before ISSUE 52 is, reads nothing; one whose
stamp says `xla` (a scan that hands the state over through HBM a chunk) reads
0.0; a window without a batch reads nothing."""
from _lib import phase_count


def read(ctx):
    stamps = (ctx["runtime"].get("startup") or {}).get("delta_rule") or {}
    batches = phase_count(ctx, "batch.dispatch")
    if not stamps or not batches:
        return None
    return 100.0 * phase_count(ctx, "batch.delta_kernel") / batches
