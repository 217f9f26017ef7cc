"""Kernels (the routed layer): the share of the window's batches that ran an
entry whose held experts are one pass of the Pallas grouped kernels
(`models/routed.py::grouped_choice`, `ops/grouped_kernel.py`): the program
counts them by the phase `batch.grouped_kernel`, `batch.dispatch` counts every
batch, and the servable's `startup.grouped` stamp names the path. A program
without the stamp, as the commit before ISSUE 51 is, reads nothing; one whose
stamp says `xla` (an expert's loop of padded blocks) reads 0.0; a window
without a batch reads nothing."""
from _lib import phase_count


def read(ctx):
    stamps = (ctx["runtime"].get("startup") or {}).get("grouped") or {}
    batches = phase_count(ctx, "batch.dispatch")
    if not stamps or not batches:
        return None
    return 100.0 * phase_count(ctx, "batch.grouped_kernel") / batches
