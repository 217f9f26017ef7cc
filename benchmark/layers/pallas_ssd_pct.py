"""Kernels (the Mamba-2 mixer's SSD): the share of the window's batches that
ran an entry whose SSD walks its chunks in the Pallas kernel
(`models/falcon_h1.py::ssd_choice`, `ops/ssd_kernel.py`): the program counts
them by the phase `batch.ssd_kernel`, `batch.dispatch` counts every batch, and
the servable's `startup.ssd` stamp names the path. A program without a path in
that stamp (every other family's, whose stamp is empty; a commit before
ISSUE 54) reads nothing; one whose stamp says `xla` (a scan that hands the
state over through HBM a chunk: the commit before ISSUE 55 always) reads 0.0;
a window without a batch reads nothing."""
from _lib import phase_count


def read(ctx):
    stamps = (ctx["runtime"].get("startup") or {}).get("ssd") or {}
    batches = phase_count(ctx, "batch.dispatch")
    if not any("path" in stamp for stamp in stamps.values()) or not batches:
        return None
    return 100.0 * phase_count(ctx, "batch.ssd_kernel") / batches
