"""Kernels (the Mamba-2 mixer's convolution): the share of the window's
batches that ran an entry whose mixers convolve their channels in the Pallas
kernel (`models/falcon_h1.py::conv_choice`, `ops/conv_kernel.py`): the program
counts them by the phase `batch.conv_kernel`, `batch.dispatch` counts every
batch, and the servable's `startup.conv` stamp names the path. A program
without a path in that stamp (every other family's, whose stamp is empty; a
commit before ISSUE 63, which has no such stamp) reads nothing; one whose
stamp says `xla` (shapes that are no whole blocks) reads 0.0; a window without
a batch reads nothing."""
from _lib import phase_count


def read(ctx):
    stamps = (ctx["runtime"].get("startup") or {}).get("conv") or {}
    batches = phase_count(ctx, "batch.dispatch")
    if not any("path" in stamp for stamp in stamps.values()) or not batches:
        return None
    return 100.0 * phase_count(ctx, "batch.conv_kernel") / batches
