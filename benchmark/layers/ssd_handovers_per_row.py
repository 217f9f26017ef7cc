"""Kernels (the Mamba-2 mixer's SSD): state hand-overs a live row, over the
window: `ssd.handovers` over `ssd.rows`, the step's own counters, which the
served step sums over its layers and live rows and the completer records as
phases by count (`models/falcon_h1.py`, `serving/batcher.py` `_complete`). It
is the length of the dependent chain a row: each hand-over waits for the one
before it and carries every head's `[P, N]` state (4.19 MB a row at the
published widths) through memory; what lies between two is a chunk's worth of
independent products. 80 for five layers over 2,048 positions in chunks of 128;
position by position it would read 10,240. None where the program counts no
such thing (every other family; the commit before ISSUE 54)."""
from _lib import phase_count


def read(ctx):
    rows = phase_count(ctx, "ssd.rows")
    return phase_count(ctx, "ssd.handovers") / rows if rows else None
