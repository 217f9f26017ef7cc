"""Kernels (the gated delta rule): state hand-overs a live row, over the
window: `delta.handovers` over `delta.rows`, the step's own counters, which
the served step sums over its linear layers and live rows and the completer
records as phases by count (`models/olmo_hybrid.py`, `serving/batcher.py`
`_complete`). It is the length of the dependent chain a row: each hand-over
waits for the one before it, and what lies between two is a chunk's worth of
independent products. 192 for six linear layers over 2,048 positions in chunks
of 64; position by position it would read 12,288. None where the program counts
no such thing (every other family; the commit before ISSUE 46)."""
from _lib import phase_count


def read(ctx):
    rows = phase_count(ctx, "delta.rows")
    return phase_count(ctx, "delta.handovers") / rows if rows else None
