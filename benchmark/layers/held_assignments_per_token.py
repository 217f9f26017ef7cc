"""Kernels (the routed layer): the (token, held expert) pairs that the blocks
of the grouped product took through an expert, a live token and routed layer,
over the window: the step's own counters `moe.assignments_here` over
`moe.tokens`, which the served step sums on the device and the completer
records as phases by count (`models/pangu_moe.py`, `serving/batcher.py`
`_complete`). `assignments_here` is counted INSIDE the expert loops, from the
rows each block gathered, so it follows the work: top_k x held / routed under
even routing (0.25 for 8 x 8 / 256), less where a loop was skipped or cut
short, 0 for a step that routed and ran no block. A padded row's tokens are in
neither counter. None where the program counts no such thing (a family
without a routed layer; the commit before ISSUE 35)."""
from _lib import phase_count


def read(ctx):
    tokens = phase_count(ctx, "moe.tokens")
    return phase_count(ctx, "moe.assignments_here") / tokens if tokens else None
