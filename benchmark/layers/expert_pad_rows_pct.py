"""Kernels (the routed layer): of the rows the held experts' products computed
over the window, the share that held no token: 100 x (1 -
`moe.assignments_here` / `moe.rows_computed`), both the step's own counters,
summed on the device and recorded by the completer as phases by count. An
expert's run of tokens is rounded up to whole tiles (the grouped kernels, 128
rows) or whole blocks (XLA's loops, 256), and `moe.rows_computed` counts those
where they are walked, so the padding is read and not reckoned from the mean
load and the skew. None where the program counts no such thing (a family
without a routed layer; the commit before ISSUE 51) or computed no row."""
from _lib import phase_count


def read(ctx):
    computed = phase_count(ctx, "moe.rows_computed")
    return 100.0 * (1.0 - phase_count(ctx, "moe.assignments_here") / computed) if computed else None
