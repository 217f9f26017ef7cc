"""Kernels (the attention's sink): the share of a query's softmax that the
learned sink of a window layer took, in percent, averaged over the window's
(live row, sink layer) pairs: the step's own counters `attn.sink_mass_ppm`
over `attn.sink_rows`, over 1e4. The served step sums them on the device and
the completer records them as phases by count (`models/mimo_v2.py`,
`serving/batcher.py` `_complete`): `sink_mass_ppm` is `exp(b_h - m) /
denominator` averaged over the heads and the queries the step computed of a
pair, in parts per million, made where the softmax ran (the kernel's own
running maximum and sum where it serves); `sink_rows` counts the pairs from
the layers whose tree holds a sink. So a step whose softmax leaves the sink
out reads 0.0, and one that gives it to another head reads another number
than the reference's `sink_mass_pct`. None where the program counts no such
thing (every other family; the commit before ISSUE 50)."""
from _lib import phase_count


def read(ctx):
    rows = phase_count(ctx, "attn.sink_rows")
    return phase_count(ctx, "attn.sink_mass_ppm") / rows / 1e4 if rows else None
