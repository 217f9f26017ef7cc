"""Kernels (the attention): the share of the (query, key) score entries that
the step's tiles computed and its masks then threw away, over the window:
100 x (1 - `attn.scores_seen` / `attn.scores_computed`), the step's own
counters, which the served step sums over its layers and live rows and the
completer records as phases by count (`models/exaone_moe.py`,
`serving/batcher.py` `_complete`). A full layer in blocks of 512 queries over
2,048 positions masks a fifth of what it computes, a window of 128 in blocks
of its own size just under a half, the same window in blocks of 512 four
fifths. None where the program counts no such thing (every other family; the
commit before ISSUE 43)."""
from _lib import phase_count


def read(ctx):
    computed = phase_count(ctx, "attn.scores_computed")
    return 100.0 * (1.0 - phase_count(ctx, "attn.scores_seen") / computed) if computed else None
