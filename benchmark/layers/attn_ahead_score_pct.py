"""Kernels (the attention): of the (query, key) score entries the step's masks
kept, the share with the key AFTER the query, over the window: 100 x
`attn.scores_ahead` / `attn.scores_seen`, the step's own counters, which the
served step sums over its layers and live rows and the completer records as
phases by count (`models/sdar_moe.py`, `serving/batcher.py` `_complete`). A
block mask of B positions over rows of L whole blocks keeps `L (B - 1) / 2`
such pairs a row and layer at all positions beside the causal `L (L + 1) / 2`
(0.146% at L 2,048 and B 4: 3,072 of 2,101,248) and none for a lone last
query. The counters come from the shapes and the `span` the family ASKED the
attention for (`sequence.ahead_pairs`), not from what a kernel masked: the
number says which mask was asked for (0.0 where the family asked for the
causal one, `span` None or 1) and is a constant of the cell's shapes, so it
neither rises nor falls with the program's speed. What holds the mask that
was SERVED is the comparison that decides `correct` (the causal mask in the
block mask's place reads 2e-2 at 2,048 tokens, fifty times the limit) and the
CPU tests of the blocks and the kernel against a dense mask. None where the
program counts no such thing (every causal family; a commit before ISSUE 64)."""
from _lib import phase_count


def read(ctx):
    if "attn.scores_ahead" not in ctx["phases"]:
        return None
    seen = phase_count(ctx, "attn.scores_seen")
    return 100.0 * phase_count(ctx, "attn.scores_ahead") / seen if seen else None
