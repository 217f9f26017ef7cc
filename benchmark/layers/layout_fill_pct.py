"""Kernels (the routed layer): how much of the worst-case layout the routing
fills: 100 x `moe.rows_computed` over the layout's bound in rows x the routed
layers x the window's batches. The buffer between the grouped kernels and the
tile table are sized for every token on `min(k, held)` held experts and a tile
of padding an expert (`models/routed.py::layout_tiles`; the servable's
`startup.grouped` stamp states that bound as `rows`, the top rung's), of which
only the tiles that hold a token are written, read or walked
(`moe.rows_computed` counts those, padding and all, where they are walked).
The routed layers are those of the servable's `startup.layer_plan` named
`.../moe` (every layer where a plan names mixers alone), and `batch.dispatch`
counts the batches. A share that holds few of many experts at a large k reads
low: an eighth at 64 of 512 held and top-22, where 8 of 256 at top-8 fill
theirs to an eighth too and 128 of 512 at top-10 to a quarter; a last layer
that routes a few tokens a row counts a whole layout and fills none of it.
None where the program counts no such thing or states no bound (a family
without a routed layer; a commit before ISSUE 58, whose stamp has no `rows`)."""
from _lib import phase_count


def read(ctx):
    startup = ctx["runtime"].get("startup") or {}
    bounds = [g["rows"] for g in (startup.get("grouped") or {}).values() if g and g.get("rows")]
    layers = [p for p in (startup.get("layer_plan") or {}).values() if p]
    computed, batches = phase_count(ctx, "moe.rows_computed"), phase_count(ctx, "batch.dispatch")
    if not computed or not batches or len(bounds) != 1 or len(layers) != 1:
        return None
    named = {kind: n for kind, n in layers[0].items() if kind.endswith("/moe")}
    routed = sum(named.values()) if named else sum(layers[0].values())
    return 100.0 * computed / (bounds[0] * routed * batches)
