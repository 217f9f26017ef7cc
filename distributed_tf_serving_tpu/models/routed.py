"""What the routed sequence-ranker families (pangu_moe, exaone_moe, mimo_v2,
qwen3_next, nemotron_h, sdar_moe) share beside `sequence`'s products and blocks: the
product with a weight under its own name (`dot`: `sequence.product` against a
weight, the families' three pieces its stacked form), the RMSNorm, an expert
of either form (the gated MLP, `silu(x G) * (x U)` through `D`: five
families'; the ungated one, `relu(x U)^2 D`: nemotron_h's; `expert_form`
reads which from the expert's own tree, a `gate` leaf or none), the rotary
turn, the router (sigmoid scores or a softmax, as the family says; with a
selection bias where the family has one) and the held experts' grouped
product with its counters. One implementation, so that a change to any of
them is measured on all six families' cells, whose rows into the experts
(the residual's 7680, 6144, 4096, 2048; nemotron_h's a LATENT's 1024 under a
residual of 4096; sdar_moe's 2048), held experts (8, 8, 8, 128 of 512, 64 of
512, and sdar_moe's 128 of 128: the one layer held WHOLE, where every one of a
token's choices is here), experts a token (8, 8, 8, 10, 22, 8), their widths
(2048, 2048, 2048, 512, 2688, 768) and loads an expert (256 tokens a step,
512, 256, 320, 704, and a mean of 1,024, a deployment's mean, skewed five
times over under seeded weights) differ.

Every product takes the number of pieces (`count`) from its caller: a family
keeps its own `OPERAND_PIECES` and hands it on at every call, so that its
tests and the benchmark's precision readings plant the precision below the
stated one by that one name.

**The share.** The routed layer is told which experts it holds (`first` and
the leading size of the experts' arrays): it routes over ALL the experts and
computes `g_e * expert_e(x)` for the held `e` only. What the absent experts
would have added is left out, and nothing stands in for the other chips.

The held experts' part is a grouped product (`held_experts`). The layer's
(token, held expert) pairs are laid out ONCE, whatever `held` is (`lay_out`:
one sort of the `[T x k]` pairs by expert and token), expert by expert in
tiles of whole rows, an expert's last tile padded: at most `T x min(k, held)`
rows and a tile an expert, since a token is on at most k experts. Where a
one-chip served entry runs on a TPU (`takes_kernel`) ONE pass of Pallas
kernels walks the tiles of 128 rows, each of which finds its expert's weights
(ops/grouped_kernel.py), the gated rows added back into their tokens' rows in
place. Everywhere else (every CPU run, the GSPMD executors, `shard_map_score`,
the trainer: a `tpu_custom_call` neither partitions nor has a gradient) it is
the plain form the kernels are tested against: ONE loop over the tiles of
EXPERT_BLOCK rows that hold a token (its length the routing decides), a tile's
tokens gathered, through its expert (`expert_mlp`, of the tree's form) and
added back into their rows times their gates. No token is dropped whatever the
routing, on either path: there is no capacity; a tile is padded to its size,
so the work follows the loads rounded up.

The step counts its routing on the device (`STEP_STATS`, summed over the
routed layers): (live token, routed layer) pairs, the (token, held expert)
pairs that the tiles of the grouped product took through an expert, the most
that one held expert took, the rows that were computed for them, padding and
all, and the held experts that took a token at all. The last four are counted
where the work is done, from the rows a tile's copies were started for or a
tile gathered: a step that routed and then skipped or cut short a tile or the
loop reads low.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from . import sequence

INIT_STD = 0.02  # matrices, the embedding and the score vector
# Rows a tile of XLA's loop takes through a held expert (the path that serves
# wherever the grouped kernels do not, whose tile is their own): enough rows
# to fill the MXU against the expert's weights, few enough that the padding
# of an expert's last tile stays under its mean load.
EXPERT_BLOCK = 256
STEP_STATS = (
    "moe.tokens", "moe.assignments_here", "moe.busiest_expert_tokens", "moe.rows_computed", "moe.experts_hit")
SCORINGS = ("sigmoid", "softmax")


def matrix(rng, shape, dtype):
    return jax.random.normal(rng, shape, dtype) * jnp.asarray(INIT_STD, dtype)


def gated_init(rng, shape_in: tuple, shape_out: tuple, dtype) -> dict:
    k_gate, k_up, k_down = jax.random.split(rng, 3)
    return {"gate": matrix(k_gate, shape_in, dtype), "up": matrix(k_up, shape_in, dtype),
            "down": matrix(k_down, shape_out, dtype)}


def dot(x: jax.Array, w: jax.Array, cd, count: int) -> jax.Array:
    """`x [..., k]` times the weight `w [k, n]`, float32: `sequence.product`
    against the weight rounded to the compute dtype whole, which makes the
    pieces of `x` meet in ONE product (two along a second contracted axis,
    three or more stacked and summed), so that the float32 result is written
    once, the weight is read once a product and the executable holds one
    product where it held one a piece (a third of its code: the ladder's
    executables have to fit the compile cache)."""
    return sequence.product("...k,kn->...n", x, w.astype(cd), cd, count)


def rms_norm(w: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def gated_mlp(p: dict, x: jax.Array, cd, count: int) -> jax.Array:
    return dot(jax.nn.silu(dot(x, p["gate"], cd, count)) * dot(x, p["up"], cd, count), p["down"], cd, count)


def relu2_mlp(p: dict, x: jax.Array, cd, count: int) -> jax.Array:
    """The ungated form, `relu(x U)^2 D`: two matrices an expert."""
    return dot(jnp.square(jax.nn.relu(dot(x, p["up"], cd, count))), p["down"], cd, count)


def expert_form(p: dict) -> str:
    """`gated_silu` or `relu2`: the form of an expert (or of the held experts
    stacked), read from its own tree: a `gate` leaf or none."""
    return "gated_silu" if "gate" in p else "relu2"


def expert_mlp(p: dict, x: jax.Array, cd, count: int) -> jax.Array:
    """An expert of either form over `x` (`expert_form`)."""
    return (gated_mlp if "gate" in p else relu2_mlp)(p, x, cd, count)


def rope_table(length: int, width: int, theta: float) -> tuple[np.ndarray, np.ndarray]:
    """cos and sin `[length, width / 2]` of the angles `t * theta ** (-2i / width)`,
    made in float64 and held as float32 constants of the step."""
    frequency = theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    angles = np.arange(length, dtype=np.float64)[:, None] * frequency[None, :]
    return np.cos(angles).astype(np.float32), np.sin(angles).astype(np.float32)


def rotate(x: jax.Array, cos, sin, width: int | None = None) -> jax.Array:
    """The rotary turn of `x [..., d]` by `cos`, `sin` (broadcast against
    `[..., d / 2]`): pairs (i, i + d/2). With `width`, of the FIRST `width`
    dims alone (pairs (i, i + width/2), `cos` and `sin` against
    `[..., width / 2]`); the others pass unturned."""
    if width is not None and width < x.shape[-1]:
        return jnp.concatenate([rotate(x[..., :width], cos, sin), x[..., width:]], axis=-1)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def check_share(experts: int, held: int, first: int, top_k: int) -> None:
    """Refuse a share the routed layer cannot be cut into."""
    if not 0 < top_k <= experts:
        raise ValueError(f"num_experts_per_tok {top_k} of {experts} routed experts")
    if first < 0 or first + held > experts or experts % held:
        raise ValueError(
            f"experts_held {held} from first_expert_held {first} of {experts} routed experts: "
            "a contiguous range of the routed experts, of a size that divides them")


def route(router: jax.Array, x: jax.Array, top_k: int, scaling: float, scoring: str = "sigmoid",
          normalise: bool = True, bias: jax.Array | None = None):
    """(the chosen experts `[T, k]`, their gates `[T, k]`, every expert's
    score `[T, E]`) for tokens `x [T, H]`: scores over ALL the routed experts
    (`scoring`: a sigmoid an expert, or one softmax over them; a family's
    router is part of its equations, so the family says which), the k
    largest, normalised to sum 1 (where `normalise`) and scaled. With a
    selection `bias [E]` (nemotron_h) the k largest of `scores + bias` are
    chosen and their gates are made from the UNBIASED scores: the bias
    chooses and never weighs. float32 at `highest` precision whatever the
    compute dtype."""
    if scoring not in SCORINGS:
        raise ValueError(f"scoring {scoring!r}: one of {SCORINGS}")
    with jax.named_scope("router"):
        logits = jnp.einsum(
            "th,he->te", x, router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
        scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" else jax.nn.softmax(logits, axis=-1)
        top, chosen = jax.lax.top_k(scores if bias is None else scores + bias.astype(jnp.float32), top_k)
        if bias is not None:
            top = jnp.take_along_axis(scores, chosen, axis=-1)
        if normalise:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        return chosen, top * scaling, scores


def layout_tiles(tokens: int, top_k: int, held: int, tile: int) -> int:
    """The most tiles of `tile` rows a layer's pairs can fill: a token is on
    at most `min(k, held)` held experts, and every held expert pads one tile
    at the most."""
    return tokens * min(top_k, held) // tile + held


def grouped_choice(count: int, tokens: int | None = None, top_k: int = 0, held: int = 0,
                   form: str = "", width: int = 0) -> dict:
    """`{"kernel": "pallas" | "xla", "tile", "pieces", "held", "rows", "form",
    "width"}`: which path serves the held experts of a routed layer, the rows
    of a tile, the pieces an activation enters its products as, the experts
    held, the layout's bound in rows (`layout_tiles`: what the buffer between
    the kernels and the tile table are sized by), the experts' form
    (`expert_form`) and the width of the rows they take and give (the
    residual's, or a latent's); the last four where `tokens`, the layer's,
    are given. A servable's `startup.grouped` stamp. The kernels
    (ops/grouped_kernel.py) run where a served entry's kernels do
    (`sequence.kernels_run`): inside the batcher's one-chip entry on a TPU,
    at every token count (the last layer's 4-8 tokens too, a third of the
    loop's time on the chip: no rule by tokens, so none is an argument)."""
    kernel, tile = sequence.kernels_run(), EXPERT_BLOCK
    if kernel:
        from ..ops.grouped_kernel import TILE as tile
    choice = {"kernel": "pallas" if kernel else "xla", "tile": tile, "pieces": count}
    if tokens is not None:
        choice.update(held=held, rows=layout_tiles(tokens, top_k, held, tile) * tile, form=form, width=width)
    return choice


def takes_kernel(count: int, tokens: int | None = None, top_k: int = 0, held: int = 0,
                 form: str = "", width: int = 0) -> bool:
    """Whether the kernels serve this routed layer (grouped_choice has the
    rule), noted for the served entry being traced."""
    choice = grouped_choice(count, tokens, top_k, held, form, width)
    served = sequence.served_entry()
    if served is not None and served.grouped is not None and choice not in served.grouped:
        served.grouped.append(choice)
    return choice["kernel"] == "pallas"


def lay_out(chosen: jax.Array, first: int, held: int, tile: int, live: jax.Array | None = None):
    """The layer's (token, held expert) pairs laid out for a grouped product,
    whatever `held` is: ONE sort of the `[T x k]` pairs by (expert, token),
    the pairs of experts not held (and of tokens not `live`) last. Returns

      orders  `[tiles, tile]` int32, the token of every row of every tile a
              pass may walk (`layout_tiles` of them): a held expert's tokens
              in row order, each expert's run rounded up to whole tiles; T
              (a row past the end, which a gather clips and a scatter drops)
              where a row holds no token
      expert  `[tiles]` int32, the held expert whose run the tile lies in
      rows    `[tiles]` int32, the rows of the tile that hold a token: the
              tiles that hold one come first, in the experts' order
      walked  how many tiles hold a token

    The caller's `dispatch` scope."""
    tokens, top_k = chosen.shape
    if (held + 1) * tokens >= 1 << 31:
        raise ValueError(f"{tokens} tokens over {held} held experts: the pairs' sort key is an int32")
    local = chosen - first
    mine = (local >= 0) & (local < held)
    if live is not None:
        mine &= live[:, None]
    token = jnp.arange(tokens, dtype=jnp.int32)[:, None]
    key = jnp.sort((jnp.where(mine, local, held).astype(jnp.int32) * tokens + token).reshape(-1))
    # every bound against every pair: no loop of a search's steps in the step
    starts = jnp.searchsorted(
        key, jnp.arange(held + 1, dtype=jnp.int32) * tokens, method="compare_all").astype(jnp.int32)
    loads = starts[1:] - starts[:-1]
    tiles = (loads + tile - 1) // tile
    ends = jnp.cumsum(tiles)
    at = jnp.arange(layout_tiles(tokens, top_k, held, tile), dtype=jnp.int32)
    # The runs that end at or before the tile: its expert (no search: a loop)
    expert = jnp.minimum(jnp.sum(at[:, None] >= ends[None, :], axis=1, dtype=jnp.int32), held - 1)
    within = at - (ends - tiles)[expert]
    rows = jnp.where(at < ends[-1], jnp.clip(loads[expert] - within * tile, 0, tile), 0)
    row = jnp.arange(tile, dtype=jnp.int32)[None, :]
    pair = jnp.minimum(starts[expert][:, None] + within[:, None] * tile + row, key.shape[0] - 1)
    orders = jnp.where(row < rows[:, None], key[pair] % tokens, tokens)
    return orders, expert, rows, ends[-1]


def held_experts(p: dict, x: jax.Array, chosen: jax.Array, gates: jax.Array, first: int, cd,
                 block: int = EXPERT_BLOCK, live: jax.Array | None = None, *, count: int):
    """The held experts' part of the routed layer for tokens `x [T, H]`: `sum
    over held e chosen by the token of g_e * expert_e(x)`, `[T, H]` float32;
    the tokens each held expert's rows took through it, `[held]` int32,
    counted where they were gathered; and the rows that were computed for
    them, padding and all. `p` holds the experts `first .. first + held - 1`
    stacked, of either form (`expert_form`: `gate`, `up`, `down`, or `up` and
    `down` alone), and H is whatever width they take and give: the residual's,
    or a latent's (nemotron_h: 1,024 of whole lanes under a residual of
    4,096); `held` may be ALL the routed experts (sdar_moe: `first` 0, every
    choice a held one, `T x k` pairs); `chosen` and `gates` are the router's `[T, k]`; `live [T]` is
    false for the tokens left out (a padded row's: their part is zero). The
    caller's `experts` scope.

    The pairs are laid out once (`lay_out`). Then one pass of
    ops/grouped_kernel.py over the tiles where `takes_kernel` says so (`block`
    is then the kernel's own tile); else ONE loop over the tiles of `block`
    rows that hold a token, whose expert's weights a tile reads by its
    index."""
    tokens, held = x.shape[0], p["down"].shape[0]
    kernel = takes_kernel(count, tokens, chosen.shape[1], held, expert_form(p), x.shape[1])
    if kernel:
        from ..ops import grouped_kernel

        block = grouped_kernel.TILE
    with jax.named_scope("dispatch"):
        mine = (chosen - first)[:, :, None] == jnp.arange(held)[None, None, :]  # [T, k, held]
        gate_of = jnp.sum(jnp.where(mine, gates[:, :, None], 0.0), axis=1)  # [T, held]
        orders, expert, rows, walked = lay_out(chosen, first, held, block, live)
    if kernel:
        return grouped_kernel.grouped_experts(
            *(p[name].astype(cd) if name in p else None for name in ("gate", "up", "down")), x, gate_of, orders, expert,
            rows, walked,
            cd=jnp.dtype(cd), count=count, tile=block, interpret=sequence.served_entry().interpret)

    def body(i, carry):
        out, took, ran = carry
        e, at = expert[i], orders[i]
        with jax.named_scope("grouped"):
            y = expert_mlp({name: w[e] for name, w in p.items()}, x.at[at].get(mode="clip"), cd, count)
        with jax.named_scope("combine"):
            gate = gate_of.at[at, e].get(mode="fill", fill_value=0.0)
            return (out.at[at].add(y * gate[:, None], mode="drop"),
                    took.at[e].add(jnp.sum(at < tokens, dtype=jnp.int32)), ran + block)

    return jax.lax.fori_loop(
        0, walked, body, (jnp.zeros(x.shape, jnp.float32), jnp.zeros((held,), jnp.int32), jnp.int32(0)))


def routed_ffn(layer: dict, a: jax.Array, top_k: int, first: int, scaling: float, cd, count: int,
               live: jax.Array | None = None, router=None, experts=None):
    """shared(a) + the held experts' part, `a`'s shape `[n, positions, H]`
    (the held experts' part alone where the layer has no `shared` expert:
    mimo_v2; the shared expert times its gate a token, `sigmoid(a . w_sg)`,
    where the layer's tree has a `shared_gate [H]`: qwen3_next); and this
    layer's counters, int32 `[len(STEP_STATS)]`. `live [n]` is false
    for the rows that are zero throughout. `router` and `experts` stand for
    this module's `route` and `held_experts` (at `count` pieces) where a family
    hands in its own names for them (pangu_moe, whose tests plant faults under
    those) or its own scoring (qwen3_next: `route` with a softmax)."""
    experts = experts or functools.partial(held_experts, count=count)
    x = a.reshape(-1, a.shape[-1])
    if live is not None:
        live = jnp.repeat(live, a.shape[1])
    chosen, gates, _ = (router or route)(layer["router"], x, top_k, scaling)
    with jax.named_scope("shared_expert"):
        shared = gated_mlp(layer["shared"], x, cd, count) if "shared" in layer else None
        if "shared_gate" in layer:
            with jax.named_scope("gate"):  # float32, as every gate
                shared = shared * jax.nn.sigmoid(
                    jnp.sum(x * layer["shared_gate"].astype(jnp.float32), axis=-1, keepdims=True))
    with jax.named_scope("experts"):
        routed, took, computed = experts(layer["experts"], x, chosen, gates, first, cd, live=live)
    tokens = jnp.int32(x.shape[0]) if live is None else jnp.sum(live, dtype=jnp.int32)
    out = routed if shared is None else shared + routed
    return out.reshape(a.shape), jnp.stack(
        [tokens, jnp.sum(took), jnp.max(took), computed, jnp.sum(took > 0, dtype=jnp.int32)])
