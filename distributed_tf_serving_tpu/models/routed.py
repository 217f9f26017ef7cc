"""What the routed sequence-ranker families (pangu_moe, exaone_moe, mimo_v2) share
beside `sequence`'s products and blocks: the product with a weight under its
own name (`dot`: `sequence.product` against a weight, the three families'
three pieces its stacked form), the RMSNorm, the gated
MLP, the rotary turn, the sigmoid router and the held experts' grouped
product with its counters. One implementation, so that a change to any of
them is measured on all three families' cells, whose hidden sizes (7680, 6144,
4096) and loads an expert (256 tokens a step, 512, 256) differ.

Every product takes the number of pieces (`count`) from its caller: a family
keeps its own `OPERAND_PIECES` and hands it on at every call, so that its
tests and the benchmark's precision readings plant the precision below the
stated one by that one name.

**The share.** The routed layer is told which experts it holds (`first` and
the leading size of the experts' arrays): it routes over ALL the experts and
computes `g_e * expert_e(x)` for the held `e` only. What the absent experts
would have added is left out, and nothing stands in for the other chips.

The held experts' part is a grouped product (`held_experts`). Where a one-chip
served entry runs on a TPU (`takes_kernel`) it is ONE pass of Pallas kernels
over row tiles of 128, each of which finds its expert's weights
(ops/grouped_kernel.py): the pairs laid out once, expert by expert, the gated
rows added back into their tokens' rows in place. Everywhere else (every CPU
run, the GSPMD executors, `shard_map_score`, the trainer: a `tpu_custom_call`
neither partitions nor has a gradient) it is the plain form the kernels are
tested against: for each held expert the tokens routed to it are gathered
EXPERT_BLOCK at a time, as many blocks as its load takes (a loop whose length
the routing decides), through the expert's gated MLP and added back into
their rows times their gates. No token is dropped whatever the routing, on
either path: there is no capacity; a tile or a block is padded to its size,
so the work follows the loads rounded up.

The step counts its routing on the device (`STEP_STATS`, summed over the
routed layers): (live token, routed layer) pairs, the (token, held expert)
pairs that the tiles or blocks of the grouped product took through an expert,
the most that one held expert took, and the rows that were computed for them,
padding and all. The last three are counted where the work is done, from the
rows a tile's copies were started for or a block gathered: a step that routed
and then skipped or cut short a tile or a loop reads low.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from . import sequence

INIT_STD = 0.02  # matrices, the embedding and the score vector
# Tokens a block of XLA's loops takes through a held expert (the path that
# serves wherever the grouped kernels do not, whose tile is their own): enough
# rows to fill the MXU against the expert's weights, few enough that the
# padding of an expert's last block stays under its mean load.
EXPERT_BLOCK = 256
STEP_STATS = ("moe.tokens", "moe.assignments_here", "moe.busiest_expert_tokens", "moe.rows_computed")


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


def route(router: jax.Array, x: jax.Array, top_k: int, scaling: float):
    """(the chosen experts `[T, k]`, their gates `[T, k]`, every expert's
    score `[T, E]`) for tokens `x [T, H]`: sigmoid scores over all the routed
    experts, the k largest, normalised to sum 1 and scaled. float32 at
    `highest` precision whatever the compute dtype."""
    with jax.named_scope("router"):
        scores = jax.nn.sigmoid(jnp.einsum(
            "th,he->te", x, router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32))
        top, chosen = jax.lax.top_k(scores, top_k)
        return chosen, top / jnp.sum(top, axis=-1, keepdims=True) * scaling, scores


def grouped_choice(count: int) -> dict:
    """`{"kernel": "pallas" | "xla", "tile", "pieces"}`: which path serves
    the held experts of a routed layer, the rows of a tile (of a block, where
    XLA's loops run) and the pieces an activation enters its products as. A servable's `startup.grouped` stamp. The kernels
    (ops/grouped_kernel.py) run where a served entry's kernels do
    (`sequence.kernels_run`): inside the batcher's one-chip entry on a TPU,
    at every token count (the last layer's 4-8 tokens too, a third of the
    loops' time on the chip: no rule by tokens, so none is an argument)."""
    if not sequence.kernels_run():
        return {"kernel": "xla", "tile": EXPERT_BLOCK, "pieces": count}
    from ..ops.grouped_kernel import TILE

    return {"kernel": "pallas", "tile": TILE, "pieces": count}


def takes_kernel(count: int) -> bool:
    """Whether the kernels serve this routed layer (grouped_choice has the
    rule), noted for the served entry being traced."""
    choice = grouped_choice(count)
    served = sequence.served_entry()
    if served is not None and served.grouped is not None and choice not in served.grouped:
        served.grouped.append(choice)
    return choice["kernel"] == "pallas"


def held_experts(p: dict, x: jax.Array, chosen: jax.Array, gates: jax.Array, first: int, cd,
                 block: int = EXPERT_BLOCK, live: jax.Array | None = None, *, count: int):
    """The held experts' part of the routed layer for tokens `x [T, H]`:
    `sum over held e chosen by the token of g_e * expert_e(x)`, `[T, H]`
    float32; the tokens each held expert's rows took through it, `[held]`
    int32, counted where they were gathered; and the rows that were computed
    for them, padding and all. `p` holds the experts `first .. first + held - 1`
    stacked; `chosen` and `gates` are the router's `[T, k]`; `live [T]` is
    false for the tokens left out (a padded row's: their part is zero). The
    caller's `experts` scope.

    One pass of ops/grouped_kernel.py over tiles that find their expert's
    weights where `takes_kernel` says so (`block` is then the kernel's own
    tile); else a loop an expert over blocks of `block` rows."""
    tokens, held = x.shape[0], p["gate"].shape[0]
    kernel = takes_kernel(count)
    if kernel:
        from ..ops import grouped_kernel

        block = grouped_kernel.TILE
    padded = -(-tokens // block) * block
    with jax.named_scope("dispatch"):
        mine = (chosen - first)[:, :, None] == jnp.arange(held)[None, None, :]  # [T, k, held]
        gate_of = jnp.sum(jnp.where(mine, gates[:, :, None], 0.0), axis=1)  # [T, held]
        routed_here = jnp.any(mine, axis=1)  # [T, held]
        if live is not None:
            routed_here &= live[:, None]
        loads = jnp.sum(routed_here, axis=0, dtype=jnp.int32)
        # A held expert's tokens first, in row order; then rows past the end,
        # which a gather clips and a scatter drops (the kernels read none).
        orders = [
            jnp.nonzero(routed_here[:, e], size=padded, fill_value=tokens)[0] for e in range(held)
        ]
    if kernel:
        return grouped_kernel.grouped_experts(
            *(p[name].astype(cd) for name in ("gate", "up", "down")), x, gate_of,
            jnp.stack(orders).astype(jnp.int32), loads, cd=jnp.dtype(cd), count=count, tile=block,
            interpret=sequence.served_entry().interpret)
    blocks = (loads + block - 1) // block
    out, took, ran = jnp.zeros(x.shape, jnp.float32), [], jnp.int32(0)
    for e in range(held):
        expert = {name: w[e] for name, w in p.items()}

        def body(i, carry, e=e, expert=expert):
            out, took, ran = carry
            rows = jax.lax.dynamic_slice(orders[e], (i * block,), (block,))
            with jax.named_scope("grouped"):
                y = gated_mlp(expert, x.at[rows].get(mode="clip"), cd, count)
            with jax.named_scope("combine"):
                gate = gate_of[:, e].at[rows].get(mode="fill", fill_value=0.0)
                return (out.at[rows].add(y * gate[:, None], mode="drop"),
                        took + jnp.sum(rows < tokens, dtype=jnp.int32), ran + block)

        out, took_e, ran = jax.lax.fori_loop(0, blocks[e], body, (out, jnp.int32(0), ran))
        took.append(took_e)
    return out, jnp.stack(took), ran


def routed_ffn(layer: dict, a: jax.Array, top_k: int, first: int, scaling: float, cd, count: int,
               live: jax.Array | None = None, router=None, experts=None):
    """shared(a) + the held experts' part, `a`'s shape `[n, positions, H]`
    (the held experts' part alone where the layer has no `shared` expert:
    mimo_v2); and this layer's counters, int32 `[len(STEP_STATS)]`. `live [n]` is false
    for the rows that are zero throughout. `router` and `experts` stand for
    this module's `route` and `held_experts` (at `count` pieces) where a family
    hands in its own names for them (pangu_moe, whose tests plant faults under
    those)."""
    experts = experts or functools.partial(held_experts, count=count)
    x = a.reshape(-1, a.shape[-1])
    if live is not None:
        live = jnp.repeat(live, a.shape[1])
    chosen, gates, _ = (router or route)(layer["router"], x, top_k, scaling)
    with jax.named_scope("shared_expert"):
        shared = gated_mlp(layer["shared"], x, cd, count) if "shared" in layer else None
    with jax.named_scope("experts"):
        routed, took, computed = experts(layer["experts"], x, chosen, gates, first, cd, live=live)
    tokens = jnp.int32(x.shape[0]) if live is None else jnp.sum(live, dtype=jnp.int32)
    out = routed if shared is None else shared + routed
    return out.reshape(a.shape), jnp.stack([tokens, jnp.sum(took), jnp.max(took), computed])
