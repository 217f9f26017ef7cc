"""pangu_moe: openPangu-Ultra-MoE-718B (`model_type: pangu_ultra_moe`; the
Pangu Ultra MoE report) as a pointwise sequence ranker, through the same
Predict path and wire contract as `phi4flash`: a candidate row is
`num_fields` token ids (`feat_ids [n, L]`, folded by `% vocab_size`),
`feat_wts [n, L]` multiplies the token's embedding (`x0_t = w_t * E[id_t]`,
float32 on the link and in the product), and `prediction_node [n]` is the
sigmoid of one logit read at the last position.

A layer has a norm after each sub-layer as well as before it
(`sandwich_norm`), four learned RMSNorm weights:

  h = x + RMS_post_attn(MLA(RMS_in(x)));   y = h + RMS_post_mlp(FFN(RMS_pre_mlp(h)))

MLA, multi-head latent attention: queries and keys/values through low-rank
bottlenecks with their own RMSNorms, and a rotary part of the keys that all
heads share:

  c_q = RMS(x W_qa);  [q_nope, q_rope] = c_q W_qb              a head: nope + rope wide
  [c_kv, k_r] = x W_kva;  [k_nope, v] = RMS(c_kv) W_kvb        a head: nope + v wide
  q = [q_nope, rot(q_rope)];  k = [k_nope, rot(k_r)]           ONE k_r for every head
  o = softmax(q k' / sqrt(nope + rope) + causal mask) v;  MLA = concat(o) W_o

`rot` turns the pairs (i, i + rope/2) of a vector at position t by the angles
`t * theta ** (-2i / rope)` (a permutation of columns away from any other
pairing: under seeded random weights the same model). No biases.

FFN of the `first_k_dense_replace` leading layers: `(silu(x W_g) * (x W_u)) W_d`.
Of the others: the shared expert of that form, whole, plus the routed layer:

  s = sigmoid(x W_r) over ALL n_routed_experts, in float32;   top-k of s, no groups
  g = the chosen s normalised to sum 1, times routed_scaling_factor
  routed = sum over the chosen e of g_e * expert_e(x)

**The share.** This chip holds what one chip of a stated deployment holds of
a layer: `experts_held` of the routed experts, from `first_expert_held` on,
and `num_attention_heads` of the published heads (their slice of W_qb, W_kvb
and W_o); W_qa, W_kva, the norms, the router and the shared expert whole. The
routed layer routes over all the experts and computes `g_e * expert_e(x)` for
the held `e` only; what the absent experts and heads would have added is left
out, and that partial result goes on to the next layer. Nothing stands in for
the other chips or for their traffic. (`tests/test_pangu_moe.py`: the shares'
parts, the shared expert counted once, add up to the uncut layer.)

The held experts' part is a grouped product: for each held expert the tokens
routed to it are gathered EXPERT_BLOCK at a time, as many blocks as its load
takes (a loop whose length the routing decides), through the expert's gated
MLP and added back into their rows times their gates. No token is dropped
whatever the routing; a block is padded to its size, so the work follows the
loads rounded up.

What the served step skips (exact, as `phi4flash`'s): the score reads the
last position, so the LAST layer's queries, attention output and FFN are
computed there alone; its keys and values, and every layer before it, at all
positions.

A row whose weights are all zero (a padded row) is zero at every position of
every layer (no biases), so every expert's part of it is zero: its tokens
are left out of the grouped product and of the counters, exactly. (Its
router scores are all a half, and `top_k` would hand all of them to experts
0 .. top_k - 1.)

The step counts its routing on the device (`STEP_STATS`, summed over the
routed layers): (live token, routed layer) pairs, the (token, held expert)
pairs that the blocks of the grouped product took through an expert, and the
most that one held expert took. The last two are counted INSIDE the expert
loops, from the rows a block gathered: a step that routed and then skipped or
cut short a loop reads low. `Model.apply_stats` returns them beside the
outputs; the batcher carries them back with the scores (serving/batcher.py
`_build_entry`).

Numerics: parameters and matmul operands in `compute_dtype` (bfloat16 as
served), float32 accumulation, residual, norms, rotary and softmax; a float32
activation enters a product as OPERAND_PIECES pieces of the compute dtype
(models/sequence.py). THREE pieces here, where `phi4flash` takes two: three
bfloat16 pieces hold a float32 value whole, and a routed model needs them.
With two, what a product drops (2 ** -17 of its operand) moves a router score
far enough that 1 (token, routed layer) pair in 10,000 chooses another top-k
set than float32 does; where one of the two experts is held here that token's
row jumps, the last position sees it through the attention, and a score
moves by up to 2e-4: as far as an all-bfloat16 step's least error (PERF.md
section 6, PR 35). The router's product and its top-k are float32 at
`highest` precision whatever the compute dtype.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import sequence
from .base import Model, ModelConfig, register_model
from .embeddings import embedding_init, field_embed

INIT_STD = 0.02  # matrices, the embedding and the score vector
# Tokens a block of the grouped product takes through a held expert: enough
# rows to fill the MXU against the expert's weights, few enough that the
# padding of an expert's last block stays under its mean load.
EXPERT_BLOCK = 256
# Pieces of the compute dtype a wider activation enters a product as: read at
# every call of `_product` (models/sequence.py has the product itself). Three
# bfloat16 pieces are the float32 value; the module's text says why not two.
OPERAND_PIECES = 3
STEP_STATS = ("moe.tokens", "moe.assignments_here", "moe.busiest_expert_tokens")


def layer_plan(config: ModelConfig) -> tuple[str, ...]:
    dense = config.first_k_dense_replace
    if not 0 <= dense <= config.num_hidden_layers:
        raise ValueError(
            f"first_k_dense_replace {dense} of num_hidden_layers {config.num_hidden_layers}")
    return ("dense",) * dense + ("moe",) * (config.num_hidden_layers - dense)


def _sizes(config: ModelConfig) -> dict[str, int]:
    experts = config.n_routed_experts
    held = config.experts_held or experts
    first = config.first_expert_held
    heads = config.num_attention_heads
    if config.qk_rope_head_dim % 2:
        raise ValueError(f"qk_rope_head_dim {config.qk_rope_head_dim}: the rotary part turns pairs")
    if not 0 < config.num_experts_per_tok <= experts:
        raise ValueError(f"num_experts_per_tok {config.num_experts_per_tok} of {experts} routed experts")
    if first < 0 or first + held > experts or experts % held:
        raise ValueError(
            f"experts_held {held} from first_expert_held {first} of n_routed_experts {experts}: "
            "a contiguous range of the routed experts, of a size that divides them")
    return {
        "hidden": config.embed_dim, "inter": config.intermediate_size, "heads": heads,
        "q_rank": config.q_lora_rank, "kv_rank": config.kv_lora_rank,
        "nope": config.qk_nope_head_dim, "rope": config.qk_rope_head_dim, "v": config.v_head_dim,
        "expert": config.moe_intermediate_size, "experts": experts, "held": held, "first": first,
        "top_k": config.num_experts_per_tok,
    }


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _matrix(rng, shape, dtype):
    return jax.random.normal(rng, shape, dtype) * jnp.asarray(INIT_STD, dtype)


def _gated_init(rng, shape_in: tuple, shape_out: tuple, dtype) -> dict:
    k_gate, k_up, k_down = jax.random.split(rng, 3)
    return {"gate": _matrix(k_gate, shape_in, dtype), "up": _matrix(k_up, shape_in, dtype),
            "down": _matrix(k_down, shape_out, dtype)}


def _layer_init(rng, kind: str, s: dict, dtype) -> dict:
    k_qa, k_qb, k_kva, k_kvb, k_o, k_mlp, k_router, k_experts = jax.random.split(rng, 8)
    hidden, heads = s["hidden"], s["heads"]
    ones = lambda width: jnp.ones((width,), dtype)  # noqa: E731
    layer = {
        "in_norm": ones(hidden), "post_attn_norm": ones(hidden),
        "pre_mlp_norm": ones(hidden), "post_mlp_norm": ones(hidden),
        "attn": {
            "q_a": _matrix(k_qa, (hidden, s["q_rank"]), dtype), "q_a_norm": ones(s["q_rank"]),
            "q_b": _matrix(k_qb, (s["q_rank"], heads * (s["nope"] + s["rope"])), dtype),
            "kv_a": _matrix(k_kva, (hidden, s["kv_rank"] + s["rope"]), dtype),
            "kv_a_norm": ones(s["kv_rank"]),
            "kv_b": _matrix(k_kvb, (s["kv_rank"], heads * (s["nope"] + s["v"])), dtype),
            "o": _matrix(k_o, (heads * s["v"], hidden), dtype),
        },
    }
    if kind == "dense":
        layer["mlp"] = _gated_init(k_mlp, (hidden, s["inter"]), (s["inter"], hidden), dtype)
    else:
        width, held = s["expert"], s["held"]
        layer["router"] = _matrix(k_router, (hidden, s["experts"]), dtype)
        layer["shared"] = _gated_init(k_mlp, (hidden, width), (width, hidden), dtype)
        layer["experts"] = _gated_init(
            k_experts, (held, hidden, width), (held, width, hidden), dtype)
    return layer


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def _product(spec: str, x: jax.Array, y: jax.Array, cd) -> jax.Array:
    """einsum(spec, x, y) as `sequence.product`, at this family's pieces."""
    return sequence.product(spec, x, y, cd, OPERAND_PIECES)


def _dot(x: jax.Array, w: jax.Array, cd) -> jax.Array:
    """`x [..., k]` times the weight `w [k, n]`, float32: the pieces of `x`
    stacked into ONE product, so that the weight is read once a product and
    the executable holds one product where it held one a piece (a third of
    its code: the ladder's executables have to fit the compile cache)."""
    stacked = jnp.stack(sequence.pieces(x, cd, OPERAND_PIECES))
    return jnp.sum(jnp.einsum("p...k,kn->p...n", stacked, w.astype(cd), preferred_element_type=jnp.float32), axis=0)


def _rms_norm(w: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _gated_mlp(p: dict, x: jax.Array, cd) -> jax.Array:
    return _dot(jax.nn.silu(_dot(x, p["gate"], cd)) * _dot(x, p["up"], cd), p["down"], cd)


def rope_table(length: int, width: int, theta: float) -> tuple[np.ndarray, np.ndarray]:
    """cos and sin `[length, width / 2]` of the angles `t * theta ** (-2i / width)`,
    made in float64 and held as float32 constants of the step."""
    frequency = theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    angles = np.arange(length, dtype=np.float64)[:, None] * frequency[None, :]
    return np.cos(angles).astype(np.float32), np.sin(angles).astype(np.float32)


def rotate(x: jax.Array, cos, sin) -> jax.Array:
    """The rotary turn of `x [..., d]` by `cos`, `sin` (broadcast against
    `[..., d / 2]`): pairs (i, i + d/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def latent_attention(p: dict, a: jax.Array, s: dict, cd, eps: float, theta: float,
                     last_only: bool = False) -> jax.Array:
    """MLA of the normed input `a [n, L, H]` over the heads held: `[n, L, H]`,
    or `[n, 1, H]` for the last position's query alone (keys and values at
    all positions either way). The caller's `attn` scope."""
    n, length, _ = a.shape
    heads, nope, rope, v_dim, rank = s["heads"], s["nope"], s["rope"], s["v"], s["kv_rank"]
    with jax.named_scope("kv_latent"):
        latent = _dot(a, p["kv_a"], cd)
        k_rope = latent[..., rank:]
        kv = _dot(_rms_norm(p["kv_a_norm"], latent[..., :rank], eps), p["kv_b"], cd)
        kv = kv.reshape(n, length, heads, nope + v_dim)
        k_nope, values = kv[..., :nope], kv[..., nope:]
    if last_only:
        a = sequence.last_position(a)
    queries = a.shape[1]
    with jax.named_scope("q_latent"):
        q = _dot(_rms_norm(p["q_a_norm"], _dot(a, p["q_a"], cd), eps), p["q_b"], cd)
        q = q.reshape(n, queries, heads, nope + rope)
        q_nope, q_rope = q[..., :nope], q[..., nope:]
    with jax.named_scope("rope"):
        cos, sin = rope_table(length, rope, theta)
        q_rope = rotate(q_rope, cos[length - queries:, None, :], sin[length - queries:, None, :])
        k_rope = rotate(k_rope, cos, sin)
    with jax.named_scope("softmax"):
        scale = (nope + rope) ** -0.5
        out = []
        for start, stop, first, last in sequence.query_blocks(queries, length):
            scores = (
                _product("nqhd,nkhd->nhqk", q_nope[:, start:stop], k_nope[:, first:last], cd)
                + _product("nqhd,nkd->nhqk", q_rope[:, start:stop], k_rope[:, first:last], cd)
            ) * scale
            probs = sequence.causal_softmax(scores, length - queries + start - first)
            out.append(_product("nhqk,nkhd->nqhd", probs, values[:, first:last], cd))
        o = out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)
    return _dot(o.reshape(n, queries, heads * v_dim), p["o"], cd)


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


def held_experts(p: dict, x: jax.Array, chosen: jax.Array, gates: jax.Array, first: int, cd,
                 block: int = EXPERT_BLOCK, live: jax.Array | None = None):
    """The held experts' part of the routed layer for tokens `x [T, H]`:
    `sum over held e chosen by the token of g_e * expert_e(x)`, `[T, H]`
    float32, and the tokens each held expert's blocks took through it,
    `[held]` int32, counted where they were gathered. `p` holds the experts
    `first .. first + held - 1` stacked; `chosen` and `gates` are the
    router's `[T, k]`; `live [T]` is false for the tokens left out (a padded
    row's: their part is zero). The caller's `experts` scope."""
    tokens, held = x.shape[0], p["gate"].shape[0]
    padded = -(-tokens // block) * block
    with jax.named_scope("dispatch"):
        mine = (chosen - first)[:, :, None] == jnp.arange(held)[None, None, :]  # [T, k, held]
        gate_of = jnp.sum(jnp.where(mine, gates[:, :, None], 0.0), axis=1)  # [T, held]
        routed_here = jnp.any(mine, axis=1)  # [T, held]
        if live is not None:
            routed_here &= live[:, None]
        blocks = (jnp.sum(routed_here, axis=0, dtype=jnp.int32) + block - 1) // block
        # A held expert's tokens first, in row order; then rows past the end,
        # which a gather clips and a scatter drops.
        orders = [
            jnp.nonzero(routed_here[:, e], size=padded, fill_value=tokens)[0] for e in range(held)
        ]
    out, took = jnp.zeros(x.shape, jnp.float32), []
    for e in range(held):
        expert = {name: w[e] for name, w in p.items()}

        def body(i, carry, e=e, expert=expert):
            out, took = carry
            rows = jax.lax.dynamic_slice(orders[e], (i * block,), (block,))
            with jax.named_scope("grouped"):
                y = _gated_mlp(expert, x.at[rows].get(mode="clip"), cd)
            with jax.named_scope("combine"):
                gate = gate_of[:, e].at[rows].get(mode="fill", fill_value=0.0)
                return (out.at[rows].add(y * gate[:, None], mode="drop"),
                        took + jnp.sum(rows < tokens, dtype=jnp.int32))

        out, took_e = jax.lax.fori_loop(0, blocks[e], body, (out, jnp.int32(0)))
        took.append(took_e)
    return out, jnp.stack(took)


def routed_ffn(layer: dict, a: jax.Array, s: dict, scaling: float, cd, live: jax.Array | None = None):
    """shared(a) + the held experts' part, `a`'s shape `[n, positions, H]`;
    and this layer's counters, int32 `[len(STEP_STATS)]`. `live [n]` is false
    for the rows that are zero throughout."""
    x = a.reshape(-1, a.shape[-1])
    if live is not None:
        live = jnp.repeat(live, a.shape[1])
    chosen, gates, _ = route(layer["router"], x, s["top_k"], scaling)
    with jax.named_scope("shared_expert"):
        shared = _gated_mlp(layer["shared"], x, cd)
    with jax.named_scope("experts"):
        routed, took = held_experts(layer["experts"], x, chosen, gates, s["first"], cd, live=live)
    tokens = jnp.int32(x.shape[0]) if live is None else jnp.sum(live, dtype=jnp.int32)
    return (shared + routed).reshape(a.shape), jnp.stack([tokens, jnp.sum(took), jnp.max(took)])


def forward(config: ModelConfig, params, batch) -> tuple[jax.Array, jax.Array]:
    """(the logit of every row, the step's counters): the last layer's
    queries, attention output and FFN at the last position alone."""
    s, cd, eps = _sizes(config), config.cdtype, config.layer_norm_eps
    plan = layer_plan(config)
    with jax.named_scope("embed"):
        # The weighted embedding in float32, where a bfloat16 row times a
        # float32 weight is exact: the first norm reads no rounding either.
        x = field_embed(params["embedding"], batch["feat_ids"], batch["feat_wts"], jnp.float32, s["hidden"])
        live = jnp.any(batch["feat_wts"] != 0, axis=1)  # a padded row is zero throughout
    stats = jnp.zeros((len(STEP_STATS),), jnp.int32)
    for i, (kind, layer) in enumerate(zip(plan, params["layers"])):
        last = i == len(plan) - 1
        with jax.named_scope("attn"):
            mix = latent_attention(
                layer["attn"], _rms_norm(layer["in_norm"], x, eps), s, cd, eps, config.rope_theta, last)
        if last:
            x = sequence.last_position(x)
        h = x + _rms_norm(layer["post_attn_norm"], mix, eps)
        a = _rms_norm(layer["pre_mlp_norm"], h, eps)
        if kind == "dense":
            with jax.named_scope("dense_mlp"):
                ffn = _gated_mlp(layer["mlp"], a, cd)
        else:
            ffn, counts = routed_ffn(layer, a, s, config.routed_scaling_factor, cd, live)
            stats = stats + counts
        x = h + _rms_norm(layer["post_mlp_norm"], ffn, eps)
    with jax.named_scope("score"):
        final = _rms_norm(params["final_norm"], x[:, -1], eps)
        return jnp.sum(final * params["score"].astype(jnp.float32), axis=-1), stats


@register_model("pangu_moe")
def build_pangu_moe(config: ModelConfig) -> Model:
    s = _sizes(config)
    plan = layer_plan(config)

    def init(rng, packed: bool = False):
        k_emb, k_score, *k_layers = jax.random.split(rng, 2 + len(plan))
        dtype = config.pdtype
        # embedding_init scales by 1/sqrt(dim); INIT_STD is wanted.
        table = embedding_init(k_emb, config.vocab_size, s["hidden"], dtype, packed)
        return {
            "embedding": table * jnp.asarray(INIT_STD * s["hidden"] ** 0.5, dtype),
            "layers": [_layer_init(k, kind, s, dtype) for k, kind in zip(k_layers, plan)],
            "final_norm": jnp.ones((s["hidden"],), dtype),
            "score": _matrix(k_score, (s["hidden"],), dtype),
        }

    def apply_stats(params, batch):
        logits, stats = forward(config, params, batch)
        return {"prediction_node": jax.nn.sigmoid(logits), "logits": logits}, stats

    def apply(params, batch):
        return apply_stats(params, batch)[0]

    # The stamp alone reads the published head count and the chips of a layer.
    heads_published = config.num_attention_heads_published or s["heads"]
    if heads_published % s["heads"]:
        raise ValueError(
            f"num_attention_heads {s['heads']} of num_attention_heads_published {heads_published}: "
            "the heads held are a whole share of the published")
    expert_plan = (
        ("published", s["experts"]), ("held", s["held"]), ("first", s["first"]),
        ("top_k", s["top_k"]), ("heads_published", heads_published),
        ("heads_held", s["heads"]), ("chips_sharing_layer", s["experts"] // s["held"]),
    )
    # The weights cross as float32, as phi4flash's and for its reason: a
    # token's weight scales its embedding in the residual stream.
    return Model(
        config=config, init=init, apply=apply, wts_in_compute_dtype=False, layer_plan=plan,
        expert_plan=expert_plan, apply_stats=apply_stats, step_stats=STEP_STATS)
