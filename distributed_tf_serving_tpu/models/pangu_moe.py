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

The routed layer itself (the router, the held experts' grouped product, its
counters) and the blocks beside it (the product of pieces with a weight, the
RMSNorm, the gated MLP, the rotary turn) are `models/routed.py`'s, shared with
`exaone_moe`; the names below that hand this family's `OPERAND_PIECES` on to
them are what its tests and the benchmark's precision readings replace.

What the served step skips (exact, as `phi4flash`'s): the score reads the
last position, so the LAST layer's queries, attention output and FFN are
computed there alone; its keys and values, and every layer before it, at all
positions.

A row whose weights are all zero (a padded row) is zero at every position of
every layer (no biases), so every expert's part of it is zero: its tokens
are left out of the grouped product and of the counters, exactly. (Its
router scores are all a half, and `top_k` would hand all of them to experts
0 .. top_k - 1.)

The step counts its routing on the device (`routed.STEP_STATS`, summed over the
routed layers). `Model.apply_stats` returns the counters beside the outputs;
the batcher carries them back with the scores (serving/batcher.py
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

import jax
import jax.numpy as jnp

from . import routed, sequence
from .base import Model, ModelConfig, register_model
from .embeddings import embedding_init, field_embed
from .routed import EXPERT_BLOCK, INIT_STD, STEP_STATS, rope_table, rotate, route
from .routed import gated_init as _gated_init, matrix as _matrix, rms_norm as _rms_norm

# Pieces of the compute dtype a wider activation enters a product as: read at
# every call of `_product` (models/sequence.py has the product itself). Three
# bfloat16 pieces are the float32 value; the module's text says why not two.
OPERAND_PIECES = 3


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
    routed.check_share(experts, held, first, config.num_experts_per_tok)
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
    """`routed.dot` at this family's pieces."""
    return routed.dot(x, w, cd, OPERAND_PIECES)


def _gated_mlp(p: dict, x: jax.Array, cd) -> jax.Array:
    return routed.gated_mlp(p, x, cd, OPERAND_PIECES)


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
        if sequence.takes_kernel(queries, length, None, OPERAND_PIECES, sequence.Heads((nope, rope), v_dim, 1, cd)):
            # Both score products accumulate in the kernel's one tile; the
            # rotary keys are one head that every query head reads.
            o = sequence.attention(
                (q_nope, q_rope), (k_nope, k_rope[:, :, None]), values, None, cd, OPERAND_PIECES, scale)
        else:
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


def held_experts(p: dict, x: jax.Array, chosen: jax.Array, gates: jax.Array, first: int, cd,
                 block: int = EXPERT_BLOCK, live: jax.Array | None = None):
    """`routed.held_experts` at this family's pieces."""
    return routed.held_experts(p, x, chosen, gates, first, cd, block, live, count=OPERAND_PIECES)


def routed_ffn(layer: dict, a: jax.Array, s: dict, scaling: float, cd, live: jax.Array | None = None):
    """`routed.routed_ffn` at this family's pieces, through this module's
    `route` and `held_experts` (one replaced by name is the one that runs)."""
    return routed.routed_ffn(
        layer, a, s["top_k"], s["first"], scaling, cd, OPERAND_PIECES, live, router=route, experts=held_experts)


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
