"""sdar_moe: SDAR-30B-A3B-Chat (`model_type: sdar_moe`; block diffusion over a
Qwen3-MoE stack, arXiv:2510.06303) as a pointwise sequence ranker, through the
same Predict path and wire contract as the eight sequence families before it:
a candidate row is `num_fields` token ids (`feat_ids [n, L]`, folded by
`% vocab_size`), `feat_wts [n, L]` multiplies the token's embedding
(`x0_t = w_t * E[id_t]`, float32 on the link and in the product), and
`prediction_node [n]` is the sigmoid of one logit read at the last position,
`s = w_score . RMS_final(y_L)`.

The model generates a block of B tokens at a time: the block enters as mask
ids, ONE forward pass predicts every masked position at that position, the
sampler commits some and the pass is repeated. The stack differs from its
autoregressive parent in ONE thing, the mask: a position sees its whole block
and every block before it. A ranker asks one question of a row and reads one
verdict: ONE denoising pass, one forward under the block mask, is what is
served. The sampler's further passes (each commits tokens and re-enters the
stack over a retained prefix), the language-model head and the noise schedule
are not on a scorer's path and are not computed. Layer i, all alike:

  a = RMS_in(x);  q = a W_q [heads x d];  k = a W_k [kv x d];  v = a W_v [kv x d]     no biases
  q_h <- RMS_qn(q_h), k_j <- RMS_kn(k_j)      over the head's d dims, one learned [d] weight each a layer
  rotary on ALL d dims of q and k: pairs (i, i + d/2), angle t * theta ** (-2i / d)
  query head h reads key-value head h // (heads / kv);  scores = q k' / sqrt(d)
  seen(t, u) = u // B <= t // B,  B = block_length           <- the one line that is SDAR
  h = x + concat_h(softmax(scores | seen) v) W_o
  b = RMS_post(h);  p = softmax(b W_r) over ALL num_experts, float32
  the num_experts_per_tok largest; g_e = p_e / their sum (norm_topk_prob); no scaling, no bias
  y = h + sum over the chosen e HELD HERE of g_e * (silu(b G_e) * (b U_e)) D_e

`RMS_w(x) = w * x / sqrt(mean(x^2) + eps)`, a plain weight. Every layer is
routed; no shared expert, no dense layer, no window. `sequence.blocked_attention`
computes the attention under `span = block_length` (the Pallas kernel where it
serves, XLA's blocks elsewhere: the same tiles as a causal mask's, because a
block of queries ends on a block's edge; `attention_kernel.check_span` refuses
what would not), and `models/routed.py` the routed layer: this chip computes
`g_e * expert_e(b)` for the `experts_held` from `first_expert_held` on and
leaves the others' part out. With `experts_held` = `num_experts` (the
benchmark's cell: 128 of 128) the layer is WHOLE: every one of a token's
choices is here.

What the served step skips (exact): the score reads the last position, which
is the last of its block (`num_fields % block_length == 0`) and so sees every
key: the LAST layer's queries, attention output and routed layer are computed
there alone, its keys and values, and every layer before it, at all
positions. A row whose weights are all zero (a padded row) is left out of the
experts and of every counter.

The step counts the routing (`routed.STEP_STATS`) and its score tiles:
`attn.scores_computed`, `attn.scores_seen` (as exaone_moe's) and
`attn.scores_ahead`, the (query, key) pairs with the key AFTER the query that
the mask keeps, a live row and layer (`L (B - 1) / 2` at all positions, 0 for
the lone last query and under any causal mask): from the shapes and the span
the attention was handed, as the other two.

Numerics as the routed families, and for their reason (a router flips where
two scores are nearer than the pieces resolve): parameters and matmul operands
in `compute_dtype`, float32 accumulation, residual, norms, rotary and softmax;
a float32 activation enters a product as OPERAND_PIECES = 3 pieces of the
compute dtype, one product a weight; the router's product, softmax and top-k
float32 at `highest`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import routed, sequence
from .base import Model, ModelConfig, register_model
from .embeddings import embedding_init, field_embed
from .routed import INIT_STD, gated_init, matrix, rms_norm, rope_table, rotate

# Pieces of the compute dtype a wider activation enters a product as: read at
# every call (tests and the benchmark's readings replace it by name).
OPERAND_PIECES = 3
STEP_STATS = routed.STEP_STATS + ("attn.scores_computed", "attn.scores_seen", "attn.scores_ahead")
# A checkpoint's RMSNorm weights start at 1; a seeded tree draws them
# 1 + N(0, NORM_INIT_STD) (as nemotron_h's), so that a weight left out, or a
# head's norm read for another's, shows in the score.
NORM_INIT_STD = 0.1


def _sizes(config: ModelConfig) -> dict:
    experts = config.num_experts
    held = config.experts_held or experts
    heads, kv = config.num_attention_heads, config.num_key_value_heads
    if kv <= 0 or heads % kv:
        raise ValueError(f"num_key_value_heads {kv} of num_attention_heads {heads}: whole groups of query heads")
    head = config.head_dim or config.embed_dim // heads
    if head % 2:
        raise ValueError(f"head_dim {head}: the rotary turn takes pairs")
    if experts <= 0:
        raise ValueError(f"num_experts {experts}: the router's width")
    routed.check_share(experts, held, config.first_expert_held, config.num_experts_per_tok)
    # A row is whole blocks (its last position is then the last of its block
    # and sees every key), and so are both paths' tiles of every layer but the
    # last at all positions: the rule the attention checks again where its
    # path is chosen.
    from ..ops.attention_kernel import check_span, tile

    block, length = config.block_length, config.num_fields
    for side in (sequence.ATTN_BLOCK, tile(length, None)):
        check_span(length, length, block, side)
    return {
        "hidden": config.embed_dim, "heads": heads, "kv": kv, "head": head, "theta": config.rope_theta,
        "span": block, "expert": config.moe_intermediate_size, "experts": experts, "held": held,
        "first": config.first_expert_held, "top_k": config.num_experts_per_tok,
        "norm_topk": bool(config.norm_topk_prob),
    }


def _norm_init(rng, width: int, dtype) -> jax.Array:
    return (1.0 + NORM_INIT_STD * jax.random.normal(rng, (width,))).astype(dtype)


def _layer_init(rng, s: dict, dtype) -> dict:
    k_in, k_post, k_qn, k_kn, k_q, k_k, k_v, k_o, k_router, k_experts = jax.random.split(rng, 10)
    hidden, head, width, held = s["hidden"], s["head"], s["expert"], s["held"]
    return {
        "input_norm": _norm_init(k_in, hidden, dtype), "post_norm": _norm_init(k_post, hidden, dtype),
        "attn": {
            "q": matrix(k_q, (hidden, s["heads"] * head), dtype), "q_norm": _norm_init(k_qn, head, dtype),
            "k": matrix(k_k, (hidden, s["kv"] * head), dtype), "k_norm": _norm_init(k_kn, head, dtype),
            "v": matrix(k_v, (hidden, s["kv"] * head), dtype),
            "o": matrix(k_o, (s["heads"] * head, hidden), dtype),
        },
        "router": matrix(k_router, (hidden, s["experts"]), dtype),
        "experts": gated_init(k_experts, (held, hidden, width), (held, width, hidden), dtype),
    }


def _dot(x: jax.Array, w: jax.Array, cd) -> jax.Array:
    """`routed.dot` at this family's pieces."""
    return routed.dot(x, w, cd, OPERAND_PIECES)


# This family's router: one softmax over all the experts, no bias. Read by
# name at every step traced (tests and the benchmark's readings replace it).
route = functools.partial(routed.route, scoring="softmax")


def qk_norm(p: dict, q: jax.Array, k: jax.Array, eps: float) -> tuple[jax.Array, jax.Array]:
    """The learned RMSNorm of every query head and every key head over its
    own dims, before the rotary turn: one `[d]` weight each a layer."""
    return rms_norm(p["q_norm"], q, eps), rms_norm(p["k_norm"], k, eps)


def _heads(s: dict, cd) -> sequence.Heads:
    return sequence.Heads((s["head"],), s["head"], s["heads"] // s["kv"], cd)


def attention(p: dict, a: jax.Array, s: dict, cd, eps: float, last_only: bool = False) -> jax.Array:
    """One layer's attention of the normed `a [n, L, H]` under the block mask:
    `[n, L, H]`, or `[n, 1, H]` for the last position's query alone against
    the keys and values of every position (the last of its block: it sees
    them all). The caller's `attn_block` scope."""
    n, length, _ = a.shape
    heads, kv, head = s["heads"], s["kv"], s["head"]
    at = sequence.last_position(a) if last_only else a
    queries = at.shape[1]
    with jax.named_scope("qkv"):
        q = _dot(at, p["q"], cd).reshape(n, queries, kv, heads // kv, head)
        k = _dot(a, p["k"], cd).reshape(n, length, kv, head)
        v = _dot(a, p["v"], cd).reshape(n, length, kv, head)
    with jax.named_scope("qk_norm"):
        q, k = qk_norm(p, q, k, eps)
    with jax.named_scope("rope"):
        cos, sin = rope_table(length, head, s["theta"])
        q = rotate(q, cos[length - queries:, None, None, :], sin[length - queries:, None, None, :])
        k = rotate(k, cos[:, None, :], sin[:, None, :])
    with jax.named_scope("softmax"):
        o = sequence.blocked_attention(q, k, v, None, cd, OPERAND_PIECES, span=s["span"])
    return _dot(o.reshape(n, queries, heads * head), p["o"], cd)


def step_pairs(layers: int, length: int, s: dict, cd) -> tuple[int, int, int]:
    """((query, key) pairs the tiles of the served step's attention compute
    over a row, those its mask keeps, those of them with the key after the
    query), summed over the layers: every layer but the last at all positions,
    the last layer's one query against every key."""
    computed = seen = ahead = 0
    for i in range(layers):
        queries = 1 if i == layers - 1 else length
        pairs = sequence.blocked_pairs(queries, length, None, OPERAND_PIECES, _heads(s, cd), s["span"])
        computed, seen = computed + pairs[0], seen + pairs[1]
        ahead += sequence.ahead_pairs(queries, length, s["span"])
    return computed, seen, ahead


def forward(config: ModelConfig, params, batch) -> tuple[jax.Array, jax.Array]:
    """(the logit of every row, the step's counters): the last layer's
    queries, attention output and routed layer at the last position alone."""
    s, cd, eps = _sizes(config), config.cdtype, config.layer_norm_eps
    layers = len(params["layers"])
    with jax.named_scope("embed"):
        # The weighted embedding in float32, where a bfloat16 row times a
        # float32 weight is exact.
        x = field_embed(params["embedding"], batch["feat_ids"], batch["feat_wts"], jnp.float32, s["hidden"])
        live = jnp.any(batch["feat_wts"] != 0, axis=1)  # a padded row is zero throughout
    moe = jnp.zeros((len(routed.STEP_STATS),), jnp.int32)
    router = functools.partial(route, normalise=s["norm_topk"])
    for i, layer in enumerate(params["layers"]):
        last = i == layers - 1
        with jax.named_scope("attn_block"):
            mix = attention(layer["attn"], rms_norm(layer["input_norm"], x, eps), s, cd, eps, last)
        if last:
            x = sequence.last_position(x)
        h = x + mix
        out, counts = routed.routed_ffn(
            layer, rms_norm(layer["post_norm"], h, eps), s["top_k"], s["first"], 1.0, cd, OPERAND_PIECES, live,
            router=router)
        moe = moe + counts
        x = h + out
    with jax.named_scope("score"):
        final = rms_norm(params["final_norm"], x[:, -1], eps)
        # (query, key) pairs a row, from the shapes alone, times the live rows
        pairs = step_pairs(layers, batch["feat_ids"].shape[1], s, cd)
        stats = jnp.concatenate([moe, jnp.sum(live, dtype=jnp.int32) * jnp.asarray(pairs, jnp.int32)])
        return jnp.sum(final * params["score"].astype(jnp.float32), axis=-1), stats


def attention_plan(config: ModelConfig) -> tuple[tuple[tuple[str, object], ...], ...]:
    """Each layer's attention as (name, value) pairs: its kind, the block
    mask's span, the block of queries and the keys a block of the XLA path
    reads at the most (`startup.attention` says which path serves), key-value
    heads, rotary dims and base."""
    s, length = _sizes(config), config.num_fields
    layer = (("kind", "block"), ("span", s["span"]), ("window", 0), ("block", min(sequence.ATTN_BLOCK, length)),
             ("keys_a_block", length), ("kv_heads", s["kv"]), ("rotary_dims", s["head"]), ("theta", s["theta"]))
    return (layer,) * config.num_hidden_layers


@register_model("sdar_moe")
def build_sdar_moe(config: ModelConfig) -> Model:
    s = _sizes(config)
    layers = config.num_hidden_layers

    def init(rng, packed: bool = False):
        k_emb, k_score, k_norm, *k_layers = jax.random.split(rng, 3 + layers)
        dtype = config.pdtype
        # embedding_init scales by 1/sqrt(dim); INIT_STD is wanted.
        table = embedding_init(k_emb, config.vocab_size, s["hidden"], dtype, packed)
        return {
            "embedding": table * jnp.asarray(INIT_STD * s["hidden"] ** 0.5, dtype),
            "layers": [_layer_init(k, s, dtype) for k in k_layers],
            "final_norm": _norm_init(k_norm, s["hidden"], dtype),
            "score": matrix(k_score, (s["hidden"],), dtype),
        }

    def apply_stats(params, batch):
        logits, stats = forward(config, params, batch)
        return {"prediction_node": jax.nn.sigmoid(logits), "logits": logits}, stats

    def apply(params, batch):
        return apply_stats(params, batch)[0]

    expert_plan = (
        ("published", s["experts"]), ("held", s["held"]), ("first", s["first"]),
        ("top_k", s["top_k"]), ("heads_published", s["heads"]), ("heads_held", s["heads"]),
        ("chips_sharing_layer", s["experts"] // s["held"]),
    )
    # The weights cross as float32, as phi4flash's and for its reason: a
    # token's weight scales its embedding in the residual stream.
    return Model(
        config=config, init=init, apply=apply, wts_in_compute_dtype=False, layer_plan=("block/moe",) * layers,
        expert_plan=expert_plan, attention_plan=attention_plan(config), apply_stats=apply_stats,
        step_stats=STEP_STATS)
