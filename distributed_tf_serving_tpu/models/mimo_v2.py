"""mimo_v2: MiMo-V2.5 (`model_type: mimo_v2`; the family's report is
MiMo-V2-Flash) as a pointwise sequence ranker, through the same Predict path
and wire contract as the four sequence families before it: a candidate row is
`num_fields` token ids (`feat_ids [n, L]`, folded by `% vocab_size`),
`feat_wts [n, L]` multiplies the token's embedding (`x0_t = w_t * E[id_t]`,
float32 on the link and in the product), and `prediction_node [n]` is the
sigmoid of one logit read at the last position, `s = w_score . RMS_final(h_L)`.

The attention differs BY LAYER KIND, and more than its mask does
(`hybrid_layer_pattern`: 0 a full layer, 1 a window layer, one full layer to
five window layers as published): the key-value heads (so the SHAPES of
`W_k`, `W_v`), the rotary base, and whether the softmax holds a learned sink.
Norms stand BEFORE the sub-layers; no norm on a query or key head:

  a = RMS_in(x)
  q = a W_q [heads x d];  k = a W_k [KV x d];  v = value_scale * (a W_v) [KV x d_v]     no biases
      KV = num_key_value_heads on a full layer, swa_num_key_value_heads on a window layer
  rotary on the FIRST r = int(d * partial_rotary_factor) dims of every q and k
      head, pairs (i, i + r/2), angle t * theta ** (-2i / r); theta = rope_theta
      on a full layer, swa_rope_theta on a window layer; the other dims unturned
  query head h reads key-value head h // (heads / KV);  s_tu = q_t . k_u / sqrt(d)
  seen(t, u) = u <= t, and t - u < sliding_window on a window layer
  full layer:   p = softmax(s | seen)
  window layer: p_tu = exp(s_tu - m) / (sum_{u seen} exp(s_tu - m) + exp(b_h - m)),
                m = max(max_u s_tu, b_h): b_h a learned logit a head, the SINK,
                which takes mass and gives no value (a row of p sums to less than 1)
  attn = concat_h(p v) W_o [heads x d_v -> H];  h = x + attn;  y = h + FFN(RMS_post(h))

FFN where `moe_layer_freq` says 0: `(silu(g W_g) * (g W_u)) W_d` at
`intermediate_size`. Where it says 1, the routed layer of `models/routed.py`
with NO shared expert: sigmoid scores over ALL `n_routed_experts`, the top
`num_experts_per_tok` (one group, the selection bias zero), normalised, times
`routed_scaling_factor`; this chip computes `g_e * expert_e(g)` for the
`experts_held` from `first_expert_held` on and leaves the others' part out.
The attention is whole on every chip of the stated deployment.

In a one-chip served entry on a TPU every layer but the last runs
`sequence.attention`, ONE Pallas kernel a layer (ops/attention_kernel.py),
whose running state starts at the sink where the layer has one; everywhere
else `sequence.blocked_attention`'s XLA blocks, either kind. The step counts
its score tiles (`attn.scores_computed`, `attn.scores_seen`, as exaone_moe's)
and what the sinks took: `attn.sink_mass_ppm`, the sink's share
`exp(b_h - m) / denominator` of a query's softmax, averaged over the heads and
the queries the step computed of a (live row, window layer) and summed over
those pairs in parts per million, and `attn.sink_rows`, the pairs summed over.
`Model.attention_plan` states each layer's kind, window, block, keys a block,
key-value heads, rotary dims, base and whether it has a sink.

What the served step skips (exact): the score reads the last position, so the
LAST layer's queries, attention output and FFN are computed there alone, its
keys and values at the positions that query sees (the last `sliding_window`
on a window layer, else all), every layer before it at all positions. A row
whose weights are all zero (a padded row) is left out of the experts and of
every counter.

Numerics as both routed families, and for their reason: parameters and matmul
operands in `compute_dtype`, float32 accumulation, residual, norms, rotary and
softmax; a float32 activation enters a product as OPERAND_PIECES = 3 pieces of
the compute dtype, one product a weight; the router's product, sigmoid and
top-k float32 at `highest` precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import routed, sequence
from .base import Model, ModelConfig, register_model
from .embeddings import embedding_init, field_embed
from .routed import INIT_STD, gated_init, matrix, rms_norm, rope_table, rotate

# Pieces of the compute dtype a wider activation enters a product as: read at
# every call (tests and the benchmark's readings replace it by name).
OPERAND_PIECES = 3
STEP_STATS = routed.STEP_STATS + (
    "attn.scores_computed", "attn.scores_seen", "attn.sink_mass_ppm", "attn.sink_rows")
KINDS = ("full", "window")  # hybrid_layer_pattern's 0 and 1
# The sink logits are learned; a seeded tree draws them N(0, SINK_INIT_STD), a
# logit a head: against a window's 128 scores of deviation near 1.6 at the
# published widths, a head's sink then takes anything from nothing to most of
# a query's softmax, so that a sink left out, or read under another head,
# shows in the score.
SINK_INIT_STD = 3.0


def layer_plan(config: ModelConfig) -> tuple[tuple[str, str], ...]:
    """(attention kind, FFN kind) of every layer: `full` or `window`, `dense`
    or `moe`. Keys left out take the published pattern: layer 0 and every
    sixth layer from layer 5 on full, the others window; layer 0 dense."""
    layers = config.num_hidden_layers
    kinds = config.hybrid_layer_pattern or tuple(0 if i == 0 or i % 6 == 5 else 1 for i in range(layers))
    routed_at = config.moe_layer_freq or (0,) + (1,) * (layers - 1)
    if len(kinds) != layers or set(kinds) - {0, 1}:
        raise ValueError(f"hybrid_layer_pattern {kinds}: 0 (full) or 1 (window) for each of num_hidden_layers {layers}")
    if len(routed_at) != layers or set(routed_at) - {0, 1}:
        raise ValueError(f"moe_layer_freq {routed_at}: 0 (dense) or 1 (routed) for each of num_hidden_layers {layers}")
    return tuple((KINDS[kind], "moe" if moe else "dense") for kind, moe in zip(kinds, routed_at))


def _sizes(config: ModelConfig) -> dict:
    experts = config.n_routed_experts
    held = config.experts_held or experts
    heads = config.num_attention_heads
    kv = {"full": config.num_key_value_heads, "window": config.swa_num_key_value_heads or config.num_key_value_heads}
    for kind, count in kv.items():
        if count <= 0 or heads % count:
            raise ValueError(f"{count} key-value heads on a {kind} layer of num_attention_heads {heads}: "
                             "whole groups of query heads")
    head = config.head_dim or config.embed_dim // heads
    rotary = int(head * config.partial_rotary_factor)
    if not 0 < rotary <= head or rotary % 2:
        raise ValueError(f"partial_rotary_factor {config.partial_rotary_factor} of head_dim {head}: "
                         f"{rotary} dims to turn; the rotary turn takes pairs")
    if config.sliding_window <= 0:
        raise ValueError(f"sliding_window {config.sliding_window}")
    if experts <= 0:
        raise ValueError(f"n_routed_experts {experts}: the router's width")
    routed.check_share(experts, held, config.first_expert_held, config.num_experts_per_tok)
    return {
        "hidden": config.embed_dim, "inter": config.intermediate_size, "heads": heads, "kv": kv, "head": head,
        "v_head": config.v_head_dim, "rotary": rotary, "value_scale": config.attention_value_scale,
        "theta": {"full": config.rope_theta, "window": config.swa_rope_theta},
        "sink": {"full": config.add_full_attention_sink_bias, "window": config.add_swa_attention_sink_bias},
        "window": config.sliding_window, "expert": config.moe_intermediate_size, "experts": experts,
        "held": held, "first": config.first_expert_held, "top_k": config.num_experts_per_tok,
    }


def _layer_init(rng, kind: str, ffn: str, s: dict, dtype) -> dict:
    """One layer's tree; the key and value projections' shapes and the sink
    follow the layer's kind."""
    k_q, k_k, k_v, k_o, k_sink, k_mlp, k_router, k_experts = jax.random.split(rng, 8)
    hidden, heads, kv = s["hidden"], s["heads"], s["kv"][kind]
    layer = {
        "input_norm": jnp.ones((hidden,), dtype), "post_attn_norm": jnp.ones((hidden,), dtype),
        "attn": {
            "q": matrix(k_q, (hidden, heads * s["head"]), dtype), "k": matrix(k_k, (hidden, kv * s["head"]), dtype),
            "v": matrix(k_v, (hidden, kv * s["v_head"]), dtype), "o": matrix(k_o, (heads * s["v_head"], hidden), dtype),
        },
    }
    if s["sink"][kind]:
        layer["attn"]["sink"] = jax.random.normal(k_sink, (heads,), dtype) * jnp.asarray(SINK_INIT_STD, dtype)
    if ffn == "dense":
        layer["mlp"] = gated_init(k_mlp, (hidden, s["inter"]), (s["inter"], hidden), dtype)
    else:
        width, held = s["expert"], s["held"]
        layer["router"] = matrix(k_router, (hidden, s["experts"]), dtype)
        layer["experts"] = gated_init(k_experts, (held, hidden, width), (held, width, hidden), dtype)
    return layer


def _dot(x: jax.Array, w: jax.Array, cd) -> jax.Array:
    """`routed.dot` at this family's pieces."""
    return routed.dot(x, w, cd, OPERAND_PIECES)


def step_pairs(kinds: tuple[str, ...], length: int, window: int) -> tuple[int, int]:
    """((query, key) pairs the tiles of the served step's attention compute
    over a row, those its masks keep), summed over the layers `kinds`: every
    layer but the last at all positions, the last layer's one query against
    the keys it reads; `sequence.blocked_pairs` has both paths' tiles."""
    computed = seen = 0
    for i, kind in enumerate(kinds):
        reach = window if kind == "window" else None
        if i == len(kinds) - 1:
            pairs = sequence.blocked_pairs(1, length if reach is None else min(length, reach), reach)
        else:
            pairs = sequence.blocked_pairs(length, length, reach)
        computed, seen = computed + pairs[0], seen + pairs[1]
    return computed, seen


def attention(p: dict, x: jax.Array, s: dict, kind: str, cd, last_only: bool = False):
    """One layer's attention of the normed `x [n, L, H]`: (`[n, L, H]`, or
    `[n, 1, H]` for the last position's query alone, whose keys and values
    are computed at the positions it sees; the sink's share of a query's
    softmax averaged over the heads and the queries computed, `[n]`, None
    where the layer has no sink). The caller's `attn_window` or `attn_full`
    scope."""
    n, length, _ = x.shape
    heads, kv, head, v_head, rotary = s["heads"], s["kv"][kind], s["head"], s["v_head"], s["rotary"]
    window = s["window"] if kind == "window" else None
    reach = x[:, length - window:] if last_only and window is not None and window < length else x
    keys = reach.shape[1]
    if last_only:
        x = sequence.last_position(x)
    queries = x.shape[1]
    with jax.named_scope("qkv"):
        q = _dot(x, p["q"], cd).reshape(n, queries, kv, heads // kv, head)
        k = _dot(reach, p["k"], cd).reshape(n, keys, kv, head)
        v = _dot(reach, p["v"], cd).reshape(n, keys, kv, v_head) * s["value_scale"]
    with jax.named_scope("rope"):
        cos, sin = rope_table(length, rotary, s["theta"][kind])
        q = rotate(q, cos[length - queries:, None, None, :], sin[length - queries:, None, None, :], rotary)
        k = rotate(k, cos[length - keys:, None, :], sin[length - keys:, None, :], rotary)
    with jax.named_scope("softmax"):
        sink = p["sink"].astype(jnp.float32) if s["sink"][kind] else None
        o = sequence.blocked_attention(q, k, v, window, cd, OPERAND_PIECES, sink)
        o, share = (o, None) if sink is None else (o[0], jnp.mean(o[1], axis=(1, 2, 3)))
    return _dot(o.reshape(n, queries, heads * v_head), p["o"], cd), share


def forward(config: ModelConfig, params, batch) -> tuple[jax.Array, jax.Array]:
    """(the logit of every row, the step's counters): the last layer's
    queries, attention output and FFN at the last position alone."""
    s, cd, eps = _sizes(config), config.cdtype, config.layer_norm_eps
    plan = layer_plan(config)
    with jax.named_scope("embed"):
        # The weighted embedding in float32, where a bfloat16 row times a
        # float32 weight is exact.
        x = field_embed(params["embedding"], batch["feat_ids"], batch["feat_wts"], jnp.float32, s["hidden"])
        live = jnp.any(batch["feat_wts"] != 0, axis=1)  # a padded row is zero throughout
    moe = jnp.zeros((len(routed.STEP_STATS),), jnp.int32)
    sunk = jnp.zeros((2,), jnp.int32)  # parts per million summed, (live row, sink layer) pairs
    for i, ((kind, ffn), layer) in enumerate(zip(plan, params["layers"])):
        last = i == len(plan) - 1
        with jax.named_scope(f"attn_{kind}"):
            mix, share = attention(layer["attn"], rms_norm(layer["input_norm"], x, eps), s, kind, cd, last)
        if "sink" in layer["attn"]:
            # The pairs from the TREE, the mass from what the softmax did: a
            # step that leaves the sink out reads 0 over them.
            ppm = 0 if share is None else jnp.sum(jnp.where(live, jnp.round(share * 1e6).astype(jnp.int32), 0))
            sunk = sunk + jnp.stack([ppm, jnp.sum(live, dtype=jnp.int32)])
        if last:
            x = sequence.last_position(x)
        h = x + mix
        g = rms_norm(layer["post_attn_norm"], h, eps)
        if ffn == "dense":
            with jax.named_scope("dense_mlp"):
                out = routed.gated_mlp(layer["mlp"], g, cd, OPERAND_PIECES)
        else:
            out, counts = routed.routed_ffn(
                layer, g, s["top_k"], s["first"], config.routed_scaling_factor, cd, OPERAND_PIECES, live)
            moe = moe + counts
        x = h + out
    with jax.named_scope("score"):
        final = rms_norm(params["final_norm"], x[:, -1], eps)
        # (query, key) pairs a row, from the shapes alone, times the live rows
        pairs = step_pairs(tuple(kind for kind, _ in plan), batch["feat_ids"].shape[1], s["window"])
        stats = jnp.concatenate([moe, jnp.sum(live, dtype=jnp.int32) * jnp.asarray(pairs, jnp.int32), sunk])
        return jnp.sum(final * params["score"].astype(jnp.float32), axis=-1), stats


def attention_plan(config: ModelConfig) -> tuple[tuple[tuple[str, object], ...], ...]:
    """Each layer's kind, window, block of queries and the most keys a block
    of the XLA path reads at all positions (`startup.attention` has the
    kernel's tile where it serves), and what differs by kind beside the mask:
    key-value heads, rotary dims, the rotary base, whether it has a sink."""
    s, length, out = _sizes(config), config.num_fields, []
    block = min(sequence.ATTN_BLOCK, length)
    for kind, _ffn in layer_plan(config):
        window = s["window"] if kind == "window" else 0
        out.append((("kind", kind), ("window", window), ("block", block),
                    ("keys_a_block", min(length, block + window - 1) if window else length),
                    ("kv_heads", s["kv"][kind]), ("rotary_dims", s["rotary"]), ("theta", s["theta"][kind]),
                    ("sink", bool(s["sink"][kind]))))
    return tuple(out)


@register_model("mimo_v2")
def build_mimo_v2(config: ModelConfig) -> Model:
    s = _sizes(config)
    plan = layer_plan(config)

    def init(rng, packed: bool = False):
        k_emb, k_score, *k_layers = jax.random.split(rng, 2 + len(plan))
        dtype = config.pdtype
        # embedding_init scales by 1/sqrt(dim); INIT_STD is wanted.
        table = embedding_init(k_emb, config.vocab_size, s["hidden"], dtype, packed)
        return {
            "embedding": table * jnp.asarray(INIT_STD * s["hidden"] ** 0.5, dtype),
            "layers": [_layer_init(k, kind, ffn, s, dtype) for k, (kind, ffn) in zip(k_layers, plan)],
            "final_norm": jnp.ones((s["hidden"],), dtype),
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
        config=config, init=init, apply=apply, wts_in_compute_dtype=False,
        layer_plan=tuple(f"{kind}/{ffn}" for kind, ffn in plan), expert_plan=expert_plan,
        attention_plan=attention_plan(config), apply_stats=apply_stats, step_stats=STEP_STATS)
