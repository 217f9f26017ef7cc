"""qwen3_next: Qwen3-Next-80B-A3B-Instruct (`model_type: qwen3_next`) as a
pointwise sequence ranker, through the same Predict path and wire contract as
the six sequence families before it: a candidate row is `num_fields` token ids
(`feat_ids [n, L]`, folded by `% vocab_size`), `feat_wts [n, L]` multiplies the
token's embedding (`x0_t = w_t * E[id_t]`, float32 on the link and in the
product), and `prediction_node [n]` is the sigmoid of one logit read at the
last position, `s = w_score . RMS0_final(y_L)`.

The mixer differs BY LAYER (`full_attention_interval`: layer i is a gated
full-attention layer where `(i + 1) % interval == 0`, else a gated-delta-rule
layer, three to one as published; `layer_types`, where given, says it layer by
layer) and EVERY layer holds the routed block. Norms stand BEFORE the
sub-layers and are ZERO-CENTRED: the weight is stored less one,

  RMS0_w(x) = x / sqrt(mean(x^2) + eps) * (1 + w)
  a = RMS0_in(x);   h = x + MIX(a);   b = RMS0_post(h);   y = h + MOE(b)

linear layer (Gated DeltaNet, arXiv:2412.06464), Hk key heads dk wide, Hv =
r x Hk value heads dv wide (16 and 32 at 128 as published):

  q = a W_q [Hk x dk], k = a W_k [Hk x dk], v = a W_v [Hv x dv], z = a W_z [Hv x dv]
  beta = sigmoid(a W_b) [Hv];   g = -exp(A_log) * softplus(a W_a + dt_bias) [Hv]     a value head's
  each of q, k, v <- silu(causal depthwise convolution, `linear_conv_kernel_dim` taps, no bias)
  q <- q / sqrt(sum q^2 + 1e-6) / sqrt(dk),  k <- k / sqrt(sum k^2 + 1e-6)           a key head
  value head h reads key head h // r:
      S_t = exp(g_t) (I - beta_t k_t k_t') S_{t-1} + beta_t k_t v_t',  S_0 = 0;   o_t = S_t' q_t
  o_h <- o_h / sqrt(mean(o_h^2) + eps) * w_o * silu(z_h)        a PLAIN weight, one [dv] a layer
  MIX = concat_h(o_h) W_out

`olmo_hybrid.gated_delta_rule` computes the rule (its docstring has the chunk
algebra, the block solve and the Pallas kernel that walks the chunks in a
one-chip served entry on a TPU), here at this family's three pieces and with
r = 2 value heads a key head: q and k stay the 16 key heads they are, `K K'`
and `Q K'` are made once a key head, and its two value heads read them.

full layer (gated attention), `heads` query heads over `kv` key-value heads, d wide:

  [q_h | gate_h] = a W_q, a head's d query columns then its d gate columns
  k = a W_k [kv x d], v = a W_v [kv x d]
  q_h <- RMS0_qn(q_h), k_j <- RMS0_kn(k_j)     over the head's d dims, one [d] weight each a layer
  rotary on the FIRST r = int(d * partial_rotary_factor) dims of q_h and k_j,
      pairs (i, i + r/2), angle t * theta ** (-2i / r); the other dims unturned
  scores = q k' / sqrt(d), causal; query head h reads key-value head h // (heads / kv)
  MIX = concat_h(softmax(scores) v * sigmoid(gate_h)) W_o

`sequence.blocked_attention` computes it: the Pallas attention kernel where it
serves AND its scratch fits the VMEM a kernel has (`sequence.attention_choice`:
heads 256 wide at three pieces fit it held COMPACT, the keys' pieces once each
and a head's float32 keys and values read from HBM a chunk at a time, and the
shapes choose that by themselves: `ops/attention_kernel.py::held_compact`; the
servable's `startup.attention` says which path serves, and why where XLA's
blocks do).

MOE (`models/routed.py`): `p = softmax(b W_r)` over ALL `num_experts`, float32;
the `num_experts_per_tok` largest, normalised to sum 1 (`norm_topk_prob`), no
scale; this chip computes `gate_e * expert_e(b)` for the `experts_held` from
`first_expert_held` on and leaves the others' part out; plus the shared
expert times its own gate a token, `sigmoid(b . w_sg) * shared(b)`. The
mixers, the router, the shared expert and the norms are whole on every chip of
the stated deployment.

What the served step skips (exact): the score reads the last position, so of
the LAST layer the output gate, norm and projection of a linear layer (its
projections, convolutions and rule run at all positions), or the queries,
gate and output of a full one (its keys and values at all positions), and its
whole routed block, are computed there alone; every layer before it at all
positions. A row whose weights are all zero (a padded row) is left out of the
experts and of every counter.

Numerics as the three routed families, and for their reason (a router flips
where two scores are nearer than the pieces resolve): parameters and matmul
operands in `compute_dtype`, float32 accumulation, residual, norms, softmax,
rotary, convolution, gates, decays, the rule's state and its solve; a float32
activation enters a product as OPERAND_PIECES = 3 pieces of the compute
dtype; the router's product, softmax and top-k float32 at `highest`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import olmo_hybrid, routed, sequence
from .base import Model, ModelConfig, register_model
from .embeddings import embedding_init, field_embed
from .routed import INIT_STD, gated_init, matrix, rms_norm, rope_table, rotate

# Pieces of the compute dtype a wider activation enters a product as: read at
# every call (tests and the benchmark's readings replace it by name).
OPERAND_PIECES = 3
STEP_STATS = routed.STEP_STATS + (
    "attn.scores_computed", "attn.scores_seen", "delta.rows", "delta.handovers", "delta.positions")
KINDS = {"linear_attention": "linear", "full_attention": "full"}
# A checkpoint's zero-centred norm weights start at 0; a seeded tree draws
# them N(0, NORM_INIT_STD), so that `w` in place of `1 + w` shows in the score.
NORM_INIT_STD = 0.1


def layer_plan(config: ModelConfig) -> tuple[str, ...]:
    """The mixer of every layer: `linear` or `full`."""
    layers, interval = config.num_hidden_layers, config.full_attention_interval
    if interval <= 0:
        raise ValueError(f"full_attention_interval {interval}")
    kinds = config.layer_types or tuple(
        "full_attention" if (i + 1) % interval == 0 else "linear_attention" for i in range(layers))
    if len(kinds) != layers or set(kinds) - set(KINDS):
        raise ValueError(
            f"layer_types {kinds}: one of {sorted(KINDS)} for each of num_hidden_layers {layers}")
    return tuple(KINDS[kind] for kind in kinds)


def _sizes(config: ModelConfig) -> dict:
    experts = config.num_experts
    held = config.experts_held or experts
    heads, kv = config.num_attention_heads, config.num_key_value_heads
    if kv <= 0 or heads % kv:
        raise ValueError(f"num_key_value_heads {kv} of num_attention_heads {heads}: whole groups of query heads")
    head = config.head_dim or config.embed_dim // heads
    rotary = int(head * config.partial_rotary_factor)
    if not 0 < rotary <= head or rotary % 2:
        raise ValueError(f"partial_rotary_factor {config.partial_rotary_factor} of head_dim {head}: "
                         f"{rotary} dims to turn; the rotary turn takes pairs")
    keys, values = config.linear_num_key_heads, config.linear_num_value_heads
    if keys <= 0 or values % keys:
        raise ValueError(f"linear_num_value_heads {values} over linear_num_key_heads {keys}: "
                         "whole groups of value heads a key head")
    if min(config.linear_key_head_dim, config.linear_value_head_dim, config.linear_conv_kernel_dim) <= 0:
        raise ValueError("linear_key_head_dim, linear_value_head_dim, linear_conv_kernel_dim: positive")
    if experts <= 0:
        raise ValueError(f"num_experts {experts}: the router's width")
    routed.check_share(experts, held, config.first_expert_held, config.num_experts_per_tok)
    return {
        "hidden": config.embed_dim, "heads": heads, "kv": kv, "head": head, "rotary": rotary,
        "theta": config.rope_theta, "lin_k": keys, "lin_v": values, "dk": config.linear_key_head_dim,
        "dv": config.linear_value_head_dim, "conv": config.linear_conv_kernel_dim,
        "neg": bool(config.linear_allow_neg_eigval), "expert": config.moe_intermediate_size,
        "shared": config.shared_expert_intermediate_size, "experts": experts, "held": held,
        "first": config.first_expert_held, "top_k": config.num_experts_per_tok,
        "norm_topk": bool(config.norm_topk_prob),
    }


def _zero_centred(rng, width: int, dtype) -> jax.Array:
    return jax.random.normal(rng, (width,), dtype) * jnp.asarray(NORM_INIT_STD, dtype)


def _linear_init(rng, s: dict, dtype) -> dict:
    """q and k of the key heads; v, the output gate z, b and a of the value
    heads (the checkpoint fuses q, k, v, z into one projection and b, a into
    another: the same products); the rule's own parameters and the
    convolutions as `olmo_hybrid` draws them; the output norm's PLAIN weight."""
    k_q, k_k, k_v, k_z, k_cq, k_ck, k_cv, k_b, k_a, k_A, k_dt, k_o = jax.random.split(rng, 12)
    hidden, keys, values, taps = s["hidden"], s["lin_k"] * s["dk"], s["lin_v"] * s["dv"], s["conv"]
    A_log, dt_bias = olmo_hybrid.decay_init(k_A, k_dt, s["lin_v"], dtype)
    return {
        "q": matrix(k_q, (hidden, keys), dtype), "k": matrix(k_k, (hidden, keys), dtype),
        "v": matrix(k_v, (hidden, values), dtype), "z": matrix(k_z, (hidden, values), dtype),
        "conv_q": olmo_hybrid.conv_init(k_cq, keys, taps, dtype),
        "conv_k": olmo_hybrid.conv_init(k_ck, keys, taps, dtype),
        "conv_v": olmo_hybrid.conv_init(k_cv, values, taps, dtype),
        "b": matrix(k_b, (hidden, s["lin_v"]), dtype), "a": matrix(k_a, (hidden, s["lin_v"]), dtype),
        "A_log": A_log, "dt_bias": dt_bias, "o_norm": jnp.ones((s["dv"],), dtype),
        "o": matrix(k_o, (values, hidden), dtype),
    }


def _layer_init(rng, kind: str, s: dict, dtype) -> dict:
    k_mix, k_in, k_post, k_qn, k_kn, k_q, k_k, k_v, k_o, k_router, k_shared, k_gate, k_experts = jax.random.split(rng, 13)
    hidden, head, width, held = s["hidden"], s["head"], s["expert"], s["held"]
    layer = {
        "input_norm": _zero_centred(k_in, hidden, dtype), "post_norm": _zero_centred(k_post, hidden, dtype),
        "router": matrix(k_router, (hidden, s["experts"]), dtype),
        "shared": gated_init(k_shared, (hidden, s["shared"]), (s["shared"], hidden), dtype),
        "shared_gate": matrix(k_gate, (hidden,), dtype),
        "experts": gated_init(k_experts, (held, hidden, width), (held, width, hidden), dtype),
    }
    if kind == "linear":
        layer["linear"] = _linear_init(k_mix, s, dtype)
    else:
        layer["attn"] = {
            # a head's query columns, then its gate columns
            "q": matrix(k_q, (hidden, s["heads"] * 2 * head), dtype), "q_norm": _zero_centred(k_qn, head, dtype),
            "k": matrix(k_k, (hidden, s["kv"] * head), dtype), "k_norm": _zero_centred(k_kn, head, dtype),
            "v": matrix(k_v, (hidden, s["kv"] * head), dtype),
            "o": matrix(k_o, (s["heads"] * head, hidden), dtype),
        }
    return layer


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def _dot(x: jax.Array, w: jax.Array, cd) -> jax.Array:
    """`routed.dot` at this family's pieces."""
    return routed.dot(x, w, cd, OPERAND_PIECES)


def rms0(w: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    """The zero-centred RMSNorm: the stored weight is the scale less one."""
    return rms_norm(1.0 + w.astype(jnp.float32), x, eps)


def route(router: jax.Array, x: jax.Array, top_k: int, scaling: float, normalise: bool = True):
    """`routed.route` with this family's router: one softmax over all the
    experts."""
    return routed.route(router, x, top_k, scaling, "softmax", normalise)


def gated_delta_net(p: dict, a: jax.Array, s: dict, cd, eps: float, last_only: bool = False) -> jax.Array:
    """One linear layer's mixer of the normed `a [n, L, H]`: `[n, L, H]`, or
    `[n, 1, H]` where the last position's output alone is asked for (the
    projections, convolutions and rule still run over every position). The
    caller's `gdn` scope."""
    n, length, _ = a.shape
    keys, values, dk, dv = s["lin_k"], s["lin_v"], s["dk"], s["dv"]
    with jax.named_scope("in_proj"):
        q, k, v = _dot(a, p["q"], cd), _dot(a, p["k"], cd), _dot(a, p["v"], cd)
        z = _dot(sequence.last_position(a) if last_only else a, p["z"], cd)
        b = jax.nn.sigmoid(_dot(a, p["b"], cd)) * (2.0 if s["neg"] else 1.0)
        g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            _dot(a, p["a"], cd) + p["dt_bias"].astype(jnp.float32))
    with jax.named_scope("conv"):
        q, k, v = (sequence.causal_conv(y, p[name]) for y, name in ((q, "conv_q"), (k, "conv_k"), (v, "conv_v")))
        q = olmo_hybrid.l2_norm(q.reshape(n, length, keys, dk)) * dk ** -0.5
        k = olmo_hybrid.l2_norm(k.reshape(n, length, keys, dk))
        v = v.reshape(n, length, values, dv)
    with jax.named_scope("delta_rule"):
        o, _ = olmo_hybrid.gated_delta_rule(q, k, v, g, b, cd=cd, count=OPERAND_PIECES)
    if last_only:
        o = sequence.last_position(o)
    with jax.named_scope("gate_norm"):
        o = rms_norm(p["o_norm"], o, eps) * jax.nn.silu(z).reshape(o.shape)
    with jax.named_scope("out_proj"):
        return _dot(o.reshape(n, -1, values * dv), p["o"], cd)


def attention_gate(o: jax.Array, gate: jax.Array) -> jax.Array:
    """A full layer's heads `o [..., d]` under their output gates: `o *
    sigmoid(gate)`, the gate a head's second d columns of `W_q`."""
    return o * jax.nn.sigmoid(gate)


def _heads(s: dict, cd) -> sequence.Heads:
    return sequence.Heads((s["head"],), s["head"], s["heads"] // s["kv"], cd)


def gated_attention(p: dict, a: jax.Array, s: dict, cd, eps: float, last_only: bool = False) -> jax.Array:
    """One full layer's gated attention of the normed `a [n, L, H]`:
    `[n, L, H]`, or `[n, 1, H]` for the last position's query alone against
    the keys and values of every position. The caller's `attn_full` scope."""
    n, length, _ = a.shape
    heads, kv, head, rotary = s["heads"], s["kv"], s["head"], s["rotary"]
    at = sequence.last_position(a) if last_only else a
    queries = at.shape[1]
    with jax.named_scope("qkv"):
        q = _dot(at, p["q"], cd).reshape(n, queries, kv, heads // kv, 2 * head)
        q, gate = q[..., :head], q[..., head:]
        k = _dot(a, p["k"], cd).reshape(n, length, kv, head)
        v = _dot(a, p["v"], cd).reshape(n, length, kv, head)
    with jax.named_scope("qk_norm"):
        q, k = rms0(p["q_norm"], q, eps), rms0(p["k_norm"], k, eps)
    with jax.named_scope("rope"):
        cos, sin = rope_table(length, rotary, s["theta"])
        q = rotate(q, cos[length - queries:, None, None, :], sin[length - queries:, None, None, :], rotary)
        k = rotate(k, cos[:, None, :], sin[:, None, :], rotary)
    with jax.named_scope("softmax"):
        o = sequence.blocked_attention(q, k, v, None, cd, OPERAND_PIECES)
    with jax.named_scope("gate"):
        o = attention_gate(o, gate)
    return _dot(o.reshape(n, queries, heads * head), p["o"], cd)


def step_counts(plan: tuple[str, ...], length: int, s: dict, cd) -> tuple[int, ...]:
    """What follows the routing's counters in STEP_STATS, a live row, from the
    shapes: the (query, key) pairs the full layers' tiles compute and those
    their masks keep (the last layer's one query where it is a full one), 1,
    and the state hand-overs and the positions of the linear layers' rules."""
    computed = seen = 0
    for i, kind in enumerate(plan):
        if kind == "full":
            pairs = sequence.blocked_pairs(
                1 if i == len(plan) - 1 else length, length, None, OPERAND_PIECES, _heads(s, cd))
            computed, seen = computed + pairs[0], seen + pairs[1]
    linear = plan.count("linear")
    return computed, seen, 1, linear * olmo_hybrid.delta_chunks(length)[1], linear * length


def forward(config: ModelConfig, params, batch) -> tuple[jax.Array, jax.Array]:
    """(the logit of every row, the step's counters): of the last layer, what
    follows its mixing along the positions at the last position alone."""
    s, cd, eps = _sizes(config), config.cdtype, config.layer_norm_eps
    plan = layer_plan(config)
    with jax.named_scope("embed"):
        # The weighted embedding in float32, where a bfloat16 row times a
        # float32 weight is exact.
        x = field_embed(params["embedding"], batch["feat_ids"], batch["feat_wts"], jnp.float32, s["hidden"])
        live = jnp.any(batch["feat_wts"] != 0, axis=1)  # a padded row is zero throughout
    moe = jnp.zeros((len(routed.STEP_STATS),), jnp.int32)
    router = functools.partial(route, normalise=s["norm_topk"])
    for i, (kind, layer) in enumerate(zip(plan, params["layers"])):
        last = i == len(plan) - 1
        a = rms0(layer["input_norm"], x, eps)
        if kind == "linear":
            with jax.named_scope("gdn"):
                mix = gated_delta_net(layer["linear"], a, s, cd, eps, last)
        else:
            with jax.named_scope("attn_full"):
                mix = gated_attention(layer["attn"], a, s, cd, eps, last)
        if last:
            x = sequence.last_position(x)
        h = x + mix
        out, counts = routed.routed_ffn(
            layer, rms0(layer["post_norm"], h, eps), s["top_k"], s["first"], 1.0, cd, OPERAND_PIECES, live,
            router=router)
        moe = moe + counts
        x = h + out
    with jax.named_scope("score"):
        final = rms0(params["final_norm"], x[:, -1], eps)
        rest = step_counts(plan, batch["feat_ids"].shape[1], s, cd)
        stats = jnp.concatenate([moe, jnp.sum(live, dtype=jnp.int32) * jnp.asarray(rest, jnp.int32)])
        return jnp.sum(final * params["score"].astype(jnp.float32), axis=-1), stats


def attention_plan(config: ModelConfig) -> tuple[tuple[tuple[str, object], ...], ...]:
    """Each layer's mixer as (name, value) pairs: a linear layer's kind,
    chunk, state hand-overs a row, bytes of a row's state, the side of the
    solve's blocks and the rule's key and value heads; a full layer's kind,
    window, block of queries and keys a block of the XLA path
    (`startup.attention` says which path serves), key-value heads, rotary
    dims, base, and that its output is gated."""
    s, length, out = _sizes(config), config.num_fields, []
    chunk, steps = olmo_hybrid.delta_chunks(length)
    for kind in layer_plan(config):
        if kind == "linear":
            out.append((("kind", kind), ("chunk", chunk), ("handovers_a_row", steps),
                        ("state_bytes_a_row", s["lin_v"] * s["dk"] * s["dv"] * 4),
                        ("solve_block", olmo_hybrid.SOLVE_BLOCK), ("key_heads", s["lin_k"]),
                        ("value_heads", s["lin_v"])))
        else:
            out.append((("kind", kind), ("window", 0), ("block", min(sequence.ATTN_BLOCK, length)),
                        ("keys_a_block", length), ("kv_heads", s["kv"]), ("rotary_dims", s["rotary"]),
                        ("theta", s["theta"]), ("gate", True)))
    return tuple(out)


@register_model("qwen3_next")
def build_qwen3_next(config: ModelConfig) -> Model:
    s = _sizes(config)
    plan = layer_plan(config)

    def init(rng, packed: bool = False):
        k_emb, k_score, k_norm, *k_layers = jax.random.split(rng, 3 + len(plan))
        dtype = config.pdtype
        # embedding_init scales by 1/sqrt(dim); INIT_STD is wanted.
        table = embedding_init(k_emb, config.vocab_size, s["hidden"], dtype, packed)
        return {
            "embedding": table * jnp.asarray(INIT_STD * s["hidden"] ** 0.5, dtype),
            "layers": [_layer_init(k, kind, s, dtype) for k, kind in zip(k_layers, plan)],
            "final_norm": _zero_centred(k_norm, s["hidden"], dtype),
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
        config=config, init=init, apply=apply, wts_in_compute_dtype=False, layer_plan=plan,
        expert_plan=expert_plan, attention_plan=attention_plan(config), apply_stats=apply_stats,
        step_stats=STEP_STATS)
