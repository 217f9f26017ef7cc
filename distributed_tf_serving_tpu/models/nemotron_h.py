"""nemotron_h: NVIDIA-Nemotron-3-Super-120B-A12B-BF16 (`model_type:
nemotron_h`) as a pointwise sequence ranker, through the same Predict path and
wire contract as the seven sequence families before it: a candidate row is
`num_fields` token ids (`feat_ids [n, L]`, folded by `% vocab_size`),
`feat_wts [n, L]` multiplies the token's embedding (`x0_t = w_t * E[id_t]`,
float32 on the link and in the product), and `prediction_node [n]` is the
sigmoid of one logit read at the last position, `s = w_score . RMS_f(x_L)`.

EVERY layer is ONE mixer behind ONE norm, `x <- x + MIX_i(RMS_i(x))`, and the
stack is a PATTERN of three kinds (`hybrid_override_pattern`, a letter a
layer; the layers run are its first `num_hidden_layers`): no layer holds both
a mixer along the row and a feed-forward part. `RMS(x) = w * x /
sqrt(mean(x^2) + eps)`, a plain weight. No biases but the convolution's.

  `M`, Mamba-2 (`falcon_h1.ssm`, whose docstring has the algebra and the
  chunked form; every multiplier 1): H heads of P channels, a `[P, N]` state a
  head, G groups of H / G heads sharing B and C (128 heads of 64, N 128, 8
  groups of 16 as published):
    [z: H P | x: H P | B: G N | C: G N | dt: H] = a W_in
    [x | B | C] <- silu(conv(.) + bias)     depthwise, causal, `mamba_d_conv` taps
    dt = softplus(dt + dt_bias) (no clamp);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,  S_0 = 0;   y_t = S_t C_t + D_h x_t
    y <- y * silu(z);  y <- RMS over each of the G groups of H P / G channels * w   (the gate first, then the norm)
    MIX = y W_out

  `*`, attention: q = a W_q (`heads` of d), k, v = a W_k, a W_v (`kv` heads of
  d), query head h reads key-value head h // (heads / kv); causal softmax of
  q k' / sqrt(d); MIX = concat_h(p v) W_o. NO rotary turn and no other
  position signal: the Mamba-2 layers carry the order.

  `E`, the LATENT routed block (`latent_moe`): the router and the shared
  expert read the full-width `a`; the routed experts live in a latent of
  `moe_latent_size` between two projections every token meets, and both
  kinds of expert are UNGATED, `relu(x U)^2 D`:
    p = sigmoid(a W_r) over ALL `n_routed_experts`, float32
    the `num_experts_per_tok` largest of p + bias (a selection bias an expert: it chooses and never weighs)
    g_e = routed_scaling_factor * p_e / (the sum of the chosen p + 1e-20)        from the UNBIASED scores
    a_lat = a W_in_lat;   r = sum over the chosen e of g_e * relu(a_lat U_e)^2 D_e       [latent]
    MIX = r W_out_lat + relu(a U_s)^2 D_s
  **The share** (`models/routed.py`): this chip routes over all the experts
  and sums `r` over the `experts_held` from `first_expert_held` on alone;
  `r W_out_lat` of that partial sum (linear, no bias: the shares still add
  up) plus the shared expert goes on to the next layer, and nothing stands
  in for the other chips. The mixers, the router, the latent projections, the
  shared expert and the norms are whole on every chip of the stated
  deployment.

What the served step skips (exact, and generic over the pattern:
`positions_plan`): the score reads the last position, so every trailing layer
that does not mix along the row (`E`) is computed at the last position alone;
of the LAST layer that does mix, what follows its mixing there alone (a
Mamba-2 layer's input projection, convolution and state walk run at all
positions, its gate, gated norm and output product at one; an attention
layer's keys and values at all positions, its queries and output at one);
every layer before it at all positions. A row whose weights are all zero (a
padded row) is left out of the experts and of every counter.

Numerics as the four routed families: parameters and matmul operands in
`compute_dtype`, float32 accumulation, residual, norms, softmax, convolution,
gates, `dt`, decays and the SSD's state; a float32 activation enters a product
as OPERAND_PIECES = 3 pieces of the compute dtype, the SSD's products between
activations included (`falcon_h1.ssm` takes the count from its caller); the
router's product, sigmoid and top-k float32 at `highest`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import falcon_h1, routed, sequence
from .base import Model, ModelConfig, register_model
from .embeddings import embedding_init, field_embed
from .routed import INIT_STD, matrix, rms_norm

# Pieces of the compute dtype a wider activation enters a product as: read at
# every call (tests and the benchmark's readings replace it by name).
OPERAND_PIECES = 3
STEP_STATS = routed.STEP_STATS + falcon_h1.STEP_STATS
# The pattern's letters and what `layer_plan` names them; the routed kind ends
# in `/moe`, by which the benchmark's readers count the routed layers.
KINDS = {"M": "mamba", "*": "attention", "E": "latent/moe"}
MIXES_ALONG_THE_ROW = ("mamba", "attention")
# A checkpoint's norm weights start at 1 and its selection bias at 0; a seeded
# tree draws them 1 + N(0, NORM_INIT_STD) and N(0, BIAS_INIT_STD), so that a
# norm left out or a bias used for the gates shows in the score.
NORM_INIT_STD = 0.1
BIAS_INIT_STD = 0.05


def layer_plan(config: ModelConfig) -> tuple[str, ...]:
    """The kind of every layer run: the first `num_hidden_layers` letters of
    `hybrid_override_pattern`, each one of KINDS."""
    pattern, layers = config.hybrid_override_pattern, config.num_hidden_layers
    unknown = sorted(set(pattern) - set(KINDS))
    if unknown or not 0 < layers <= len(pattern):
        raise ValueError(
            f"hybrid_override_pattern {pattern!r}: a letter of {sorted(KINDS)} a layer "
            f"(not {unknown}), num_hidden_layers {layers} of them at the least")
    return tuple(KINDS[letter] for letter in pattern[:layers])


def positions_plan(plan: tuple[str, ...]) -> tuple[str, ...]:
    """How much of each layer the score needs: `all` positions; `cut`, the
    LAST layer that mixes along the row, whose mixing runs at all positions
    and what follows it at the last one; `last`, every layer after it (none
    mixes along the row), at the last position alone."""
    mixing = [i for i, kind in enumerate(plan) if kind in MIXES_ALONG_THE_ROW]
    cut = mixing[-1] if mixing else -1
    return tuple("all" if i < cut else "cut" if i == cut else "last" for i in range(len(plan)))


def _sizes(config: ModelConfig) -> dict:
    heads, kv = config.num_attention_heads, config.num_key_value_heads
    if kv <= 0 or heads % kv:
        raise ValueError(f"num_key_value_heads {kv} of num_attention_heads {heads}: whole groups of query heads")
    head = config.head_dim or config.embed_dim // heads
    ssm_heads, ssm_head, groups = config.mamba_n_heads, config.mamba_d_head, config.mamba_n_groups
    if min(ssm_heads, ssm_head) <= 0 or ssm_heads * ssm_head != config.mamba_d_ssm:
        raise ValueError(f"mamba_d_ssm {config.mamba_d_ssm}: mamba_n_heads {ssm_heads} heads of mamba_d_head {ssm_head}")
    if groups <= 0 or ssm_heads % groups:
        raise ValueError(f"mamba_n_groups {groups} of mamba_n_heads {ssm_heads}: whole groups of heads share B and C")
    if min(config.mamba_d_state, config.mamba_d_conv, config.mamba_chunk_size) <= 0:
        raise ValueError("mamba_d_state, mamba_d_conv, mamba_chunk_size: positive")
    experts = config.n_routed_experts
    held = config.experts_held or experts
    if min(head, experts, config.moe_latent_size, config.moe_intermediate_size,
           config.moe_shared_expert_intermediate_size) <= 0:
        raise ValueError("head_dim, n_routed_experts, moe_latent_size, moe_intermediate_size, "
                         "moe_shared_expert_intermediate_size: positive")
    routed.check_share(experts, held, config.first_expert_held, config.num_experts_per_tok)
    d_ssm, state = config.mamba_d_ssm, config.mamba_d_state
    return {
        "hidden": config.embed_dim, "heads": heads, "kv": kv, "head": head,
        # the Mamba-2 mixer's, under `falcon_h1.ssm`'s names: every multiplier of that family 1 here
        "d_ssm": d_ssm, "ssm_heads": ssm_heads, "ssm_head": ssm_head, "state": state, "groups": groups,
        "taps": config.mamba_d_conv, "chunk": config.mamba_chunk_size,
        "widths": (d_ssm, d_ssm, groups * state, groups * state, ssm_heads), "channels": d_ssm + 2 * groups * state,
        "ssm_in": 1.0, "ssm_out": 1.0, "ssm_mults": (1.0,) * len(falcon_h1.SLICES),
        # the latent routed block's
        "latent": config.moe_latent_size, "expert": config.moe_intermediate_size,
        "shared": config.moe_shared_expert_intermediate_size, "experts": experts, "held": held,
        "first": config.first_expert_held, "top_k": config.num_experts_per_tok,
        "scaling": config.routed_scaling_factor, "norm_topk": bool(config.norm_topk_prob),
    }


def _norm_init(rng, width: int, dtype) -> jax.Array:
    return (1.0 + NORM_INIT_STD * jax.random.normal(rng, (width,))).astype(dtype)


def ungated_init(rng, shape_in: tuple, shape_out: tuple, dtype) -> dict:
    k_up, k_down = jax.random.split(rng)
    return {"up": matrix(k_up, shape_in, dtype), "down": matrix(k_down, shape_out, dtype)}


def _layer_init(rng, kind: str, s: dict, dtype) -> dict:
    """One layer's tree: its norm and, under its kind's name, its one mixer."""
    k_norm, k_mix, k_q, k_k, k_v, k_o, k_router, k_bias, k_in, k_out, k_shared, k_experts = jax.random.split(rng, 12)
    hidden, head, latent, held = s["hidden"], s["head"], s["latent"], s["held"]
    layer = {"norm": _norm_init(k_norm, hidden, dtype)}
    if kind == "mamba":
        layer["ssm"] = falcon_h1._ssm_init(k_mix, s, dtype)
    elif kind == "attention":
        layer["attn"] = {
            "q": matrix(k_q, (hidden, s["heads"] * head), dtype), "k": matrix(k_k, (hidden, s["kv"] * head), dtype),
            "v": matrix(k_v, (hidden, s["kv"] * head), dtype), "o": matrix(k_o, (s["heads"] * head, hidden), dtype),
        }
    else:
        layer["moe"] = {
            "router": matrix(k_router, (hidden, s["experts"]), dtype),
            # float32 whatever the parameters' dtype, as the checkpoint keeps it
            "router_bias": BIAS_INIT_STD * jax.random.normal(k_bias, (s["experts"],), jnp.float32),
            "latent_in": matrix(k_in, (hidden, latent), dtype), "latent_out": matrix(k_out, (latent, hidden), dtype),
            "shared": ungated_init(k_shared, (hidden, s["shared"]), (s["shared"], hidden), dtype),
            "experts": ungated_init(k_experts, (held, latent, s["expert"]), (held, s["expert"], latent), dtype),
        }
    return layer


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def _dot(x: jax.Array, w: jax.Array, cd) -> jax.Array:
    """`routed.dot` at this family's pieces."""
    return routed.dot(x, w, cd, OPERAND_PIECES)


def route(router: jax.Array, bias: jax.Array, x: jax.Array, s: dict):
    """`routed.route` with this family's router: a sigmoid an expert, the
    selection bias, the top-k normalised (where the config says) and scaled."""
    return routed.route(router, x, s["top_k"], s["scaling"], "sigmoid", s["norm_topk"], bias)


def attention(p: dict, a: jax.Array, s: dict, cd, last_only: bool = False) -> jax.Array:
    """One `*` layer's attention of the normed `a [n, L, hidden]`: `[n, L,
    hidden]`, or `[n, 1, hidden]` for the last position's query alone against
    the keys and values of every position. No position signal. The caller's
    `attn_full` scope."""
    n, length, _ = a.shape
    heads, kv, head = s["heads"], s["kv"], s["head"]
    at = sequence.last_position(a) if last_only else a
    queries = at.shape[1]
    with jax.named_scope("qkv"):
        q = _dot(at, p["q"], cd).reshape(n, queries, kv, heads // kv, head)
        k = _dot(a, p["k"], cd).reshape(n, length, kv, head)
        v = _dot(a, p["v"], cd).reshape(n, length, kv, head)
    with jax.named_scope("softmax"):
        o = sequence.blocked_attention(q, k, v, None, cd, OPERAND_PIECES)
    return _dot(o.reshape(n, queries, heads * head), p["o"], cd)


def latent_moe(p: dict, a: jax.Array, s: dict, cd, live: jax.Array | None = None):
    """One `E` layer's block of the normed `a [n, positions, hidden]`: the
    shared expert at the full width plus the held experts' part through the
    latent; and this layer's counters, int32 `[len(routed.STEP_STATS)]`.
    `live [n]` is false for the rows that are zero throughout."""
    x = a.reshape(-1, a.shape[-1])
    if live is not None:
        live = jnp.repeat(live, a.shape[1])
    chosen, gates, _ = route(p["router"], p["router_bias"], x, s)
    with jax.named_scope("shared_expert"):
        shared = routed.relu2_mlp(p["shared"], x, cd, OPERAND_PIECES)
    with jax.named_scope("latent_in"):
        latent = _dot(x, p["latent_in"], cd)
    with jax.named_scope("experts"):
        held, took, computed = routed.held_experts(
            p["experts"], latent, chosen, gates, s["first"], cd, live=live, count=OPERAND_PIECES)
    with jax.named_scope("latent_out"):
        out = shared + _dot(held, p["latent_out"], cd)
    tokens = jnp.int32(x.shape[0]) if live is None else jnp.sum(live, dtype=jnp.int32)
    return out.reshape(a.shape), jnp.stack(
        [tokens, jnp.sum(took), jnp.max(took), computed, jnp.sum(took > 0, dtype=jnp.int32)])


def _heads(s: dict, cd) -> sequence.Heads:
    return sequence.Heads((s["head"],), s["head"], s["heads"] // s["kv"], cd)


def step_counts(plan: tuple[str, ...], length: int, s: dict, cd) -> tuple[int, ...]:
    """What follows the routing's counters in STEP_STATS, a live row, from the
    shapes (`falcon_h1.STEP_STATS`): the (query, key) pairs the attention
    layers' tiles compute and those their masks keep (one query where the
    layer is the one cut to the last position), 1, and the state hand-overs
    and the positions of the Mamba-2 layers' SSDs. A layer at the last
    position alone (`positions_plan`) mixes nothing along the row."""
    computed = seen = 0
    for kind, positions in zip(plan, positions_plan(plan)):
        if kind == "attention":
            pairs = sequence.blocked_pairs(1 if positions == "cut" else length, length, None, OPERAND_PIECES, _heads(s, cd))
            computed, seen = computed + pairs[0], seen + pairs[1]
    mambas = plan.count("mamba")
    return computed, seen, 1, mambas * falcon_h1.ssd_chunks(length, s["chunk"])[1], mambas * length


def forward(config: ModelConfig, params, batch) -> tuple[jax.Array, jax.Array]:
    """(the logit of every row, the step's counters), each layer over the
    positions `positions_plan` gives it."""
    s, cd, eps = _sizes(config), config.cdtype, config.layer_norm_eps
    plan = layer_plan(config)
    with jax.named_scope("embed"):
        # The weighted embedding in float32, where a bfloat16 row times a
        # float32 weight is exact.
        x = field_embed(params["embedding"], batch["feat_ids"], batch["feat_wts"], jnp.float32, s["hidden"])
        live = jnp.any(batch["feat_wts"] != 0, axis=1)  # a padded row is zero throughout
    moe = jnp.zeros((len(routed.STEP_STATS),), jnp.int32)
    for kind, positions, layer in zip(plan, positions_plan(plan), params["layers"]):
        if positions == "last" and x.shape[1] > 1:  # a stack that mixes nowhere along the row
            x = sequence.last_position(x)
        a = rms_norm(layer["norm"], x, eps)
        cut = positions == "cut"
        if kind == "mamba":
            with jax.named_scope("ssm"):
                mix = falcon_h1.ssm(layer["ssm"], a, s, cd, eps, cut, OPERAND_PIECES)
        elif kind == "attention":
            with jax.named_scope("attn_full"):
                mix = attention(layer["attn"], a, s, cd, cut)
        else:
            mix, counts = latent_moe(layer["moe"], a, s, cd, live)
            moe = moe + counts
        if cut:
            x = sequence.last_position(x)
        x = x + mix
    with jax.named_scope("score"):
        final = rms_norm(params["final_norm"], x[:, -1], eps)
        rest = step_counts(plan, batch["feat_ids"].shape[1], s, cd)
        stats = jnp.concatenate([moe, jnp.sum(live, dtype=jnp.int32) * jnp.asarray(rest, jnp.int32)])
        return jnp.sum(final * params["score"].astype(jnp.float32), axis=-1), stats


def attention_plan(config: ModelConfig) -> tuple[tuple[tuple[str, object], ...], ...]:
    """Each layer's mixer as (name, value) pairs: a Mamba-2 layer's kind,
    chunk, state hand-overs a row and bytes of a row's state (as falcon_h1
    states them under `ssd`); an attention layer's kind, window, block of
    queries and keys a block of the XLA path (`startup.attention` says which
    path serves), its key-value heads and that nothing turns it (`rotary_dims`
    0); a routed layer's kind, the latent's width and the experts' form."""
    s, length, out = _sizes(config), config.num_fields, []
    walk = falcon_h1.ssd_choice(length, s)
    for kind in layer_plan(config):
        if kind == "mamba":
            out.append((("kind", "ssd"), ("chunk", walk["chunk"]),
                        ("handovers_a_row", falcon_h1.ssd_chunks(length, s["chunk"])[1]),
                        ("state_bytes_a_row", walk["state_bytes_a_row"])))
        elif kind == "attention":
            out.append((("kind", "full"), ("window", 0), ("block", min(sequence.ATTN_BLOCK, length)),
                        ("keys_a_block", length), ("kv_heads", s["kv"]), ("rotary_dims", 0)))
        else:
            out.append((("kind", kind), ("latent", s["latent"]), ("form", "relu2")))
    return tuple(out)


@register_model("nemotron_h")
def build_nemotron_h(config: ModelConfig) -> Model:
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
        config=config, init=init, apply=apply, wts_in_compute_dtype=False, layer_plan=plan,
        expert_plan=expert_plan, attention_plan=attention_plan(config), apply_stats=apply_stats,
        step_stats=STEP_STATS)
