"""exaone_moe: K-EXAONE-236B-A23B (`model_type: exaone_moe`) as a pointwise
sequence ranker, through the same Predict path and wire contract as
`phi4flash` and `pangu_moe`: a candidate row is `num_fields` token ids
(`feat_ids [n, L]`, folded by `% vocab_size`), `feat_wts [n, L]` multiplies
the token's embedding (`x0_t = w_t * E[id_t]`, float32 on the link and in the
product), and `prediction_node [n]` is the sigmoid of one logit read at the
last position, `s = w_score . RMS_final(h_L)`.

The attention differs BY LAYER, from a plan in the configuration
(`layer_types`: `sliding_attention` or `full_attention`, three to one as
published). A layer normalises each sub-layer's OUTPUT before the residual
add and has no norm before it (EXAONE 4.0, arXiv:2507.11407), two learned
RMSNorm weights a layer, and one more a query and a key head:

  q = x W_q [heads x d];  k = x W_k [kv x d];  v = x W_v [kv x d]      no biases
  q <- RMS_q(q), k <- RMS_k(k)            per head, one learned [d] weight each a layer
  sliding layers: rotary on all d dims of q and k (pairs (i, i + d/2), angle
                  t * theta ** (-2i / d)); full layers: none
  query head h reads key-value head h // (heads / kv);  scores = q k' / sqrt(d)
  seen(t, u) = u <= t, and t - u < sliding_window on a sliding layer
  attn = concat_h(softmax(scores | seen) v) W_o
  h = x + RMS_post_attn(attn);   y = h + RMS_post_ffn(FFN(h))

FFN of the `first_k_dense_replace` leading layers: `(silu(h W_g) * (h W_u)) W_d`
at `intermediate_size`. Of the others the shared expert of that form at
`moe_intermediate_size`, whole, plus the routed layer of `models/routed.py`:
sigmoid scores over ALL `num_experts`, the top `num_experts_per_tok`
(one group, the selection bias zero), normalised, times
`routed_scaling_factor`; this chip computes `g_e * expert_e(h)` for the
`experts_held` from `first_expert_held` on and leaves the others' part out.
The attention is whole on every chip of the stated deployment.

**Blocks follow the layer's kind.** In a one-chip served entry on a TPU every
layer but the last runs `sequence.attention`, ONE Pallas kernel a layer whose
tiles follow the kind too (ops/attention_kernel.py: 512 x 512, 128 x 128 for a
window of 128, and no key block a mask throws away whole). Everywhere else,
the XLA path: a full layer takes `sequence`'s blocks of
ATTN_BLOCK queries against every key up to the block's end. A sliding layer
takes blocks of `sliding_window` queries, ALL of them in one batched product,
each against the key block before its own and its own: 2 x window keys a
block, where `sequence.query_blocks` at 512 would read 639 keys a block for
a window of 128 and mask four fifths of the tile. The step counts both
(`attn.scores_computed`, `attn.scores_seen`: (query, key) pairs its tiles
compute and those the masks keep, a live row and layer; every head computes
the same pairs), and `Model.attention_plan` states each layer's kind, window,
block and keys a block.

What the served step skips (exact): the score reads the last position, so the
LAST layer's queries, attention output and FFN are computed there alone; its
keys and values at the positions that query can see (the last `sliding_window`
where it is a sliding layer, else all), and every layer before it at all
positions. A row whose weights are all zero (a padded row) is zero at every
position of every layer and is left out of the experts and of every counter.

Numerics as `pangu_moe`, and for its reason (a routed model): parameters and
matmul operands in `compute_dtype`, float32 accumulation, residual, norms,
rotary and softmax; a float32 activation enters a product as OPERAND_PIECES
= 3 pieces of the compute dtype, one product a weight; the router's product,
sigmoid and top-k float32 at `highest` precision.
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
STEP_STATS = routed.STEP_STATS + ("attn.scores_computed", "attn.scores_seen")
KINDS = {"sliding_attention": "window", "full_attention": "full"}
PERIOD = ("sliding_attention",) * 3 + ("full_attention",)  # `sliding_window_pattern: "LLLG"`


def layer_plan(config: ModelConfig) -> tuple[tuple[str, str], ...]:
    """(attention kind, FFN kind) of every layer: `window` or `full`, `dense` or `moe`."""
    layers, dense = config.num_hidden_layers, config.first_k_dense_replace
    kinds = config.layer_types or (PERIOD * layers)[:layers]
    if len(kinds) != layers or set(kinds) - set(KINDS):
        raise ValueError(
            f"layer_types {kinds}: one of {sorted(KINDS)} for each of num_hidden_layers {layers}")
    if not 0 <= dense <= layers:
        raise ValueError(f"first_k_dense_replace {dense} of num_hidden_layers {layers}")
    return tuple((KINDS[kind], "dense" if i < dense else "moe") for i, kind in enumerate(kinds))


def _sizes(config: ModelConfig) -> dict[str, int]:
    experts = config.num_experts
    held = config.experts_held or experts
    heads, kv = config.num_attention_heads, config.num_key_value_heads
    if kv <= 0 or heads % kv:
        raise ValueError(f"num_key_value_heads {kv} of num_attention_heads {heads}: whole groups of query heads")
    head = config.head_dim or config.embed_dim // heads
    if head <= 0 or head % 2:
        raise ValueError(f"head_dim {head}: the rotary turn takes pairs")
    if config.sliding_window <= 0:
        raise ValueError(f"sliding_window {config.sliding_window}")
    if experts <= 0:
        raise ValueError(f"num_experts {experts}: the router's width")
    routed.check_share(experts, held, config.first_expert_held, config.num_experts_per_tok)
    return {
        "hidden": config.embed_dim, "inter": config.intermediate_size, "heads": heads, "kv": kv, "head": head,
        "window": config.sliding_window, "expert": config.moe_intermediate_size, "experts": experts,
        "held": held, "first": config.first_expert_held, "top_k": config.num_experts_per_tok,
    }


def _layer_init(rng, ffn: str, s: dict, dtype) -> dict:
    k_q, k_k, k_v, k_o, k_mlp, k_router, k_experts = jax.random.split(rng, 7)
    hidden, head = s["hidden"], s["head"]
    ones = lambda width: jnp.ones((width,), dtype)  # noqa: E731
    layer = {
        "post_attn_norm": ones(hidden), "post_ffn_norm": ones(hidden),
        "attn": {
            "q": matrix(k_q, (hidden, s["heads"] * head), dtype), "q_norm": ones(head),
            "k": matrix(k_k, (hidden, s["kv"] * head), dtype), "k_norm": ones(head),
            "v": matrix(k_v, (hidden, s["kv"] * head), dtype),
            "o": matrix(k_o, (s["heads"] * head, hidden), dtype),
        },
    }
    if ffn == "dense":
        layer["mlp"] = gated_init(k_mlp, (hidden, s["inter"]), (s["inter"], hidden), dtype)
    else:
        width, held = s["expert"], s["held"]
        layer["router"] = matrix(k_router, (hidden, s["experts"]), dtype)
        layer["shared"] = gated_init(k_mlp, (hidden, width), (width, hidden), dtype)
        layer["experts"] = gated_init(k_experts, (held, hidden, width), (held, width, hidden), dtype)
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


def band_blocks(length: int, window: int, block: int) -> tuple[int, int]:
    """(query blocks of `block` over `length` positions, key blocks BEFORE its
    own that a query block's window reaches)."""
    return -(-length // block), -(-(window - 1) // block)


def step_pairs(kinds: tuple[str, ...], length: int, window: int) -> tuple[int, int]:
    """((query, key) pairs the tiles of the served step's attention compute
    over a row, those its masks keep), summed over the layers `kinds`: every
    layer but the last at all positions in its kind's blocks, the last layer's
    one query against the keys it reads."""
    computed = seen = 0
    for i, kind in enumerate(kinds):
        reach = window if kind == "window" else None
        if i == len(kinds) - 1:
            keys = length if reach is None else min(length, reach)
            computed, seen = computed + keys, seen + keys
        elif reach is None or sequence.kernel_serves(length):
            blocks = sequence.blocked_pairs(length, length, reach)
            computed, seen = computed + blocks[0], seen + blocks[1]
        else:
            blocks, back = band_blocks(length, window, window)
            computed += blocks * window * (back + 1) * window
            seen += sum(min(t + 1, window) for t in range(length))
    return computed, seen


def band_attention(q: jax.Array, k: jax.Array, v: jax.Array, window: int, cd, block: int | None = None) -> jax.Array:
    """Causal attention within `window` positions at ALL positions, every block
    in one batched product: `q [n, L, G, J, d]` (J query heads a key-value
    head), `k`, `v [n, L, G, d]`; returns `[n, L, G, J, d]` float32. A block of
    `block` queries (the window itself unless given) reads its own key block
    and as many before it as its window reaches: position t sees
    t - window + 1 .. t. The rows are padded to whole blocks; a padded query
    sees itself, and no query sees a padded key."""
    n, length, groups, per_group, head = q.shape
    block = block or window
    blocks, back = band_blocks(length, window, block)
    padded = blocks * block

    def in_blocks(x, before):  # [n, L, ...] -> [n, blocks, block, ...], shifted `before` blocks back
        x = jnp.pad(x, ((0, 0), (before * block, padded - length)) + ((0, 0),) * (x.ndim - 2))
        return x[:, :padded].reshape((n, blocks, block) + x.shape[2:])

    q_b = in_blocks(q, 0)
    k_b = jnp.concatenate([in_blocks(k, j) for j in range(back, -1, -1)], axis=2)
    v_b = jnp.concatenate([in_blocks(v, j) for j in range(back, -1, -1)], axis=2)
    scores = _product("nbqgjd,nbkgd->nbgjqk", q_b, k_b, cd) * head ** -0.5
    first = jnp.arange(blocks)[:, None, None] * block
    q_pos = first + jnp.arange(block)[None, :, None]
    k_pos = first - back * block + jnp.arange((back + 1) * block)[None, None, :]
    seen = (k_pos <= q_pos) & (q_pos - k_pos < window) & (k_pos >= 0)  # [blocks, block, keys a block]
    probs = jax.nn.softmax(jnp.where(seen[None, :, None, None], scores, -jnp.inf), axis=-1)
    out = _product("nbgjqk,nbkgd->nbqgjd", probs, v_b, cd)
    return out.reshape(n, padded, groups, per_group, head)[:, :length]


def blocked_attention(q: jax.Array, k: jax.Array, v: jax.Array, window: int | None, cd) -> jax.Array:
    """`sequence.blocked_attention` at this family's pieces: a full layer at
    all positions, either kind at the last position alone."""
    return sequence.blocked_attention(q, k, v, window, cd, OPERAND_PIECES)


def attention(p: dict, x: jax.Array, s: dict, kind: str, cd, eps: float, theta: float,
              last_only: bool = False) -> jax.Array:
    """One layer's attention of `x [n, L, H]`: `[n, L, H]`, or `[n, 1, H]` for
    the last position's query alone, whose keys and values are computed at the
    positions it sees. The caller's `attn_window` or `attn_full` scope."""
    n, length, _ = x.shape
    heads, kv, head = s["heads"], s["kv"], s["head"]
    window = s["window"] if kind == "window" else None
    reach = x[:, length - window:] if last_only and window is not None and window < length else x
    keys = reach.shape[1]
    if last_only:
        x = sequence.last_position(x)
    queries = x.shape[1]
    with jax.named_scope("qkv"):
        q = _dot(x, p["q"], cd).reshape(n, queries, kv, heads // kv, head)
        k = _dot(reach, p["k"], cd).reshape(n, keys, kv, head)
        v = _dot(reach, p["v"], cd).reshape(n, keys, kv, head)
    with jax.named_scope("qk_norm"):
        q, k = rms_norm(p["q_norm"], q, eps), rms_norm(p["k_norm"], k, eps)
    if window is not None:
        with jax.named_scope("rope"):
            cos, sin = rope_table(length, head, theta)
            q = rotate(q, cos[length - queries:, None, None, :], sin[length - queries:, None, None, :])
            k = rotate(k, cos[length - keys:, None, :], sin[length - keys:, None, :])
    with jax.named_scope("softmax"):
        if window is not None and queries > 1 and not sequence.kernel_serves(queries):
            o = band_attention(q, k, v, window, cd)
        else:  # the kernel where it serves, whose tiles follow the window themselves
            o = blocked_attention(q, k, v, window, cd)
    return _dot(o.reshape(n, queries, heads * head), p["o"], cd)


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
    for i, ((kind, ffn), layer) in enumerate(zip(plan, params["layers"])):
        last = i == len(plan) - 1
        with jax.named_scope(f"attn_{kind}"):
            mix = attention(layer["attn"], x, s, kind, cd, eps, config.rope_theta, last)
        if last:
            x = sequence.last_position(x)
        h = x + rms_norm(layer["post_attn_norm"], mix, eps)
        if ffn == "dense":
            with jax.named_scope("dense_mlp"):
                out = routed.gated_mlp(layer["mlp"], h, cd, OPERAND_PIECES)
        else:
            out, counts = routed.routed_ffn(
                layer, h, s["top_k"], s["first"], config.routed_scaling_factor, cd, OPERAND_PIECES, live)
            moe = moe + counts
        x = h + rms_norm(layer["post_ffn_norm"], out, eps)
    with jax.named_scope("score"):
        final = rms_norm(params["final_norm"], x[:, -1], eps)
        # (query, key) pairs a row, from the shapes alone, times the live rows
        pairs = step_pairs(tuple(kind for kind, _ in plan), batch["feat_ids"].shape[1], s["window"])
        stats = jnp.concatenate([moe, jnp.sum(live, dtype=jnp.int32) * jnp.asarray(pairs, jnp.int32)])
        return jnp.sum(final * params["score"].astype(jnp.float32), axis=-1), stats


def attention_plan(config: ModelConfig) -> tuple[tuple[tuple[str, object], ...], ...]:
    """Each layer's kind, window, block of queries and the most keys a block
    reads, at all positions, as (name, value) pairs."""
    s, length, out = _sizes(config), config.num_fields, []
    for kind, _ffn in layer_plan(config):
        if kind == "window":
            block = s["window"]
            keys = block * (band_blocks(length, block, block)[1] + 1)
        else:
            block, keys = min(sequence.ATTN_BLOCK, length), length
        out.append((("kind", kind), ("window", s["window"] if kind == "window" else 0),
                    ("block", block), ("keys_a_block", keys)))
    return tuple(out)


@register_model("exaone_moe")
def build_exaone_moe(config: ModelConfig) -> Model:
    s = _sizes(config)
    plan = layer_plan(config)

    def init(rng, packed: bool = False):
        k_emb, k_score, *k_layers = jax.random.split(rng, 2 + len(plan))
        dtype = config.pdtype
        # embedding_init scales by 1/sqrt(dim); INIT_STD is wanted.
        table = embedding_init(k_emb, config.vocab_size, s["hidden"], dtype, packed)
        return {
            "embedding": table * jnp.asarray(INIT_STD * s["hidden"] ** 0.5, dtype),
            "layers": [_layer_init(k, ffn, s, dtype) for k, (_kind, ffn) in zip(k_layers, plan)],
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
