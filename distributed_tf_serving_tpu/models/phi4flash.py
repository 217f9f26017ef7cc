"""phi4flash: Phi-4-mini-flash-reasoning (SambaY, "Decoder-Hybrid-Decoder
Architecture for Efficient Reasoning with Long Generation") as a pointwise
sequence ranker: a candidate row is a SEQUENCE of `num_fields` token ids, and
the score is one logit read at the last position (the form of a
`...ForSequenceClassification` head with one label), through the same
Predict path as every CTR family.

Wire contract: `feat_ids [n, L]` are L token ids a row, folded by
`% vocab_size` like every model's ids; `feat_wts [n, L]` multiplies the
token's embedding (`x0_t = w_t * E[id_t]`, through `field_embed`, float32
on the link and in the product);
`prediction_node [n] = sigmoid(s)`. L is fixed (the wire has no ragged rows)
and there is no positional encoding of any kind, as published.

Every layer is pre-norm with two residuals,
`h = x + mix(LN1(x)); y = h + W_down(silu(G) * U)`, `[G, U] = W_gate_up LN2(h)`
(the published fused `gate_up_proj` is held as its two halves, `gate` and
`up`: two matrix products of half the size, the same numbers),
and `mix` is one of five kinds laid out by the published constructor's rule
(`layer_plan`): Mamba selective-scan layers alternating with sliding-window
differential attention in the first half (the self-decoder), one full
differential attention layer whose keys and values are kept, then gated
memory units (which read the last Mamba layer's scan output, no mixing along
positions) alternating with cross attention against those kept keys and
values (the cross-decoder).

What the served step uses that a plain forward pass does not: the score
reads the last position only, and the layers after the full-attention one
mix nothing along positions except through its K and V. So `apply` computes
layers `0 .. N/2` at all L positions, K and V of layer `N/2 + 1` at all
positions, and everything else at the LAST position only. That is exact
(the benchmark's plain reference computes every layer at every position; the
tests hold the two together). Nothing else is left out or approximated.

Numerics: parameters and matmul operands in `compute_dtype` (bfloat16 as
served), float32 accumulation, and float32 for the residual stream, the
norms, the softmax, the convolution and the scan's state. A float32
activation enters a product as OPERAND_PIECES arrays of the compute dtype:
its rounding to bfloat16 and the rounding of what that left (16 bits of
mantissa: two passes of the MXU against a bfloat16 weight, three where both
operands are activations; `sequence.product` makes either ONE product).
Rounded to one bfloat16 piece the activations alone put 0.02 rms on a logit of
standard deviation 1 at the published widths, half of what computing wholly
in bfloat16 costs, and no comparison of a few scores could tell the stated
precision from the one below it.

Head convention (a permutation of projection columns: under seeded random
weights every convention is the same model): query heads `2h, 2h+1` are
`q1, q2` of differential head `h`; key heads `2g, 2g+1` are `k1, k2` of
key-value group `g`; value heads `2g, 2g+1` side by side are its one value
of twice the head size; differential head `h` reads group `h // r`, `r`
differential heads a group.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import sequence
from .base import Model, ModelConfig, register_model
from .embeddings import embedding_init, field_embed

INIT_STD = 0.02  # matrices, the embedding and the score vector
LAMBDA_STD = 0.1  # the four lambda vectors of a differential attention layer
RMS_EPS = 1e-5  # the per-head RMSNorm of the differential attention
# Positions a step of the chunked scan's loop advances: the recurrence runs
# position by position inside a chunk, unrolled, so the loop's overhead and
# the state's round trip through memory are paid once a chunk.
SCAN_CHUNK = 16
# Pieces of the compute dtype a wider activation enters a product as: read at
# every call of `_product` (models/sequence.py has the product itself).
OPERAND_PIECES = sequence.OPERAND_PIECES


def layer_plan(num_layers: int) -> tuple[str, ...]:
    """The kind of every layer, by the published constructor's rule: with
    N layers, layer i is `mamba` (i even, i <= N/2), `window` (i odd,
    i < N/2), `full` (i = N/2 + 1), `gmu` (i even, i >= N/2 + 2) or `cross`
    (i odd, i >= N/2 + 3)."""
    if num_layers < 8 or num_layers % 4:
        raise ValueError(
            f"num_hidden_layers must be a multiple of 4 and at least 8, got {num_layers}: "
            "layer N/2 has to be a Mamba layer, followed by the full-attention layer, a "
            "gated memory unit and a cross-attention layer"
        )
    half = num_layers // 2
    plan = []
    for i in range(num_layers):
        if i <= half:
            plan.append("mamba" if i % 2 == 0 else "window")
        elif i == half + 1:
            plan.append("full")
        else:
            plan.append("gmu" if i % 2 == 0 else "cross")
    return tuple(plan)


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _sizes(config: ModelConfig) -> dict[str, int]:
    hidden, heads, kv = config.embed_dim, config.num_attention_heads, config.num_key_value_heads
    if hidden % heads or heads % 2 or kv % 2 or (heads // 2) % (kv // 2):
        raise ValueError(
            f"embed_dim {hidden}, num_attention_heads {heads}, num_key_value_heads {kv}: "
            "heads must divide the width, both counts be even (a differential head is a "
            "pair) and the key-value pairs divide the query pairs"
        )
    return {
        "hidden": hidden, "inter": config.mlp_dims[0], "heads": heads, "kv": kv,
        "head": hidden // heads, "inner": config.ssm_expand * hidden,
        "state": config.ssm_state, "conv": config.ssm_conv,
        "dt_rank": -(-hidden // 16),
    }


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _matrix(rng, shape, dtype, std=INIT_STD):
    return jax.random.normal(rng, shape, dtype) * jnp.asarray(std, dtype)


def _norm_init(width: int, dtype) -> dict:
    return {"w": jnp.ones((width,), dtype), "b": jnp.zeros((width,), dtype)}


def _diff_init(rng, head: int, dtype) -> dict:
    keys = jax.random.split(rng, 4)
    names = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
    out = {n: _matrix(k, (head,), dtype, LAMBDA_STD) for n, k in zip(names, keys)}
    out["subln"] = jnp.ones((2 * head,), dtype)
    return out


def _mamba_init(rng, s: dict, dtype) -> dict:
    k_in, k_conv, k_cb, k_x, k_dt, k_bias, k_out = jax.random.split(rng, 7)
    inner, state = s["inner"], s["state"]
    # The Mamba defaults: dt log-uniform in [1e-3, 1e-1] through the inverse
    # of softplus, A = -(1 .. d_state) in every channel, D = 1; the depthwise
    # convolution as torch's Conv1d draws it (uniform, bound 1/sqrt(width)).
    dt = jnp.exp(
        jax.random.uniform(k_bias, (inner,)) * (math.log(0.1) - math.log(0.001)) + math.log(0.001)
    ).clip(1e-4)
    bound = s["conv"] ** -0.5
    return {
        "in_proj": _matrix(k_in, (s["hidden"], 2 * inner), dtype),
        "conv_w": jax.random.uniform(k_conv, (inner, s["conv"]), dtype, -bound, bound),
        "conv_b": jax.random.uniform(k_cb, (inner,), dtype, -bound, bound),
        "x_proj": _matrix(k_x, (inner, s["dt_rank"] + 2 * state), dtype),
        "dt_proj": _matrix(k_dt, (s["dt_rank"], inner), dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, state + 1, dtype=jnp.float32)), (inner, state)).astype(dtype),
        "D": jnp.ones((inner,), dtype),
        "out_proj": _matrix(k_out, (inner, s["hidden"]), dtype),
    }


def _layer_init(rng, kind: str, s: dict, dtype) -> dict:
    k_mix, k_up, k_down, k_a, k_b, k_c = jax.random.split(rng, 6)
    hidden, kv_width = s["hidden"], s["kv"] * s["head"]
    layer = {
        "ln1": _norm_init(hidden, dtype), "ln2": _norm_init(hidden, dtype),
        "gate": _matrix(k_up, (hidden, s["inter"]), dtype),
        "up": _matrix(k_b, (hidden, s["inter"]), dtype),
        "down": _matrix(k_down, (s["inter"], hidden), dtype),
    }
    if kind == "mamba":
        layer["mamba"] = _mamba_init(k_mix, s, dtype)
    elif kind in ("window", "full"):
        layer["attn"] = {
            "qkv": _matrix(k_mix, (hidden, hidden + 2 * kv_width), dtype),
            "o": _matrix(k_a, (hidden, hidden), dtype), **_diff_init(k_c, s["head"], dtype),
        }
    elif kind == "gmu":
        layer["gmu"] = {
            "in_proj": _matrix(k_mix, (hidden, s["inner"]), dtype),
            "out_proj": _matrix(k_a, (s["inner"], hidden), dtype),
        }
    else:
        layer["cross"] = {
            "q": _matrix(k_mix, (hidden, hidden), dtype),
            "o": _matrix(k_a, (hidden, hidden), dtype), **_diff_init(k_c, s["head"], dtype),
        }
    return layer


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def _product(spec: str, x: jax.Array, y: jax.Array, cd) -> jax.Array:
    """einsum(spec, x, y) as `sequence.product`, at this family's pieces."""
    return sequence.product(spec, x, y, cd, OPERAND_PIECES)


def _dot(x: jax.Array, w: jax.Array, cd) -> jax.Array:
    """x @ w, as `_product` (at this family's two pieces ONE product, the
    pieces along a second contracted axis: `sequence.product` has the form)."""
    return _product("...k,kn->...n", x, w, cd)


def _layer_norm(p: dict, x: jax.Array, eps: float) -> jax.Array:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    centred = x - mean
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(var + eps) * p["w"].astype(jnp.float32) + p["b"].astype(jnp.float32)


def selective_scan(u, delta, a, b, c, chunk: int = SCAN_CHUNK):
    """The selective state-space recurrence, chunked.

      S_t = exp(delta_t (x) A) . S_{t-1} + (delta_t . u_t) (x) B_t,   S_0 = 0
      y_t = S_t C_t

    u, delta [n, L, Di] float32; a [Di, N] (negative); b, c [n, L, N];
    returns y [n, L, Di] float32. A `lax.scan` over chunks of `chunk`
    positions with the recurrence unrolled inside a chunk, the state
    `[n, N, Di]` (channels along the lanes) carried in float32: the
    same arithmetic in the same order as the position-by-position loop, so
    no [n, L, Di, N] tensor exists. A length that is no multiple of the chunk
    is padded with delta = 0, which leaves the state as it is."""
    with jax.named_scope("scan"):
        n, length, inner = u.shape
        pad = -length % chunk

        def chunks(x):  # [n, L, w] -> [L/chunk, chunk, n, w], time-major
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            return jnp.moveaxis(x, 1, 0).reshape(-1, chunk, n, x.shape[-1])

        a_t = a.T[None].astype(jnp.float32)  # [1, N, Di]

        def body(state, xs):
            d, du, b_k, c_k = xs
            ys = []
            for t in range(chunk):
                decay = jnp.exp(d[t][:, None, :] * a_t)
                state = decay * state + du[t][:, None, :] * b_k[t][:, :, None]
                ys.append(jnp.sum(state * c_k[t][:, :, None], axis=1))
            return state, jnp.stack(ys)

        state0 = jnp.zeros((n, a.shape[1], inner), jnp.float32)
        _, y = jax.lax.scan(body, state0, (chunks(delta), chunks(delta * u), chunks(b), chunks(c)))
        return jnp.moveaxis(y.reshape(-1, n, inner), 0, 1)[:, :length]


def _mamba(p: dict, x: jax.Array, s: dict, cd) -> tuple[jax.Array, jax.Array]:
    """(mix [n, L, H], the scan's output before the gate [n, L, Di])."""
    with jax.named_scope("ssm"):
        inner, state, rank = s["inner"], s["state"], s["dt_rank"]
        uz = _dot(x, p["in_proj"], cd)
        u, z = uz[..., :inner], uz[..., inner:]
        u = sequence.causal_conv(u, p["conv_w"], p["conv_b"])
        proj = _dot(u, p["x_proj"], cd)
        dt, b, c = proj[..., :rank], proj[..., rank:rank + state], proj[..., rank + state:]
        delta = jax.nn.softplus(_dot(dt, p["dt_proj"], cd) + p["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
        m = selective_scan(u, delta, a, b, c) + p["D"].astype(jnp.float32) * u
        return _dot(m * jax.nn.silu(z), p["out_proj"], cd), m


def _split_kv(kv: jax.Array, s: dict) -> tuple[jax.Array, jax.Array]:
    """[n, L, 2 * kv * head] -> keys [n, L, G, 2, head] (k1, k2 of a group)
    and values [n, L, G, 2 * head]."""
    n, length, _ = kv.shape
    groups, head = s["kv"] // 2, s["head"]
    k, v = kv[..., :s["kv"] * head], kv[..., s["kv"] * head:]
    return k.reshape(n, length, groups, 2, head), v.reshape(n, length, groups, 2 * head)


def diff_attention(p, q, k, v, layer: int, q_start: int, window: int | None, s: dict, cd):
    """Differential attention of the queries at positions q_start .. against
    the keys at positions 0 ..: causal, and within `window` positions where
    one is given (position t sees t - window + 1 .. t).

      o = softmax(q1 k1' / sqrt(d) + mask) v - lambda softmax(q2 k2' / sqrt(d) + mask) v
      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(layer)
      o <- RMSNorm(o) * (1 - lambda_init)

    q [n, Lq, H]; k [n, Lk, G, 2, d]; v [n, Lk, G, 2d]; returns [n, Lq, H],
    the heads side by side, before the output projection."""
    n, lq, _ = q.shape
    groups, head = s["kv"] // 2, s["head"]
    per_group = s["heads"] // 2 // groups
    q = q.reshape(n, lq, groups, per_group, 2, head)
    scores = _product("nqgjcd,nkgcd->ngjcqk", q, k, cd) * head ** -0.5
    probs = sequence.causal_softmax(scores, q_start, window)
    return _difference(p, _product("ngjcqk,nkge->nqgjce", probs, v, cd), layer)


def _difference(p, out: jax.Array, layer: int) -> jax.Array:
    """`out [n, Lq, G, J, 2, 2d]`, the two softmaxes' `p v` of every head
    pair: their difference under lambda, normed; `[n, Lq, H]`."""
    f32 = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    base = lambda_init(layer)
    lam = (
        jnp.exp(jnp.sum(f32("lambda_q1") * f32("lambda_k1")))
        - jnp.exp(jnp.sum(f32("lambda_q2") * f32("lambda_k2"))) + base
    )
    o = out[..., 0, :] - lam * out[..., 1, :]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + RMS_EPS)
    o = o * f32("subln") * (1.0 - base)
    return o.reshape(out.shape[0], out.shape[1], -1)


def _attend(p, q, k, v, layer: int, window: int | None, s: dict, cd) -> jax.Array:
    """diff_attention for queries at the LAST q.shape[1] positions of the
    keys' range (all of them, or the last one alone), in `sequence`'s blocks
    of queries; a block reads only the keys its window can reach."""
    offset = k.shape[1] - q.shape[1]
    shapes = sequence.Heads((s["head"],), 2 * s["head"], s["heads"] // s["kv"], cd)
    if sequence.takes_kernel(q.shape[1], k.shape[1], window, OPERAND_PIECES, shapes):
        # The halves of a head pair are two key heads that share one value
        # head of width 2d: heads in the order (group, half, query head).
        n, lq, groups, head = q.shape[0], q.shape[1], s["kv"] // 2, s["head"]
        halves = jnp.swapaxes(q.reshape(n, lq, groups, -1, 2, head), 3, 4)
        out = sequence.attention(
            (halves.reshape(n, lq, -1, head),), (k.reshape(n, -1, 2 * groups, head),), v, window, cd,
            OPERAND_PIECES, head ** -0.5)
        return _difference(p, jnp.swapaxes(out.reshape(halves.shape[:5] + (2 * head,)), 3, 4), layer)
    out = [
        diff_attention(
            p, q[:, start:stop], k[:, first:last], v[:, first:last], layer,
            offset + start - first, window, s, cd)
        for start, stop, first, last in sequence.query_blocks(q.shape[1], k.shape[1], window)
    ]
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def _mlp(layer: dict, h: jax.Array, eps: float, cd) -> jax.Array:
    with jax.named_scope("mlp"):
        a = _layer_norm(layer["ln2"], h, eps)
        return h + _dot(jax.nn.silu(_dot(a, layer["gate"], cd)) * _dot(a, layer["up"], cd), layer["down"], cd)


def forward(config: ModelConfig, params, batch) -> jax.Array:
    """The logit of every row: everything after the full-attention layer's
    keys and values is computed at the last position alone."""
    s, cd, eps = _sizes(config), config.cdtype, config.layer_norm_eps
    plan = layer_plan(config.num_hidden_layers)
    hidden = s["hidden"]
    # The weighted embedding in float32, where a bfloat16 row times a
    # bfloat16 weight is exact: the first norm reads no rounding either.
    x = field_embed(params["embedding"], batch["feat_ids"], batch["feat_wts"], jnp.float32, hidden)
    memory = keys = values = None
    for i, (kind, layer) in enumerate(zip(plan, params["layers"])):
        a = _layer_norm(layer["ln1"], x, eps)
        if kind == "mamba":
            mix, m = _mamba(layer["mamba"], a, s, cd)
            if i == config.num_hidden_layers // 2:
                memory = m
        elif kind == "window":
            with jax.named_scope("attn_window"):
                p = layer["attn"]
                qkv = _dot(a, p["qkv"], cd)
                k, v = _split_kv(qkv[..., hidden:], s)
                mix = _attend(p, qkv[..., :hidden], k, v, i, config.sliding_window, s, cd)
                mix = _dot(mix, p["o"], cd)
        elif kind == "full":
            with jax.named_scope("attn_full"):
                p = layer["attn"]
                keys, values = _split_kv(_dot(a, p["qkv"][:, hidden:], cd), s)
                # From here on only the last position is computed.
                x, a, memory = sequence.last_position(x, a, memory)
                q = _dot(a, p["qkv"][:, :hidden], cd)
                mix = _dot(_attend(p, q, keys, values, i, None, s, cd), p["o"], cd)
        elif kind == "gmu":
            with jax.named_scope("gmu"):
                p = layer["gmu"]
                mix = _dot(memory * jax.nn.silu(_dot(a, p["in_proj"], cd)), p["out_proj"], cd)
        else:
            with jax.named_scope("attn_cross"):
                p = layer["cross"]
                q = _dot(a, p["q"], cd)
                mix = _dot(_attend(p, q, keys, values, i, None, s, cd), p["o"], cd)
        x = _mlp(layer, x + mix, eps, cd)
    with jax.named_scope("score"):
        last = _layer_norm(params["final_ln"], x[:, -1], eps)
        return jnp.sum(last * params["score"].astype(jnp.float32), axis=-1)


@register_model("phi4flash")
def build_phi4flash(config: ModelConfig) -> Model:
    s = _sizes(config)
    plan = layer_plan(config.num_hidden_layers)

    def init(rng, packed: bool = False):
        k_emb, k_score, *k_layers = jax.random.split(rng, 2 + len(plan))
        dtype = config.pdtype
        # embedding_init scales by 1/sqrt(dim); the published 0.02 is wanted.
        table = embedding_init(k_emb, config.vocab_size, s["hidden"], dtype, packed)
        return {
            "embedding": table * jnp.asarray(INIT_STD * s["hidden"] ** 0.5, dtype),
            "layers": [_layer_init(k, kind, s, dtype) for k, kind in zip(k_layers, plan)],
            "final_ln": _norm_init(s["hidden"], dtype),
            "score": _matrix(k_score, (s["hidden"],), dtype),
        }

    def apply(params, batch):
        logits = forward(config, params, batch)
        return {"prediction_node": jax.nn.sigmoid(logits), "logits": logits}

    # The weights cross as float32: a token's weight scales its embedding in
    # the residual stream, and rounded to bfloat16 on the link it alone put
    # 1.3e-3 rms on the logit at the published widths, forty times what the
    # whole step's arithmetic does (PERF.md, PR 32).
    return Model(
        config=config, init=init, apply=apply, wts_in_compute_dtype=False, layer_plan=plan)
