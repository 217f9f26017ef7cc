"""Olmo-Hybrid-7B (`model_type: olmo_hybrid`) as a pointwise sequence ranker,
the plain reference: float32 `jax.numpy`, every layer at every position, the
gated delta rule as its position-by-position recurrence, `[L, L]` masks and a
dense softmax a head; no chunks, no triangular solve, no blocks, no pieces,
nothing skipped, nothing imported from the program.

A row is L token ids (`feat_ids [n, L]`, folded by `% V`) with a weight a
token: `x_t = w_t * E[id_t]`. Layer i, of the kind `layer_types[i]`:

  h = x + RMS_post_attn(mix(x))      the norm on the sub-layer's OUTPUT, none before it
  y = h + RMS_post_ffn((silu(h W_g) * (h W_u)) W_d)

  full_attention:
    q = x W_q, k = x W_k, v = x W_v  [heads x d], no biases
    q <- RMS_q(q), k <- RMS_k(k)     over the WHOLE projection width, one learned weight a column
    no rotary;  scores = q k' / sqrt(d);  seen(t, u) = u <= t
    query head h reads key-value head h // (heads / kv)
    mix = concat_h(softmax(scores | seen) v) W_o

  linear_attention (the gated delta rule), H heads, keys dk wide, values dv:
    q = x W_q [H x dk], k = x W_k [H x dk], v = x W_v [H x dv]
    each <- silu(conv(.)): y_t = sum_j w[:, j] * x_{t - (taps - 1) + j}, a channel alone, no bias
    q <- q / sqrt(sum q^2 + 1e-6) / sqrt(dk),  k <- k / sqrt(sum k^2 + 1e-6)       per head
    b_t = 2 sigmoid(x W_b) [H]       (1 sigmoid(.) where neg_eigval is false)
    g_t = -exp(A_log) * softplus(x W_a + dt_bias) [H],  a_t = exp(g_t)
    S_0 = 0 [dk x dv] a head;  S_t = a_t (I - b_t k_t k_t') S_{t-1} + b_t k_t v_t';  o_t = S_t' q_t
    o <- RMS_o(o) (one learned [dv] weight) * silu(x W_gate);  mix = concat_h(o) W_o

After the last layer: s = RMS(y_{L-1}) . w_score, score = sigmoid(s).

`params` is the pytree the program's own `init` makes (bfloat16 leaves are
cast to float32 as each is used); its shapes give every size but the width of
a full layer's head. `layer_types` (default: the published plan's first
layers), that width, the norms' epsilon and `neg_eigval` are keyword arguments
at the published values. Call under `jax.default_matmul_precision("highest")`.
"""

import math

import jax
import jax.numpy as jnp

LINEAR, FULL = "linear_attention", "full_attention"
LAYER_TYPES = (LINEAR, LINEAR, LINEAR, FULL) * 8
HEAD, EPS, NEG_EIGVAL = 128, 1e-6, True


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(w, x, eps=EPS):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(w)


def gated_mlp(p, x):
    return (jax.nn.silu(x @ _f32(p["gate"])) * (x @ _f32(p["up"]))) @ _f32(p["down"])


def conv_silu(x, w):
    """x [n, L, channels], w [channels, taps]: position t reads t - taps + 1 .. t."""
    w, taps, length = _f32(w), w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + length] * w[:, j] for j in range(taps)))


def delta_rule(q, k, v, a, b):
    """q, k [n, L, H, dk], v [n, L, H, dv], a, b [n, L, H]: o [n, L, H, dv],
    position by position from S_0 = 0."""
    def step(state, x):
        q_t, k_t, v_t, a_t, b_t = x
        read = jnp.einsum("nhd,nhde->nhe", k_t, state)  # S' k
        state = a_t[..., None, None] * (state - b_t[..., None, None] * k_t[..., :, None] * read[..., None, :])
        state = state + b_t[..., None, None] * k_t[..., :, None] * v_t[..., None, :]
        return state, jnp.einsum("nhde,nhd->nhe", state, q_t)

    n, _, heads, dk = q.shape
    state = jnp.zeros((n, heads, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, state, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, a, b)))
    return jnp.moveaxis(o, 0, 1)


def linear_attention(p, x, eps=EPS, neg_eigval=NEG_EIGVAL):
    n, length, _ = x.shape
    heads = p["A_log"].shape[0]
    dk, dv = p["q"].shape[1] // heads, p["v"].shape[1] // heads
    q = conv_silu(x @ _f32(p["q"]), p["conv_q"]).reshape(n, length, heads, dk)
    k = conv_silu(x @ _f32(p["k"]), p["conv_k"]).reshape(n, length, heads, dk)
    v = conv_silu(x @ _f32(p["v"]), p["conv_v"]).reshape(n, length, heads, dv)
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) / math.sqrt(dk)
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    b = jax.nn.sigmoid(x @ _f32(p["b"])) * (2.0 if neg_eigval else 1.0)
    a = jnp.exp(-jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(x @ _f32(p["a"]) + _f32(p["dt_bias"])))
    o = rms_norm(p["o_norm"], delta_rule(q, k, v, a, b), eps)
    o = o * jax.nn.silu(x @ _f32(p["gate"])).reshape(n, length, heads, dv)
    return o.reshape(n, length, heads * dv) @ _f32(p["o"])


def full_attention(p, x, head=HEAD, eps=EPS):
    """A head at a time, so that [n, heads, L, L] is never whole."""
    n, length, _ = x.shape
    heads, kv = p["q"].shape[1] // head, p["k"].shape[1] // head
    q = rms_norm(p["q_norm"], x @ _f32(p["q"]), eps).reshape(n, length, heads, head)
    k = rms_norm(p["k_norm"], x @ _f32(p["k"]), eps).reshape(n, length, kv, head)
    v = (x @ _f32(p["v"])).reshape(n, length, kv, head)
    t = jnp.arange(length)
    seen = t[None, :] <= t[:, None]
    out = []
    for h in range(heads):
        g = h // (heads // kv)
        scores = jnp.einsum("nqd,nkd->nqk", q[:, :, h], k[:, :, g]) / math.sqrt(head)
        out.append(jnp.einsum("nqk,nkd->nqd", jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), v[:, :, g]))
    return jnp.concatenate(out, axis=-1) @ _f32(p["o"])


def layer_forward(layer, x, kind, head=HEAD, eps=EPS, neg_eigval=NEG_EIGVAL):
    if kind == LINEAR:
        mix = linear_attention(layer["linear"], x, eps, neg_eigval)
    else:
        mix = full_attention(layer["attn"], x, head, eps)
    h = x + rms_norm(layer["post_attn_norm"], mix, eps)
    return h + rms_norm(layer["post_ffn_norm"], gated_mlp(layer["mlp"], h), eps)


def once_there(x, tree):
    """`tree` as it is, but not before `x` is there: for the host's memory
    alone. XLA's CPU backend orders a program for concurrency, and a weight's
    cast to float32 waits for nothing but the weight, so every cast would come
    first and the whole model stand in float32 at once (8.2 GB of this
    configuration's). A cast that waits for the layer before it is made when
    it is needed, and the next layer's takes its room. w + 0 is w in every
    format, so no number changes."""
    zero = x.ravel()[0] * 0
    return jax.tree.map(lambda w: w + zero.astype(w.dtype), tree)


def logits(params, batch, layer_types=LAYER_TYPES, **sizes):
    table = _f32(params["embedding"])
    rows = jnp.remainder(batch["feat_ids"], table.shape[0])
    x = table[rows] * _f32(batch["feat_wts"])[..., None]
    for kind, layer in zip(layer_types, params["layers"]):
        x = layer_forward(once_there(x, layer), x, kind, **sizes)
    return rms_norm(params["final_norm"], x[:, -1], sizes.get("eps", EPS)) @ _f32(params["score"])


def forward(params, batch, **sizes):
    return jax.nn.sigmoid(logits(params, batch, **sizes))
