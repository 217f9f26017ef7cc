"""Phi-4-mini-flash-reasoning (SambaY) as a pointwise sequence ranker, the
plain reference: float32 `jax.numpy`, every layer at every position, a
position-by-position recurrence for the selective scan, a dense masked
softmax for every attention; no kernel, no chunking, no blocking, nothing
skipped, nothing imported from the program.

A row is L token ids (`feat_ids [n, L]`, folded by `% V`) with a weight a
token: `x_t = w_t * E[id_t]`. No positional encoding. With N layers, layer i
(from 0) is pre-norm with two residuals,

  h = x + mix(LN1(x));   y = h + (silu(G) * U) W_down,   G = LN2(h) W_gate,  U = LN2(h) W_up

and `mix` is, by the published constructor's rule:

  mamba   (i even, i <= N/2)   [u, z] = a W_in;  u = silu(conv1d_causal(u) + b_conv)
                               [dt, B, C] = u W_x;  D_t = softplus(dt W_dt + b_dt)
                               S_t = exp(D_t (x) A) . S_{t-1} + (D_t . u_t) (x) B_t,  A = -exp(A_log), S_0 = 0
                               m_t = S_t C_t + D . u_t;   mix = (m . silu(z)) W_out
                               layer N/2 hands m on as the MEMORY of the gated memory units
  window  (i odd, i < N/2)     differential attention, causal, position t sees t-W+1 .. t
  full    (i = N/2 + 1)        differential attention, causal; its K and V are kept
  gmu     (i even, i >= N/2+2) mix = (memory . silu(a W_in)) W_out
  cross   (i odd, i >= N/2+3)  differential attention of its own queries against the kept K and V, causal

Differential attention (query heads 2h, 2h+1 are q1, q2 of differential head
h; key heads 2g, 2g+1 are k1, k2 of group g; value heads 2g, 2g+1 side by side
are its value of width 2d; differential head h reads group h // r):

  o = softmax(q1 k1' / sqrt(d) + mask) v - lambda softmax(q2 k2' / sqrt(d) + mask) v
  lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,  lambda_init = 0.8 - 0.6 exp(-0.3 i)
  o <- RMSNorm_2d(o) * w_subln * (1 - lambda_init);   mix = concat(o) W_o

After the last layer: s = LN(y_{L-1}) . w_score, score = sigmoid(s).

`params` is the pytree the program's own `init` makes (bfloat16 leaves are
cast to float32 as each is used; the published fused `gate_up_proj` is its two
halves). The head size is the length of a lambda vector, the head counts
follow from it and the projections' widths; the window and the norms' epsilon
are keyword arguments, at the published values. Call under
`jax.default_matmul_precision("highest")`.
"""

import math

import jax
import jax.numpy as jnp

WINDOW, LN_EPS, RMS_EPS = 512, 1e-5, 1e-5


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _layer_norm(p, x, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * _f32(p["w"]) + _f32(p["b"])


def _mamba(p, a):
    conv_w, a_neg = _f32(p["conv_w"]), -jnp.exp(_f32(p["A_log"]))  # [Di, K], [Di, N]
    inner, taps = conv_w.shape
    state = a_neg.shape[1]
    rank = p["x_proj"].shape[1] - 2 * state
    uz = a @ _f32(p["in_proj"])
    u, z = uz[..., :inner], uz[..., inner:]
    length = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    u = sum(padded[:, k:k + length] * conv_w[:, k] for k in range(taps)) + _f32(p["conv_b"])
    u = jax.nn.silu(u)
    proj = u @ _f32(p["x_proj"])
    dt, b, c = proj[..., :rank], proj[..., rank:rank + state], proj[..., rank + state:]
    delta = jax.nn.softplus(dt @ _f32(p["dt_proj"]) + _f32(p["dt_bias"]))

    def step(s, xs):  # s [n, Di, N]
        d_t, u_t, b_t, c_t = xs
        s = jnp.exp(d_t[..., None] * a_neg) * s + (d_t * u_t)[..., None] * b_t[:, None, :]
        return s, (s * c_t[:, None, :]).sum(-1)

    time_major = lambda x: jnp.swapaxes(x, 0, 1)  # noqa: E731
    s0 = jnp.zeros((u.shape[0], inner, state), u.dtype)
    _, y = jax.lax.scan(step, s0, tuple(map(time_major, (delta, u, b, c))))
    m = time_major(y) + _f32(p["D"]) * u
    return (m * jax.nn.silu(z)) @ _f32(p["out_proj"]), m


def _diff_attention(p, q, k, v, layer, window):
    """q [n, L, heads * d]; k, v [n, L, kv_heads * d]; dense [L, L] scores."""
    n, length, _ = q.shape
    d = p["lambda_q1"].shape[0]
    heads, kv_heads = q.shape[-1] // d, k.shape[-1] // d
    groups, per_group = kv_heads // 2, heads // kv_heads
    q = q.reshape(n, length, groups, per_group, 2, d)
    k = k.reshape(n, length, groups, 2, d)
    v = v.reshape(n, length, groups, 2 * d)
    t = jnp.arange(length)
    seen = t[None, :] <= t[:, None]
    if window is not None:
        seen = seen & (t[:, None] - t[None, :] < window)
    scores = jnp.einsum("nqgjcd,nkgcd->ngjcqk", q, k) / math.sqrt(d)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("ngjcqk,nkge->nqgjce", probs, v)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = (
        jnp.exp(jnp.dot(_f32(p["lambda_q1"]), _f32(p["lambda_k1"])))
        - jnp.exp(jnp.dot(_f32(p["lambda_q2"]), _f32(p["lambda_k2"]))) + lam_init
    )
    o = out[..., 0, :] - lam * out[..., 1, :]
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + RMS_EPS) * _f32(p["subln"]) * (1.0 - lam_init)
    return o.reshape(n, length, -1)


def logits(params, batch, window=WINDOW, eps=LN_EPS):
    table = _f32(params["embedding"])
    rows = jnp.remainder(batch["feat_ids"], table.shape[0])
    x = table[rows] * _f32(batch["feat_wts"])[..., None]
    layers = params["layers"]
    half = len(layers) // 2
    memory = kept = None
    for i, layer in enumerate(layers):
        a = _layer_norm(layer["ln1"], x, eps)
        if i <= half and i % 2 == 0:
            mix, m = _mamba(layer["mamba"], a)
            if i == half:
                memory = m
        elif i <= half + 1:
            p = layer["attn"]
            qkv = a @ _f32(p["qkv"])
            hidden = a.shape[-1]
            kv_width = (qkv.shape[-1] - hidden) // 2
            q, k, v = qkv[..., :hidden], qkv[..., hidden:hidden + kv_width], qkv[..., hidden + kv_width:]
            if i == half + 1:
                kept = (k, v)
            mix = _diff_attention(p, q, k, v, i, window if i < half else None)
            mix = mix @ _f32(p["o"])
        elif i % 2 == 0:
            p = layer["gmu"]
            mix = (memory * jax.nn.silu(a @ _f32(p["in_proj"]))) @ _f32(p["out_proj"])
        else:
            p = layer["cross"]
            mix = _diff_attention(p, a @ _f32(p["q"]), *kept, i, None) @ _f32(p["o"])
        h = x + mix
        a = _layer_norm(layer["ln2"], h, eps)
        x = h + (jax.nn.silu(a @ _f32(layer["gate"])) * (a @ _f32(layer["up"]))) @ _f32(layer["down"])
    return _layer_norm(params["final_ln"], x[:, -1], eps) @ _f32(params["score"])


def forward(params, batch, window=WINDOW, eps=LN_EPS):
    return jax.nn.sigmoid(logits(params, batch, window, eps))
