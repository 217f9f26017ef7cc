"""Qwen3-Next-80B-A3B-Instruct (`model_type: qwen3_next`) as a pointwise
sequence ranker, the plain reference: float32 `jax.numpy`, every layer at
every position, the gated delta rule as its position-by-position recurrence,
`[L, L]` masks and a dense softmax a key-value group, every held expert over
every token times its gate; no chunks, no triangular solve, no blocks, no
pieces, no gather, no grouping, nothing skipped, nothing imported from the
program.

A row is L token ids (`feat_ids [n, L]`, folded by `% V`) with a weight a
token: `x_t = w_t * E[id_t]`. Layer i is a full layer where
`(i + 1) % interval == 0` (the tree says so: it then has an "attn"), else a
linear one. Norms are ZERO-CENTRED, the weight stored less one:

  RMS0_w(x) = x / sqrt(mean(x^2) + eps) * (1 + w)
  a = RMS0_in(x);   h = x + MIX(a);   b = RMS0_post(h);   y = h + MOE(b)

  MIX, linear (Gated DeltaNet), Hk key heads, Hv = r x Hk value heads:
    q = a W_q [Hk x dk], k = a W_k [Hk x dk], v = a W_v [Hv x dv], z = a W_z [Hv x dv]
    beta = sigmoid(a W_b) [Hv];  g = -exp(A_log) * softplus(a W_a + dt_bias) [Hv]
    each of q, k, v <- silu(conv(.)): y_t = sum_j w[:, j] * x_{t - (taps - 1) + j}, a channel alone, no bias
    q <- q / sqrt(sum q^2 + 1e-6) / sqrt(dk),  k <- k / sqrt(sum k^2 + 1e-6)        a key head
    value head h reads key head h // r:
      S_0 = 0 [dk x dv];  S_t = exp(g_t) (I - beta_t k_t k_t') S_{t-1} + beta_t k_t v_t';  o_t = S_t' q_t
    o_h <- o_h / sqrt(mean(o_h^2) + eps) * w_o * silu(z_h)       a PLAIN weight, one [dv] a layer
    MIX = concat_h(o_h) W_out

  MIX, full (gated attention), `heads` query heads over `kv` key-value heads, d wide:
    [q_h | gate_h] = a W_q: a head's d query columns, then its d gate columns
    k = a W_k [kv x d], v = a W_v [kv x d]
    q_h <- RMS0_qn(q_h), k_j <- RMS0_kn(k_j)      over the head's d dims
    rot on the first `rotary` dims of q_h and k_j: the pairs (i, i + rotary/2) at
        position t turned by t * theta ** (-2i / rotary); the other dims as they are
    scores = q k' / sqrt(d), seen(t, u) = u <= t; query head h reads key-value head h // (heads / kv)
    MIX = concat_h(softmax(scores | seen) v * sigmoid(gate_h)) W_o

  MOE:
    p = softmax(b W_r) over ALL the router's experts;  the top_k largest;  gate_e = p_e / sum of those
    expert_e(b) = (silu(b G_e) * (b U_e)) D_e
    MOE = sum over the chosen e HELD HERE of gate_e expert_e(b)  +  sigmoid(b . w_sg) * expert_shared(b)

After the last layer: s = RMS0_final(y_{L-1}) . w_score, score = sigmoid(s).

**The share.** `params` is the pytree the program's own `init` makes
(bfloat16 leaves are cast to float32 as each is used). It holds what ONE chip
of the deployment holds of a layer: the experts `first .. first + held - 1`
stacked (`held` the leading size of the experts' arrays); the mixers, the
router, the norms and the gated shared expert whole. The routed sum runs over
the held experts alone; what the others would add is left out, here as in the
program, and the partial result goes on to the next layer. With every expert
held, this is the whole model.

Left out, as in the program: the multi-token-prediction layer (a scorer reads
one logit), the language-model head, the absent experts.

The full layers' head width, rotary dims and base, the width of a linear
layer's key head, the top-k, `first`, the norms' epsilon and `neg_eigval`
(false: beta in (0, 1)) are keyword arguments at the published values (the
tree's shapes give the rest). Call under
`jax.default_matmul_precision("highest")`.
"""

import math

import jax
import jax.numpy as jnp

HEAD, ROTARY, THETA, EPS = 256, 64, 10000000.0, 1e-6
KEY_DIM, TOP_K, FIRST, NEG_EIGVAL = 128, 10, 0, False


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms0(w, x, eps=EPS):
    """Zero-centred: the stored weight is the scale less one."""
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + _f32(w))


def gated_mlp(p, x):
    return (jax.nn.silu(x @ _f32(p["gate"])) * (x @ _f32(p["up"]))) @ _f32(p["down"])


def conv_silu(x, w):
    """x [n, L, channels], w [channels, taps]: position t reads t - taps + 1 .. t."""
    w, taps, length = _f32(w), w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + length] * w[:, j] for j in range(taps)))


def delta_rule(q, k, v, a, b):
    """q, k [n, L, Hk, dk], v [n, L, Hv, dv], a, b [n, L, Hv]: o [n, L, Hv, dv],
    position by position from S_0 = 0; value head h reads key head h // (Hv / Hk)."""
    n, _, key_heads, dk = q.shape
    heads = v.shape[2]
    of = jnp.arange(heads) // (heads // key_heads)  # a value head's key head

    def step(state, x):
        q_t, k_t, v_t, a_t, b_t = x
        q_t, k_t = q_t[:, of], k_t[:, of]
        read = jnp.einsum("nhd,nhde->nhe", k_t, state)  # S' k
        state = a_t[..., None, None] * (state - b_t[..., None, None] * k_t[..., :, None] * read[..., None, :])
        state = state + b_t[..., None, None] * k_t[..., :, None] * v_t[..., None, :]
        return state, jnp.einsum("nhde,nhd->nhe", state, q_t)

    state = jnp.zeros((n, heads, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, state, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, a, b)))
    return jnp.moveaxis(o, 0, 1)


def linear_mix(p, x, dk=KEY_DIM, eps=EPS, neg_eigval=NEG_EIGVAL):
    n, length, _ = x.shape
    heads, dv, key_heads = p["A_log"].shape[0], p["o_norm"].shape[0], p["q"].shape[1] // dk
    q = conv_silu(x @ _f32(p["q"]), p["conv_q"]).reshape(n, length, key_heads, dk)
    k = conv_silu(x @ _f32(p["k"]), p["conv_k"]).reshape(n, length, key_heads, dk)
    v = conv_silu(x @ _f32(p["v"]), p["conv_v"]).reshape(n, length, heads, dv)
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) / math.sqrt(dk)
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    b = jax.nn.sigmoid(x @ _f32(p["b"])) * (2.0 if neg_eigval else 1.0)
    a = jnp.exp(-jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(x @ _f32(p["a"]) + _f32(p["dt_bias"])))
    o = delta_rule(q, k, v, a, b)
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + eps) * _f32(p["o_norm"])  # a plain weight
    o = o * jax.nn.silu(x @ _f32(p["z"])).reshape(n, length, heads, dv)
    return o.reshape(n, length, heads * dv) @ _f32(p["o"])


def rot(x, rotary, theta):
    """x [n, L, heads, d]: the first `rotary` dims of every head at position t
    turned by t's angles, the others as they are."""
    half = rotary // 2
    t = jnp.arange(x.shape[1], dtype=jnp.float32)
    angles = t[:, None] * theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary:]], -1)


def full_mix(p, x, head=HEAD, rotary=ROTARY, theta=THETA, eps=EPS):
    """A key-value group at a time, so that [n, heads, L, L] is never whole."""
    n, length, _ = x.shape
    heads, kv = p["q"].shape[1] // (2 * head), p["k"].shape[1] // head
    both = (x @ _f32(p["q"])).reshape(n, length, heads, 2 * head)
    q, gate = both[..., :head], both[..., head:]
    q = rot(rms0(p["q_norm"], q, eps), rotary, theta)
    k = rot(rms0(p["k_norm"], (x @ _f32(p["k"])).reshape(n, length, kv, head), eps), rotary, theta)
    v = (x @ _f32(p["v"])).reshape(n, length, kv, head)
    t = jnp.arange(length)
    seen = t[None, :] <= t[:, None]
    per_group, out = heads // kv, []
    for g in range(kv):
        mine = q[:, :, g * per_group:(g + 1) * per_group]  # the query heads that read group g
        scores = jnp.einsum("nqhd,nkd->nhqk", mine, k[:, :, g]) / math.sqrt(head)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("nhqk,nkd->nqhd", probs, v[:, :, g]))
    o = jnp.concatenate(out, axis=2) * jax.nn.sigmoid(gate)
    return o.reshape(n, length, heads * head) @ _f32(p["o"])


def router_gates(router, x, top_k=TOP_K):
    """The gate of EVERY routed expert for every token, [..., E]: the
    softmax's probability over the sum of the token's top-k where the expert
    is among them, else 0."""
    probs = jax.nn.softmax(x @ _f32(router), axis=-1)
    kth = jnp.sort(probs, axis=-1)[..., -top_k]
    kept = jnp.where(probs >= kth[..., None], probs, 0.0)
    return kept / kept.sum(-1, keepdims=True)


def moe(layer, x, first=FIRST, top_k=TOP_K):
    """sigmoid(x . w_sg) * shared(x) + the part of the routed sum that the
    experts held give: every held expert over every token, times its gate
    (zero where the token did not choose it)."""
    held = layer["experts"]["gate"].shape[0]
    gates = router_gates(layer["router"], x, top_k)[..., first:first + held]
    out = jax.nn.sigmoid(x @ _f32(layer["shared_gate"]))[..., None] * gated_mlp(layer["shared"], x)

    def add(out, expert_and_gate):
        expert, gate = expert_and_gate
        return out + gate[..., None] * gated_mlp(expert, x), None

    return jax.lax.scan(add, out, (layer["experts"], jnp.moveaxis(gates, -1, 0)))[0]


def layer_forward(layer, x, first=FIRST, top_k=TOP_K, head=HEAD, rotary=ROTARY, theta=THETA, key_dim=KEY_DIM,
                  eps=EPS, neg_eigval=NEG_EIGVAL):
    a = rms0(layer["input_norm"], x, eps)
    if "attn" in layer:
        mix = full_mix(layer["attn"], a, head, rotary, theta, eps)
    else:
        mix = linear_mix(layer["linear"], a, key_dim, eps, neg_eigval)
    h = x + mix
    return h + moe(layer, rms0(layer["post_norm"], h, eps), first, top_k)


def once_there(x, tree):
    """`tree` as it is, but not before `x` is there: for the host's memory
    alone. XLA's CPU backend orders a program for concurrency, and a weight's
    cast to float32 waits for nothing but the weight, so every cast would come
    first and the whole model stand in float32 at once (8.8 GB of this
    configuration's). A cast that waits for the layer before it is made when
    it is needed, and the next layer's takes its room. w + 0 is w in every
    format, so no number changes."""
    zero = x.ravel()[0] * 0
    return jax.tree.map(lambda w: w + zero.astype(w.dtype), tree)


def logits(params, batch, **sizes):
    table = _f32(params["embedding"])
    rows = jnp.remainder(batch["feat_ids"], table.shape[0])
    x = table[rows] * _f32(batch["feat_wts"])[..., None]
    for layer in params["layers"]:
        x = layer_forward(once_there(x, layer), x, **sizes)
    return rms0(params["final_norm"], x[:, -1], sizes.get("eps", EPS)) @ _f32(params["score"])


def forward(params, batch, **sizes):
    return jax.nn.sigmoid(logits(params, batch, **sizes))
