"""K-EXAONE-236B-A23B (`model_type: exaone_moe`) as a pointwise sequence
ranker, the plain reference: float32 `jax.numpy`, every layer at every
position, `[L, L]` masks and a dense softmax, every held expert over every
token under a mask; no blocks, no pieces, no gather, no grouping, nothing
skipped, nothing imported from the program.

A row is L token ids (`feat_ids [n, L]`, folded by `% V`) with a weight a
token: `x_t = w_t * E[id_t]`. Layer i, of the kind `layer_types[i]`:

  q = x W_q [heads x d];  k = x W_k [kv x d];  v = x W_v [kv x d]      no biases
  q <- RMS_q(q), k <- RMS_k(k)      per head, one learned [d] weight each a layer
  sliding layers: rot on all d dims of q and k; full layers: none. rot turns the
      pairs (i, i + d/2) at position t by t * theta ** (-2i / d)
  query head h reads key-value head h // (heads / kv);  scores = q k' / sqrt(d)
  seen(t, u) = u <= t                       (full)
  seen(t, u) = u <= t and t - u < window    (sliding: position t sees t-window+1 .. t)
  attn = concat_h(softmax(scores | seen) v) W_o
  h = x + RMS_post_attn(attn)       the norm on the sub-layer's OUTPUT, none before it
  FFN   the leading dense layers (those with an "mlp"):  (silu(h W_g) * (h W_u)) W_d
        the others:  shared(h) + sum over the chosen e of g_e * expert_e(h), where
        s = sigmoid(h W_r) over all the routed experts, the top-k of s are chosen
        (one group, the selection bias zero), g = the chosen s normalised to sum 1,
        times the scaling factor; the shared expert and every expert of the dense form
  y = h + RMS_post_ffn(FFN(h))

After the last layer: s = RMS(y_{L-1}) . w_score, score = sigmoid(s).

**The share.** `params` is the pytree the program's own `init` makes
(bfloat16 leaves are cast to float32 as each is used). It holds what ONE chip
of the deployment holds of a layer: the experts `first .. first + held - 1`
stacked (`held` the leading size of the experts' arrays); the attention, the
router, the norms and the shared expert whole. The routed sum runs over the
held experts alone; what the others would add is left out, here as in the
program, and the partial result goes on to the next layer. With every expert
held, this is the whole model.

`layer_types` (default: the published plan's first layers), the window, the
head width, the top-k, the scaling, `theta`, `first` and the norms' epsilon
are keyword arguments at the published values (the tree's shapes give the
rest). Call under `jax.default_matmul_precision("highest")`.
"""

import math

import jax
import jax.numpy as jnp

SLIDING, FULL = "sliding_attention", "full_attention"
LAYER_TYPES = (SLIDING, SLIDING, SLIDING, FULL) * 12
WINDOW, HEAD = 128, 128
TOP_K, SCALING, THETA, EPS, FIRST = 8, 2.5, 1000000.0, 1e-5, 0


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(w, x, eps=EPS):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(w)


def gated_mlp(gate, up, down, x):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


def rot(x, theta):
    """x [n, L, heads, d]: every head at position t turned by t's angles."""
    half = x.shape[-1] // 2
    t = jnp.arange(x.shape[1], dtype=jnp.float32)
    angles = t[:, None] * theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, kind, window=WINDOW, head=HEAD, theta=THETA, eps=EPS):
    """One layer's grouped-query attention of x [n, L, H], a key-value group
    at a time (so that [n, heads, L, L] is never whole)."""
    n, length, _ = x.shape
    heads, kv = p["q"].shape[1] // head, p["k"].shape[1] // head
    q = rms_norm(p["q_norm"], (x @ _f32(p["q"])).reshape(n, length, heads, head), eps)
    k = rms_norm(p["k_norm"], (x @ _f32(p["k"])).reshape(n, length, kv, head), eps)
    v = (x @ _f32(p["v"])).reshape(n, length, kv, head)
    if kind == SLIDING:
        q, k = rot(q, theta), rot(k, theta)
    t = jnp.arange(length)
    seen = t[None, :] <= t[:, None]
    if kind == SLIDING:
        seen &= t[:, None] - t[None, :] < window
    per_group, out = heads // kv, []
    for g in range(kv):
        mine = q[:, :, g * per_group:(g + 1) * per_group]  # the query heads that read group g
        scores = jnp.einsum("nqhd,nkd->nhqk", mine, k[:, :, g]) / math.sqrt(head)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("nhqk,nkd->nqhd", probs, v[:, :, g]))
    return jnp.concatenate(out, axis=2).reshape(n, length, heads * head) @ _f32(p["o"])


def router_gates(router, x, top_k=TOP_K, scaling=SCALING):
    """The gate of EVERY routed expert for every token, [..., E]: the
    normalised, scaled score where the expert is among the token's top-k,
    else 0."""
    scores = jax.nn.sigmoid(x @ _f32(router))
    kth = jnp.sort(scores, axis=-1)[..., -top_k]
    kept = jnp.where(scores >= kth[..., None], scores, 0.0)
    return kept / kept.sum(-1, keepdims=True) * scaling


def routed(layer, x, first=FIRST, top_k=TOP_K, scaling=SCALING):
    """shared(x) + the part of the routed sum that the experts held give."""
    gates = router_gates(layer["router"], x, top_k, scaling)
    out = gated_mlp(layer["shared"]["gate"], layer["shared"]["up"], layer["shared"]["down"], x)
    experts = layer["experts"]
    for e in range(experts["gate"].shape[0]):
        y = gated_mlp(experts["gate"][e], experts["up"][e], experts["down"][e], x)
        out = out + gates[..., first + e, None] * y
    return out


def layer_forward(layer, x, kind, first=FIRST, top_k=TOP_K, scaling=SCALING, window=WINDOW, head=HEAD,
                  theta=THETA, eps=EPS):
    h = x + rms_norm(layer["post_attn_norm"], attention(layer["attn"], x, kind, window, head, theta, eps), eps)
    if "mlp" in layer:
        ffn = gated_mlp(layer["mlp"]["gate"], layer["mlp"]["up"], layer["mlp"]["down"], h)
    else:
        ffn = routed(layer, h, first, top_k, scaling)
    return h + rms_norm(layer["post_ffn_norm"], ffn, eps)


def once_there(x, tree):
    """`tree` as it is, but not before `x` is there: for the host's memory
    alone. XLA's CPU backend orders a program for concurrency, and a weight's
    cast to float32 waits for nothing but the weight, so every cast would come
    first and the whole model stand in float32 at once (9.5 GB of this
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
