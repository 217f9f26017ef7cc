"""openPangu-Ultra-MoE-718B (`model_type: pangu_ultra_moe`) as a pointwise
sequence ranker, the plain reference: float32 `jax.numpy`, every layer at
every position, a dense masked softmax, every held expert over every token
under a mask; no gather, no grouping, no blocking, no cache, nothing skipped,
nothing imported from the program.

A row is L token ids (`feat_ids [n, L]`, folded by `% V`) with a weight a
token: `x_t = w_t * E[id_t]`. A layer has a norm after each sub-layer as well
as before it (`sandwich_norm`):

  h = x + RMS(MLA(RMS(x)));   y = h + RMS(FFN(RMS(h)))        four learned weights

  MLA   c_q = RMS(x W_qa);  [q_nope, q_rope] = c_q W_qb  (a head: nope + rope wide)
        [c_kv, k_r] = x W_kva;  [k_nope, v] = RMS(c_kv) W_kvb  (a head: nope + v wide)
        q = [q_nope, rot(q_rope)],  k = [k_nope, rot(k_r)]: ONE k_r for all heads
        o = softmax(q k' / sqrt(nope + rope) + causal mask) v;   MLA = concat(o) W_o
        rot turns the pairs (i, i + rope/2) at position t by t * theta ** (-2i / rope)
  FFN   the leading dense layers (those with an "mlp"):  (silu(x W_g) * (x W_u)) W_d
        the others:  shared(x) + sum over the chosen e of g_e * expert_e(x), where
        s = sigmoid(x W_r) over all the routed experts, the top-k of s are chosen
        (no groups, no selection bias), g = the chosen s normalised to sum 1, times
        the scaling factor; the shared expert and every expert of the dense form

After the last layer: s = RMS(y_{L-1}) . w_score, score = sigmoid(s).

**The share.** `params` is the pytree the program's own `init` makes
(bfloat16 leaves are cast to float32 as each is used). It holds what ONE chip
of the deployment holds of a layer: the experts `first .. first + held - 1`
stacked (`held` the leading size of the experts' arrays) and the heads whose
slices of W_qb, W_kvb and W_o are there; the router, W_qa, W_kva, the norms
and the shared expert whole. The routed sum runs over the held experts alone,
the attention over the held heads alone; what the others would add is left
out, here as in the program, and the partial result goes on to the next
layer. With every expert and every head held, this is the whole model.

The head widths, the top-k, the scaling, `theta`, `first` and the norms'
epsilon are keyword arguments at the published values (the tree's shapes give
the rest). Call under `jax.default_matmul_precision("highest")`.
"""

import math

import jax
import jax.numpy as jnp

NOPE, ROPE, V_HEAD = 128, 64, 128
TOP_K, SCALING, THETA, EPS, FIRST = 8, 2.5, 25600000.0, 1e-5, 0


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(w, x, eps=EPS):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(w)


def gated_mlp(gate, up, down, x):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


def rot(x, positions, theta):
    """x [..., L, d] or [..., L, heads, d] with L at axis 1."""
    half = x.shape[-1] // 2
    angles = positions[:, None] * theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla(p, a, nope=NOPE, rope=ROPE, v_head=V_HEAD, theta=THETA, eps=EPS):
    """Latent attention of the normed input a [n, L, H] over the heads `p` holds."""
    n, length, _ = a.shape
    heads = p["o"].shape[0] // v_head
    rank = p["kv_a"].shape[1] - rope
    q = rms_norm(p["q_a_norm"], a @ _f32(p["q_a"]), eps) @ _f32(p["q_b"])
    q = q.reshape(n, length, heads, nope + rope)
    latent = a @ _f32(p["kv_a"])
    kv = rms_norm(p["kv_a_norm"], latent[..., :rank], eps) @ _f32(p["kv_b"])
    kv = kv.reshape(n, length, heads, nope + v_head)
    t = jnp.arange(length, dtype=jnp.float32)
    k_rope = rot(latent[..., rank:], t, theta)  # [n, L, rope]: one for every head
    q = jnp.concatenate([q[..., :nope], rot(q[..., nope:], t, theta)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope[:, :, None, :], (n, length, heads, rope))], -1)
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k) / math.sqrt(nope + rope)
    seen = t[None, :] <= t[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("nhqk,nkhd->nqhd", probs, kv[..., nope:])
    return out.reshape(n, length, heads * v_head) @ _f32(p["o"])


def router_gates(router, x, top_k=TOP_K, scaling=SCALING):
    """The gate of EVERY routed expert for every token, [..., E]: the
    normalised, scaled score where the expert is among the token's top-k,
    else 0."""
    scores = jax.nn.sigmoid(x @ _f32(router))
    kth = jnp.sort(scores, axis=-1)[..., -top_k]
    chosen = scores >= kth[..., None]
    kept = jnp.where(chosen, scores, 0.0)
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


def layer_forward(layer, x, first=FIRST, top_k=TOP_K, scaling=SCALING, nope=NOPE, rope=ROPE,
                  v_head=V_HEAD, theta=THETA, eps=EPS):
    a = rms_norm(layer["in_norm"], x, eps)
    h = x + rms_norm(layer["post_attn_norm"], mla(layer["attn"], a, nope, rope, v_head, theta, eps), eps)
    a = rms_norm(layer["pre_mlp_norm"], h, eps)
    if "mlp" in layer:
        ffn = gated_mlp(layer["mlp"]["gate"], layer["mlp"]["up"], layer["mlp"]["down"], a)
    else:
        ffn = routed(layer, a, first, top_k, scaling)
    return h + rms_norm(layer["post_mlp_norm"], ffn, eps)


def once_there(x, tree):
    """`tree` as it is, but not before `x` is there: for the host's memory
    alone. XLA's CPU backend orders a program for concurrency, and a weight's
    cast to float32 waits for nothing but the weight, so every cast would come
    first and the whole model stand in float32 at once (9.6 GiB of this
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
    return rms_norm(params["final_norm"], x[:, -1], sizes.get("eps", EPS)) @ _f32(params["score"])


def forward(params, batch, **sizes):
    return jax.nn.sigmoid(logits(params, batch, **sizes))
