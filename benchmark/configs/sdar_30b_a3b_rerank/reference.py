"""SDAR-30B-A3B-Chat (`model_type: sdar_moe`; block diffusion over a Qwen3-MoE
stack) as a pointwise sequence ranker, the plain reference: float32
`jax.numpy`, ONE denoising pass, every layer at every position, the block mask
as one `jnp.where` over `[L, L]` and a dense softmax a key-value group, every
held expert over every token times its gate; no blocks, no tiles, no pieces,
no gather, no grouping, nothing skipped, nothing imported from the program.

A row is L token ids (`feat_ids [n, L]`, folded by `% V`) with a weight a
token: `x_t = w_t * E[id_t]`. Layer i, all alike:

  RMS_w(x) = w * x / sqrt(mean(x^2) + eps)                         a plain weight
  a = RMS_in(x);  q = a W_q [heads x d];  k = a W_k [kv x d];  v = a W_v [kv x d]
  q_h <- RMS_qn(q_h), k_j <- RMS_kn(k_j)       over the head's d dims, one [d] weight each a layer
  rot on all d dims of q_h and k_j: the pairs (i, i + d/2) at position t turned by t * theta ** (-2i / d)
                                               (the angles in float64, their cos and sin float32)
  scores = q k' / sqrt(d); query head h reads key-value head h // (heads / kv)
  seen(t, u) = u // B <= t // B                                    the block mask: a position sees its
                                                                   whole block of B and every block before it
  h = x + concat_h(softmax(scores | seen) v) W_o
  b = RMS_post(h);  p = softmax(b W_r) over ALL the router's experts
  the top_k largest;  gate_e = p_e / the sum of those (norm_topk)  no scaling, no bias, no shared expert
  expert_e(b) = (silu(b G_e) * (b U_e)) D_e
  y = h + sum over the chosen e HELD HERE of gate_e expert_e(b)

After the last layer: s = RMS_final(y_{L-1}) . w_score, score = sigmoid(s).

**The share.** `params` is the pytree the program's own `init` makes
(bfloat16 leaves are cast to float32 as each is used). It holds the experts
`first .. first + held - 1` stacked (`held` the leading size of the experts'
arrays); the attention, the router and the norms whole. The routed sum runs
over the held experts alone; with every expert held (the benchmark's
configuration: 128 of 128) this is the whole layer.

Left out, as in the program: the sampler's further passes (each commits
tokens and re-enters the stack), the language-model head, the noise schedule
(training's).

The head's width, the rotary base, the block length, the top-k, `first`,
whether the chosen gates are normalised and the norms' epsilon are keyword
arguments at the published values (the block length the family's default; the
tree's shapes give the rest). Call under
`jax.default_matmul_precision("highest")`.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

HEAD, THETA, EPS, BLOCK = 128, 1000000.0, 1e-6, 4
TOP_K, FIRST, NORM_TOPK = 8, 0, True


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms(w, x, eps=EPS):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(w)


def gated_mlp(p, x):
    return (jax.nn.silu(x @ _f32(p["gate"])) * (x @ _f32(p["up"]))) @ _f32(p["down"])


def rot(x, theta):
    """x [n, L, heads, d]: every head at position t turned by t's angles, the
    pairs (i, i + d/2)."""
    half = x.shape[-1] // 2
    # The angles in float64, the table then float32: a position times a
    # frequency loses 1e-4 rad in float32 by position 2,047, a thousand times
    # float32's rounding, which moved an attention output by 1e-5 of its size
    # and flipped a router's near ties (PERF.md section 6, PR 64).
    angles = np.arange(x.shape[1], dtype=np.float64)[:, None] * theta ** (-np.arange(half, dtype=np.float64) / half)
    cos, sin = (jnp.asarray(f(angles), jnp.float32)[None, :, None, :] for f in (np.cos, np.sin))
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block_mask(length, block=BLOCK):
    """seen [L, L]: position t (a row) sees position u (a column) where u's
    block is t's or an earlier one."""
    t = jnp.arange(length)
    return t[None, :] // block <= t[:, None] // block


def mix(p, x, head=HEAD, theta=THETA, eps=EPS, block=BLOCK):
    """A key-value group at a time, so that [n, heads, L, L] is never whole."""
    n, length, _ = x.shape
    heads, kv = p["q"].shape[1] // head, p["k"].shape[1] // head
    q = rot(rms(p["q_norm"], (x @ _f32(p["q"])).reshape(n, length, heads, head), eps), theta)
    k = rot(rms(p["k_norm"], (x @ _f32(p["k"])).reshape(n, length, kv, head), eps), theta)
    v = (x @ _f32(p["v"])).reshape(n, length, kv, head)
    seen = block_mask(length, block)
    per_group, out = heads // kv, []
    for g in range(kv):
        mine = q[:, :, g * per_group:(g + 1) * per_group]  # the query heads that read group g
        scores = jnp.einsum("nqhd,nkd->nhqk", mine, k[:, :, g]) / math.sqrt(head)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("nhqk,nkd->nqhd", probs, v[:, :, g]))
    return jnp.concatenate(out, axis=2).reshape(n, length, heads * head) @ _f32(p["o"])


def router_gates(router, x, top_k=TOP_K, norm_topk=NORM_TOPK):
    """The gate of EVERY routed expert for every token, [..., E]: the
    softmax's probability (over the sum of the token's top-k where
    `norm_topk`) where the expert is among them, else 0."""
    probs = jax.nn.softmax(x @ _f32(router), axis=-1)
    kth = jnp.sort(probs, axis=-1)[..., -top_k]
    kept = jnp.where(probs >= kth[..., None], probs, 0.0)
    return kept / kept.sum(-1, keepdims=True) if norm_topk else kept


def moe(layer, x, first=FIRST, top_k=TOP_K, norm_topk=NORM_TOPK):
    """The part of the routed sum that the experts held give: every held
    expert over every token, times its gate (zero where the token did not
    choose it)."""
    held = layer["experts"]["gate"].shape[0]
    gates = router_gates(layer["router"], x, top_k, norm_topk)[..., first:first + held]

    def add(out, expert_and_gate):
        expert, gate = expert_and_gate
        return out + gate[..., None] * gated_mlp(expert, x), None

    return jax.lax.scan(add, jnp.zeros_like(x), (layer["experts"], jnp.moveaxis(gates, -1, 0)))[0]


def layer_forward(layer, x, head=HEAD, theta=THETA, eps=EPS, block=BLOCK, first=FIRST, top_k=TOP_K,
                  norm_topk=NORM_TOPK):
    h = x + mix(layer["attn"], rms(layer["input_norm"], x, eps), head, theta, eps, block)
    return h + moe(layer, rms(layer["post_norm"], h, eps), first, top_k, norm_topk)


def once_there(x, tree):
    """`tree` as it is, but not before `x` is there: for the host's memory
    alone. XLA's CPU backend orders a program for concurrency, and a weight's
    cast to float32 waits for nothing but the weight, so every cast would come
    first and the whole model stand in float32 at once (12.5 GB of this
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
    return rms(params["final_norm"], x[:, -1], sizes.get("eps", EPS)) @ _f32(params["score"])


def forward(params, batch, **sizes):
    return jax.nn.sigmoid(logits(params, batch, **sizes))
