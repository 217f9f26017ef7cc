"""MiMo-V2.5 (`model_type: mimo_v2`) as a pointwise sequence ranker, the plain
reference: float32 `jax.numpy`, every layer at every position, `[L, L]` masks
and a dense softmax (the sink a column beside the keys'), every held expert
over every token under a mask; no blocks, no pieces, no kernel, no gather, no
grouping, nothing skipped, nothing imported from the program.

A row is L token ids (`feat_ids [n, L]`, folded by `% V`) with a weight a
token: `x_t = w_t * E[id_t]`. Layer i, of the kind `hybrid_layer_pattern[i]`
(0 full, 1 window):

  a = RMS_in(x)                              the norm BEFORE the sub-layer, none on a head
  q = a W_q [heads x d];  k = a W_k [KV x d];  v = value_scale * (a W_v) [KV x d_v]     no biases
      KV is the layer's own: W_k's columns over d (4 full, 8 window as published)
  rot turns the FIRST r dims of every q and k head, the pairs (i, i + r/2) at
      position t by t * theta ** (-2i / r); theta differs by kind; dims r.. unturned
  query head h reads key-value head h // (heads / KV);  s = q k' / sqrt(d)
  seen(t, u) = u <= t                        (full)
  seen(t, u) = u <= t and t - u < window     (window: position t sees t-window+1 .. t)
  a layer WITH a sink (one whose tree holds `sink [heads]`): the softmax runs
      over the seen keys AND one more logit b_h a head that no key carries:
      p_tu = exp(s_tu - m) / (sum_u exp(s_tu - m) + exp(b_h - m)); the sink's
      own probability is dropped, so a row of p sums to less than 1
  attn = concat_h(p v) W_o;   h = x + attn
  g = RMS_post(h)
  FFN   a layer with an "mlp":  (silu(g W_g) * (g W_u)) W_d
        the others:  sum over the chosen e of g_e * expert_e(g), NO shared expert:
        s = sigmoid(g W_r) over all the routed experts, the top-k of s are chosen
        (one group, the selection bias zero), g_e = the chosen s normalised to sum 1,
        times the scaling factor; every expert of the dense form
  y = h + FFN(g)

After the last layer: s = RMS(y_{L-1}) . w_score, score = sigmoid(s).

Departures from the published description (the configuration's file has each
under `assumed`): the rotate-half pairing; `routed_scaling_factor: null` read
as 1.0; `attention_value_scale` on both kinds of layer; the one-logit head in
place of the language-model head; no multi-token-prediction layers, no vision
or audio tower.

**The share.** `params` is the pytree the program's own `init` makes
(bfloat16 leaves are cast to float32 as each is used). It holds what ONE chip
of the deployment holds of a layer: the experts `first .. first + held - 1`
stacked (`held` the leading size of the experts' arrays); the attention, the
router and the norms whole. The routed sum runs over the held experts alone;
what the others would add is left out, here as in the program, and the
partial result goes on to the next layer. With every expert held, this is the
whole model.

**The sink's mass.** `sink_mass_pct` is what the served step's counters
`attn.sink_mass_ppm / attn.sink_rows / 1e4` read, computed here from the dense
softmax: the sink's probability averaged over heads and positions of a (row,
sink layer) pair, then over the pairs.

The pattern (default: the published one's first layers), the window, the head
widths, the rotary dims, both bases, the value scale, the top-k, the scaling,
`first` and the norms' epsilon are keyword arguments at the published values
(the tree's shapes give the rest). Call under
`jax.default_matmul_precision("highest")`.
"""

import math

import jax
import jax.numpy as jnp

PATTERN = (0, 1, 1, 1, 1, 0) + (1, 1, 1, 1, 1, 0) * 7  # hybrid_layer_pattern: 0 full, 1 window
WINDOW, HEAD, V_HEAD, ROTARY = 128, 192, 128, 64  # ROTARY = int(192 * 0.334)
THETA_FULL, THETA_WINDOW, VALUE_SCALE = 10000000.0, 10000.0, 0.707
TOP_K, SCALING, EPS, FIRST = 8, 1.0, 1e-5, 0


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(w, x, eps=EPS):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(w)


def gated_mlp(gate, up, down, x):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


def rot(x, theta, rotary=ROTARY):
    """x [n, L, heads, d]: the first `rotary` dims of every head at position t
    turned by t's angles, the others as they are."""
    half = rotary // 2
    t = jnp.arange(x.shape[1], dtype=jnp.float32)
    angles = t[:, None] * theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary:]], -1)


def attention(p, x, kind, window=WINDOW, head=HEAD, v_head=V_HEAD, rotary=ROTARY, theta_full=THETA_FULL,
              theta_window=THETA_WINDOW, value_scale=VALUE_SCALE):
    """(one layer's grouped-query attention of the normed x [n, L, H], a
    key-value group at a time so that [n, heads, L, L] is never whole; the
    sink's probability averaged over the heads, [n, L], or None for a layer
    without a sink)."""
    n, length, _ = x.shape
    heads, kv = p["q"].shape[1] // head, p["k"].shape[1] // head
    theta = theta_window if kind else theta_full
    q = rot((x @ _f32(p["q"])).reshape(n, length, heads, head), theta, rotary)
    k = rot((x @ _f32(p["k"])).reshape(n, length, kv, head), theta, rotary)
    v = value_scale * (x @ _f32(p["v"])).reshape(n, length, kv, v_head)
    t = jnp.arange(length)
    seen = t[None, :] <= t[:, None]
    if kind:
        seen &= t[:, None] - t[None, :] < window
    per_group, out, sunk = heads // kv, [], []
    for g in range(kv):
        mine = q[:, :, g * per_group:(g + 1) * per_group]  # the query heads that read group g
        scores = jnp.where(seen, jnp.einsum("nqhd,nkd->nhqk", mine, k[:, :, g]) / math.sqrt(head), -jnp.inf)
        if "sink" in p:
            b = _f32(p["sink"])[g * per_group:(g + 1) * per_group]
            column = jnp.broadcast_to(b[None, :, None, None], scores.shape[:-1] + (1,))
            probs = jax.nn.softmax(jnp.concatenate([scores, column], axis=-1), axis=-1)
            sunk.append(probs[..., -1])  # [n, heads of the group, L]
            probs = probs[..., :-1]  # the sink takes mass and gives no value
        else:
            probs = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("nhqk,nkd->nqhd", probs, v[:, :, g]))
    mix = jnp.concatenate(out, axis=2).reshape(n, length, heads * v_head) @ _f32(p["o"])
    return mix, (jnp.concatenate(sunk, axis=1).mean(axis=1) if sunk else None)


def router_gates(router, x, top_k=TOP_K, scaling=SCALING):
    """The gate of EVERY routed expert for every token, [..., E]: the
    normalised, scaled score where the expert is among the token's top-k,
    else 0."""
    scores = jax.nn.sigmoid(x @ _f32(router))
    kth = jnp.sort(scores, axis=-1)[..., -top_k]
    kept = jnp.where(scores >= kth[..., None], scores, 0.0)
    return kept / kept.sum(-1, keepdims=True) * scaling


def routed(layer, x, first=FIRST, top_k=TOP_K, scaling=SCALING):
    """The part of the routed sum that the experts held give; no shared expert."""
    gates = router_gates(layer["router"], x, top_k, scaling)
    experts, out = layer["experts"], jnp.zeros_like(x)
    for e in range(experts["gate"].shape[0]):
        y = gated_mlp(experts["gate"][e], experts["up"][e], experts["down"][e], x)
        out = out + gates[..., first + e, None] * y
    return out


def layer_forward(layer, x, kind, first=FIRST, top_k=TOP_K, scaling=SCALING, eps=EPS, **attention_sizes):
    mix, sunk = attention(layer["attn"], rms_norm(layer["input_norm"], x, eps), kind, **attention_sizes)
    h = x + mix
    g = rms_norm(layer["post_attn_norm"], h, eps)
    if "mlp" in layer:
        return h + gated_mlp(layer["mlp"]["gate"], layer["mlp"]["up"], layer["mlp"]["down"], g), sunk
    return h + routed(layer, g, first, top_k, scaling), sunk


def once_there(x, tree):
    """`tree` as it is, but not before `x` is there: for the host's memory
    alone. XLA's CPU backend orders a program for concurrency, and a weight's
    cast to float32 waits for nothing but the weight, so every cast would come
    first and the whole model stand in float32 at once (8.3 GB of this
    configuration's). A cast that waits for the layer before it is made when
    it is needed, and the next layer's takes its room. w + 0 is w in every
    format, so no number changes."""
    zero = x.ravel()[0] * 0
    return jax.tree.map(lambda w: w + zero.astype(w.dtype), tree)


def logits_and_sinks(params, batch, hybrid_layer_pattern=PATTERN, **sizes):
    """(the logit of every row; for every layer with a sink, the sink's
    probability averaged over the heads at every position, [n, L])."""
    table = _f32(params["embedding"])
    rows = jnp.remainder(batch["feat_ids"], table.shape[0])
    x = table[rows] * _f32(batch["feat_wts"])[..., None]
    sinks = []
    for kind, layer in zip(hybrid_layer_pattern, params["layers"]):
        x, sunk = layer_forward(once_there(x, layer), x, kind, **sizes)
        sinks.append(sunk)
    return rms_norm(params["final_norm"], x[:, -1], sizes.get("eps", EPS)) @ _f32(params["score"]), sinks


def logits(params, batch, **sizes):
    return logits_and_sinks(params, batch, **sizes)[0]


def forward(params, batch, **sizes):
    return jax.nn.sigmoid(logits(params, batch, **sizes))


def sink_mass_pct(params, batch, **sizes):
    """What the served step's `attn.sink_mass_ppm / attn.sink_rows / 1e4`
    reads over these rows, in percent: the mean over (row, sink layer) pairs
    of the sink's probability averaged over the heads and over the positions
    the served step computes of that layer: all of them, but the LAST layer's
    last position alone (the one departure of this number from `every layer
    at every position`: it mirrors a counter of the served step)."""
    sinks = logits_and_sinks(params, batch, **sizes)[1]
    pairs = [sunk[:, -1] if i == len(sinks) - 1 else sunk.mean(axis=1)
             for i, sunk in enumerate(sinks) if sunk is not None]
    return 100.0 * jnp.stack(pairs).mean()
