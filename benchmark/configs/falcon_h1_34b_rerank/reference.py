"""Falcon-H1-34B-Instruct (`model_type: falcon_h1`) as a pointwise sequence
ranker, the plain reference: float32 `jax.numpy`, every layer at every
position, the Mamba-2 mixer as its position-by-position recurrence, `[L, L]`
masks and a dense softmax a head; no chunks, no blocks, no pieces, nothing
skipped, nothing imported from the program.

A row is L token ids (`feat_ids [n, L]`, folded by `% V`) with a weight a
token: `x_t = w_t * E[id_t] * embedding_multiplier`. Every layer (all alike):

  a = RMS_in(x)                          ONE norm, read by both mixers
  attention:
    q = (a * attention_in_multiplier) W_q [heads x d], k = (.) W_k * key_multiplier [kv x d], v = (.) W_v [kv x d]
    rotary on all d dims of q and k: pairs (i, i + d/2), angle t * theta ** (-2i / d)
    scores = q k' / sqrt(d);  seen(t, u) = u <= t;  query head h reads key-value head h // (heads / kv)
    att = concat_h(softmax(scores | seen) v) W_o * attention_out_multiplier
  ssm (Mamba-2), H heads of P channels, a state of N a channel, G groups:
    p = ((a * ssm_in_multiplier) W_in) * m,  m = ssm_multipliers spread over the slices
        [z: H P | x: H P | B: G N | C: G N | dt: H], one multiplier a slice, in that order
    [x | B | C] <- silu(conv(.) + bias): y_t = sum_j w[:, j] * u_{t - (taps - 1) + j} + bias, a channel alone
    dt = softplus(dt + dt_bias) [H] (no clamp);  A = -exp(A_log) [H]
    head h, its group g = h // (H / G):  S_0 = 0 [P, N]
      S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_{g,t};   y_t = S_t C_{g,t} + D_h x_t
    y <- y * silu(z);  y <- y / sqrt(mean over each of the G groups of H P / G channels of y^2 + eps) * w
        (mamba_rms_norm true, mamba_norm_before_gate false: the gate first, then the norm, by groups)
    ssm = y W_out * ssm_out_multiplier
  h = x + att + ssm
  y = h + ((silu((b W_gate) * mlp_multipliers[0]) * (b W_up)) W_down) * mlp_multipliers[1],  b = RMS_ff(h)

After the last layer: s = RMS(y_{L-1}) . w_score, score = sigmoid(s). No bias on
any projection (attention_bias, mlp_bias, mamba_proj_bias, projectors_bias
false); the convolution has one (mamba_conv_bias true).

DEPARTURES from the published model, each the scorer's: the head is one logit
at the last position (`lm_head_multiplier` belongs to the language-model head,
which a scorer does not compute); the weights are seeded, not trained; the
depth is the configuration's cut (the tree's own number of layers).

`params` is the pytree the program's own `init` makes (bfloat16 leaves are cast
to float32 as each is used); its shapes give every size but the width of an
attention head, a Mamba head and the groups. Those, the multipliers, the
rotary base and the norms' epsilon are keyword arguments at the published
values. Call under `jax.default_matmul_precision("highest")`.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

HEAD, SSM_HEAD, GROUPS, THETA, EPS = 128, 128, 2, 1e11, 1e-5
EMBEDDING_MULTIPLIER = 5.656854249492381
ATTENTION_IN_MULTIPLIER, ATTENTION_OUT_MULTIPLIER, KEY_MULTIPLIER = 1.0, 0.0375, 0.011048543456039804
SSM_IN_MULTIPLIER, SSM_OUT_MULTIPLIER = 0.25, 0.08838834764831845
SSM_MULTIPLIERS = (0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738)  # z, x, B, C, dt
MLP_MULTIPLIERS = (0.1767766952966369, 0.011160714285714284)  # the gate's, the output's
PUBLISHED = {
    "head": HEAD, "ssm_head": SSM_HEAD, "groups": GROUPS, "theta": THETA, "eps": EPS,
    "embedding_multiplier": EMBEDDING_MULTIPLIER, "attention_in_multiplier": ATTENTION_IN_MULTIPLIER,
    "attention_out_multiplier": ATTENTION_OUT_MULTIPLIER, "key_multiplier": KEY_MULTIPLIER,
    "ssm_in_multiplier": SSM_IN_MULTIPLIER, "ssm_out_multiplier": SSM_OUT_MULTIPLIER,
    "ssm_multipliers": SSM_MULTIPLIERS, "mlp_multipliers": MLP_MULTIPLIERS,
}


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(w, x, eps=EPS):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(w)


def rotary(x, theta):
    """x [n, L, heads, d]: pairs (i, i + d/2) turned by t * theta ** (-2i / d)."""
    length, d = x.shape[1], x.shape[-1]
    # the angles in float64 (a position times a frequency loses 1e-4 rad in float32 by position 2,047)
    angle = np.arange(length, dtype=np.float64)[:, None] * theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    cos, sin = (jnp.asarray(f(angle), jnp.float32)[None, :, None, :] for f in (np.cos, np.sin))
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(p, a, m):
    """A head at a time, so that [n, heads, L, L] is never whole."""
    n, length, _ = a.shape
    head = m["head"]
    heads, kv = p["q"].shape[1] // head, p["k"].shape[1] // head
    a = a * m["attention_in_multiplier"]
    q = rotary((a @ _f32(p["q"])).reshape(n, length, heads, head), m["theta"])
    k = rotary((a @ _f32(p["k"]) * m["key_multiplier"]).reshape(n, length, kv, head), m["theta"])
    v = (a @ _f32(p["v"])).reshape(n, length, kv, head)
    t = jnp.arange(length)
    seen = t[None, :] <= t[:, None]
    out = []
    for h in range(heads):
        g = h // (heads // kv)
        scores = jnp.einsum("nqd,nkd->nqk", q[:, :, h], k[:, :, g]) / math.sqrt(head)
        out.append(jnp.einsum("nqk,nkd->nqd", jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), v[:, :, g]))
    return jnp.concatenate(out, axis=-1) @ _f32(p["o"]) * m["attention_out_multiplier"]


def conv_silu(x, w, bias):
    """x [n, L, channels], w [channels, taps], bias [channels]: position t reads t - taps + 1 .. t."""
    w, taps, length = _f32(w), w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + length] * w[:, j] for j in range(taps)) + _f32(bias))


def recurrence(x, dt, a, b, c):
    """x [n, L, H, P], dt [n, L, H], a [H], b, c [n, L, H, N] (a head's own
    group's, repeated): y [n, L, H, P], position by position from S_0 = 0."""
    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[..., None, None] * state + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        return state, jnp.einsum("nhps,nhs->nhp", state, c_t)

    n, _, heads, width = x.shape
    state = jnp.zeros((n, heads, width, b.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(step, state, tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def ssm(p, a, m):
    n, length, _ = a.shape
    heads, width, groups = p["A_log"].shape[0], m["ssm_head"], m["groups"]
    inner = heads * width
    state = (p["in"].shape[1] - 2 * inner - heads) // (2 * groups)
    projected = (a * m["ssm_in_multiplier"]) @ _f32(p["in"])
    edges = (inner, 2 * inner, 2 * inner + groups * state, 2 * inner + 2 * groups * state)
    parts = jnp.split(projected, edges, axis=-1)  # z, x, B, C, dt
    z, x, b, c, dt = (part * scale for part, scale in zip(parts, m["ssm_multipliers"]))
    mixed = conv_silu(jnp.concatenate([x, b, c], axis=-1), p["conv_w"], p["conv_b"])
    x, b, c = jnp.split(mixed, (inner, inner + groups * state), axis=-1)
    x = x.reshape(n, length, heads, width)
    # a head reads its own group's B and C: head h, group h // (heads / groups)
    b = jnp.repeat(b.reshape(n, length, groups, state), heads // groups, axis=2)
    c = jnp.repeat(c.reshape(n, length, groups, state), heads // groups, axis=2)
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))
    y = recurrence(x, dt, -jnp.exp(_f32(p["A_log"])), b, c) + _f32(p["D"])[:, None] * x
    y = y.reshape(n, length, inner) * jax.nn.silu(z)  # the gate first
    grouped = y.reshape(n, length, groups, inner // groups)  # then the norm, a group of channels at a time
    grouped = grouped / jnp.sqrt((grouped * grouped).mean(-1, keepdims=True) + m["eps"])
    y = grouped.reshape(n, length, inner) * _f32(p["norm"])
    return y @ _f32(p["out"]) * m["ssm_out_multiplier"]


def gated_mlp(p, x, m):
    gate = jax.nn.silu(x @ _f32(p["gate"]) * m["mlp_multipliers"][0])
    return (gate * (x @ _f32(p["up"]))) @ _f32(p["down"]) * m["mlp_multipliers"][1]


def layer_forward(layer, x, m):
    a = rms_norm(layer["input_norm"], x, m["eps"])
    h = x + attention(layer["attn"], a, m) + ssm(layer["ssm"], a, m)
    return h + gated_mlp(layer["mlp"], rms_norm(layer["pre_ff_norm"], h, m["eps"]), m)


def once_there(x, tree):
    """`tree` as it is, but not before `x` is there: for the host's memory
    alone. XLA's CPU backend orders a program for concurrency, and a weight's
    cast to float32 waits for nothing but the weight, so every cast would come
    first and the whole stack stand in float32 at once (8.6 GB of this
    configuration's). A cast that waits for the layer before it is made when
    it is needed, and the next layer's takes its room. w + 0 is w in every
    format, so no number changes."""
    zero = x.ravel()[0] * 0
    return jax.tree.map(lambda w: w + zero.astype(w.dtype), tree)


def logits(params, batch, **sizes):
    m = dict(PUBLISHED, **sizes)
    table = _f32(params["embedding"])
    rows = jnp.remainder(batch["feat_ids"], table.shape[0])
    x = table[rows] * _f32(batch["feat_wts"])[..., None] * m["embedding_multiplier"]
    for layer in params["layers"]:
        x = layer_forward(once_there(x, layer), x, m)
    return rms_norm(params["final_norm"], x[:, -1], m["eps"]) @ _f32(params["score"])


def forward(params, batch, **sizes):
    return jax.nn.sigmoid(logits(params, batch, **sizes))
