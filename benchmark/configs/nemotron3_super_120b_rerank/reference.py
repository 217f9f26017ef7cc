"""NVIDIA-Nemotron-3-Super-120B-A12B-BF16 (`model_type: nemotron_h`) as a
pointwise sequence ranker, the plain reference: float32 `jax.numpy`, every
layer at every position, the Mamba-2 mixer as its position-by-position
recurrence, `[L, L]` masks and a dense softmax a key-value group, every held
expert over every token times its gate; no chunks, no blocks, no pieces, no
gather, no grouping, nothing skipped, nothing imported from the program.

A row is L token ids (`feat_ids [n, L]`, folded by `% V`) with a weight a
token: `x_t = w_t * E[id_t]`. Every layer is ONE mixer behind ONE norm,

  x <- x + MIX_i(RMS_i(x)),   RMS_w(x) = w * x / sqrt(mean(x^2) + eps)      a plain weight

and the tree says which mixer a layer holds (`ssm`, `attn` or `moe`):

  MIX, Mamba-2 (`ssm`), H heads of P channels, a state of N a channel, G groups:
    [z: H P | x: H P | B: G N | C: G N | dt: H] = a W_in
    [x | B | C] <- silu(conv(.) + bias): y_t = sum_j w[:, j] * u_{t - (taps - 1) + j} + bias, a channel alone
    dt = softplus(dt + dt_bias) [H] (no clamp);  A = -exp(A_log) [H]
    head h, its group g = h // (H / G):  S_0 = 0 [P, N]
      S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_{g,t};   y_t = S_t C_{g,t} + D_h x_t
    y <- y * silu(z);  y <- y / sqrt(mean over each of the G groups of H P / G channels of y^2 + eps) * w
        (the gate first, then the norm, by groups)
    MIX = y W_out

  MIX, attention (`attn`), `heads` query heads over `kv` key-value heads, d wide:
    q = a W_q, k = a W_k, v = a W_v;   NO rotary turn, no position signal of any kind
    scores = q k' / sqrt(d), seen(t, u) = u <= t; query head h reads key-value head h // (heads / kv)
    MIX = concat_h(softmax(scores | seen) v) W_o

  MIX, the latent routed block (`moe`):
    p = sigmoid(a W_r) over ALL the router's experts
    the top_k largest of p + bias (the selection bias: it chooses, it never weighs)
    g_e = scaling * p_e / (the sum of the chosen p + 1e-20)            from the UNBIASED p
    a_lat = a W_in_lat;   expert_e(u) = relu(u U_e)^2 D_e              ungated, in the latent
    r = sum over the chosen e HELD HERE of g_e expert_e(a_lat)
    MIX = r W_out_lat + relu(a U_s)^2 D_s                              the shared expert reads the full width

After the last layer: s = RMS_f(x_{L-1}) . w_score, score = sigmoid(s).

**The share.** `params` is the pytree the program's own `init` makes
(bfloat16 leaves are cast to float32 as each is used). It holds what ONE chip
of the deployment holds of a layer: the experts `first .. first + held - 1`
stacked (`held` the leading size of the experts' arrays); the mixers, the
router with its bias, the latent projections, the shared expert and the norms
whole. The routed sum runs over the held experts alone; what the others would
add is left out, here as in the program, and `r W_out_lat` of that partial sum
goes on to the next layer. With every expert held, this is the whole model.

Left out, as in the program: the multi-token-prediction layer (a scorer reads
one logit), the language-model head, the absent experts.

The width of an attention head and of a Mamba-2 head, the groups, the top-k,
`first`, the scaling, whether the chosen scores are normalised and the norms'
epsilon are keyword arguments at the published values (the tree's shapes give
the rest). Call under `jax.default_matmul_precision("highest")`.
"""

import math

import jax
import jax.numpy as jnp

HEAD, SSM_HEAD, GROUPS, EPS = 128, 64, 8, 1e-5
TOP_K, FIRST, SCALING, NORM_TOPK = 22, 0, 5.0, True


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(w, x, eps=EPS):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(w)


def relu2_mlp(p, x):
    return jnp.square(jax.nn.relu(x @ _f32(p["up"]))) @ _f32(p["down"])


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


def mamba_mix(p, a, ssm_head=SSM_HEAD, groups=GROUPS, eps=EPS):
    n, length, _ = a.shape
    heads, width = p["A_log"].shape[0], ssm_head
    inner = heads * width
    state = (p["in"].shape[1] - 2 * inner - heads) // (2 * groups)
    z, mixed, dt = jnp.split(a @ _f32(p["in"]), (inner, 2 * inner + 2 * groups * state), axis=-1)
    mixed = conv_silu(mixed, p["conv_w"], p["conv_b"])
    x, b, c = jnp.split(mixed, (inner, inner + groups * state), axis=-1)
    x = x.reshape(n, length, heads, width)
    # a head reads its own group's B and C: head h, group h // (heads / groups)
    b = jnp.repeat(b.reshape(n, length, groups, state), heads // groups, axis=2)
    c = jnp.repeat(c.reshape(n, length, groups, state), heads // groups, axis=2)
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))
    y = recurrence(x, dt, -jnp.exp(_f32(p["A_log"])), b, c) + _f32(p["D"])[:, None] * x
    y = y.reshape(n, length, inner) * jax.nn.silu(z)  # the gate first
    grouped = y.reshape(n, length, groups, inner // groups)  # then the norm, a group of channels at a time
    grouped = grouped / jnp.sqrt((grouped * grouped).mean(-1, keepdims=True) + eps)
    return (grouped.reshape(n, length, inner) * _f32(p["norm"])) @ _f32(p["out"])


def attention_mix(p, a, head=HEAD):
    """A key-value group at a time, so that [n, heads, L, L] is never whole."""
    n, length, _ = a.shape
    heads, kv = p["q"].shape[1] // head, p["k"].shape[1] // head
    q = (a @ _f32(p["q"])).reshape(n, length, heads, head)
    k = (a @ _f32(p["k"])).reshape(n, length, kv, head)
    v = (a @ _f32(p["v"])).reshape(n, length, kv, head)
    t = jnp.arange(length)
    seen = t[None, :] <= t[:, None]
    per_group, out = heads // kv, []
    for g in range(kv):
        mine = q[:, :, g * per_group:(g + 1) * per_group]  # the query heads that read group g
        scores = jnp.einsum("nqhd,nkd->nhqk", mine, k[:, :, g]) / math.sqrt(head)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("nhqk,nkd->nqhd", probs, v[:, :, g]))
    return jnp.concatenate(out, axis=2).reshape(n, length, heads * head) @ _f32(p["o"])


def router_gates(router, bias, x, top_k=TOP_K, scaling=SCALING, norm_topk=NORM_TOPK):
    """The gate of EVERY routed expert for every token, [..., E]: where the
    expert is among the token's top-k by `p + bias`, its UNBIASED score over
    the sum of the chosen ones' (where `norm_topk`), times `scaling`; else 0."""
    p = jax.nn.sigmoid(x @ _f32(router))
    biased = p + _f32(bias)
    kth = jnp.sort(biased, axis=-1)[..., -top_k]
    kept = jnp.where(biased >= kth[..., None], p, 0.0)
    if norm_topk:
        kept = kept / (kept.sum(-1, keepdims=True) + 1e-20)
    return kept * scaling


def moe_mix(p, a, first=FIRST, top_k=TOP_K, scaling=SCALING, norm_topk=NORM_TOPK):
    """relu(a U_s)^2 D_s + (the part of the routed sum that the experts held
    give, in the latent) W_out_lat: every held expert over every token's
    latent, times its gate (zero where the token did not choose it)."""
    held = p["experts"]["up"].shape[0]
    gates = router_gates(p["router"], p["router_bias"], a, top_k, scaling, norm_topk)[..., first:first + held]
    latent = a @ _f32(p["latent_in"])

    def add(out, expert_and_gate):
        expert, gate = expert_and_gate
        return out + gate[..., None] * relu2_mlp(expert, latent), None

    routed = jax.lax.scan(add, jnp.zeros_like(latent), (p["experts"], jnp.moveaxis(gates, -1, 0)))[0]
    return routed @ _f32(p["latent_out"]) + relu2_mlp(p["shared"], a)


def layer_forward(layer, x, head=HEAD, ssm_head=SSM_HEAD, groups=GROUPS, first=FIRST, top_k=TOP_K, scaling=SCALING,
                  norm_topk=NORM_TOPK, eps=EPS):
    a = rms_norm(layer["norm"], x, eps)
    if "ssm" in layer:
        return x + mamba_mix(layer["ssm"], a, ssm_head, groups, eps)
    if "attn" in layer:
        return x + attention_mix(layer["attn"], a, head)
    return x + moe_mix(layer["moe"], a, first, top_k, scaling, norm_topk)


def once_there(x, tree):
    """`tree` as it is, but not before `x` is there: for the host's memory
    alone. XLA's CPU backend orders a program for concurrency, and a weight's
    cast to float32 waits for nothing but the weight, so every cast would come
    first and the whole stack stand in float32 at once (10.9 GB of this
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
