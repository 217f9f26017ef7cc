"""falcon_h1: Falcon-H1-34B-Instruct (`model_type: falcon_h1`) as a pointwise
sequence ranker, through the same Predict path and wire contract as the five
sequence families before it: a candidate row is `num_fields` token ids
(`feat_ids [n, L]`, folded by `% vocab_size`), `feat_wts [n, L]` multiplies the
token's embedding (float32 on the link and in the product), and
`prediction_node [n]` is the sigmoid of one logit read at the last position,
`s = w_score . RMS_final(h_L)`.

EVERY layer holds two mixers of different kinds SIDE BY SIDE, a Mamba-2 (SSD)
mixer and grouped-query attention: one RMSNorm, both read it, and their outputs
are added before the residual. Every product carries one of the model's twelve
published multipliers, applied in float32 where the equations put it (folding
one into a bfloat16 weight is another rounding and is not done):

  x_0[t] = w_t * E[id_t] * embedding_multiplier
  a = RMS_in(x)
  attention:  q = (a * attention_in_multiplier) W_q,  k = (.) W_k * key_multiplier,  v = (.) W_v     no biases
              rotary on all d dims of q and k, pairs (i, i + d/2), angle t * rope_theta ** (-2i / d)
              query head h reads key-value head h // (heads / kv);  scores = q k' / sqrt(d), causal, full
              att = concat_h(softmax(scores) v) W_o * attention_out_multiplier
  ssm:        p = ((a * ssm_in_multiplier) W_in) * m,  m = ssm_multipliers spread over the slices
                  [z: d_ssm | x: d_ssm | B: G N | C: G N | dt: H], one multiplier a slice, in that order
              [x | B | C] <- silu(conv(.) + bias)     depthwise, causal, mamba_d_conv taps
              dt = softplus(dt + dt_bias) [H];  A = -exp(A_log) [H]      a scalar a head
              head h (x_h [P], group g = h // (H / G), B_g, C_g [N]):
                  S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,  S_0 = 0     [P, N]
                  y_t = S_t C_t + D_h x_t
              y <- y * silu(z);  y <- RMS over each of the G groups of d_ssm / G channels * w [d_ssm]
                  (mamba_rms_norm true, mamba_norm_before_gate false: the gate first, then the norm)
              ssm = y W_out * ssm_out_multiplier
  h = x + att + ssm
  y = h + ((silu((b W_gate) * mlp_multipliers[0]) * (b W_up)) W_down) * mlp_multipliers[1],  b = RMS_ff(h)

`ssm`, `ssd` and the blocks under them also serve nemotron_h's Mamba-2 layers
(models/nemotron_h.py: the same mixer alone in a layer, every multiplier 1,
128 heads of 64 over 8 groups of 16 with a `[64, 128]` state, 10,240 convolved
channels, three pieces), which hands in its own sizes and piece count.

The state is a `[P, N]` matrix a head whose decay is a scalar a head and
position (`[128, 256]` over 32 heads, 4.19 MB a row, at the published widths;
nemotron_h's `[64, 128]` over 128 heads are the same bytes):
position by position a row would carry it through memory L times a layer. `ssd`
computes the recurrence a chunk of `mamba_chunk_size` positions at a time,
exactly (in real arithmetic). With `cum_i` the running sum of `dt A` inside a
chunk (falling: every exponent below is a difference <= 0 under its mask, so
nothing overflows however fast a head decays) and `S_in` the state handed in,

  Y = (M o (C B')) (dt x) + exp(cum) o (C S_in),     M_ij = exp(cum_i - cum_j) where j <= i, else 0
  S_out = exp(cum_last) S_in + (exp(cum_last - cum) dt x)' B

`C B'` is made once a GROUP and read by its heads. XLA's path makes every
product for all chunks of a row at once but the hand-over itself, a `lax.scan`
over the chunks whose carry is the float32 state and whose step is one
multiply-add of it (`ssd.handovers`: one a chunk, row and layer): the chunks'
own states and the states handed in pass through memory. Inside a one-chip
served entry on a TPU (`takes_kernel`, by `sequence.kernels_run`; the servable's
`startup.ssd` stamp says which) the walk at all positions is ONE Pallas kernel
a layer (ops/ssd_kernel.py): a grid over (row, group of heads, chunk: 8 heads
a step here, a whole group of 16 of nemotron_h's narrower ones) whose
states stay in VMEM from a row's first chunk to its last, x, B, C and y
crossing as the convolution leaves them; the same pieces in the same pairs,
float32 sums in another order (tests/test_ssd_kernel.py). A length that is no
multiple of the chunk is padded with `dt = 0`, which leaves the state as it
is. The convolution before it is ONE kernel too, in every layer
(ops/conv_kernel.py, chosen by `conv_choice` from the shapes; the
`startup.conv` stamp): it reads the channels x | B | C where they lie in the
input projection's array and leaves them side by side in one array, which the
SSD's kernel reads three windows of, so that between `in_proj` and the walk
the channels are read once and written once
(tests/test_conv_kernel.py); everywhere else `sequence.causal_conv`.

The attention is `sequence.blocked_attention`: in a one-chip served entry on a
TPU every layer but the last runs ONE Pallas kernel (ops/attention_kernel.py),
everywhere else XLA's blocks.

What the served step skips (exact): the score reads the last position, so of
the LAST layer the keys and values, the SSM's input projection, convolution
and state hand-overs are computed at all positions, and the query, the
attention's output, the SSD's read of the state (the last `y` alone: the
chunks' products inside themselves are not made at all, and the hand-overs
are XLA's scan on every backend), the gate, the gated
norm, both output products and the MLP at the last position only; every layer
before it at all positions. A row whose weights are all zero (a padded row) is
left out of every counter.

Numerics as `olmo_hybrid`: parameters and matmul operands in `compute_dtype`,
float32 accumulation, residual, norms, softmax, rotary, convolution, gates,
`dt`, decays and the SSD's state; a float32 activation enters a product as
OPERAND_PIECES = 2 pieces of the compute dtype, the products between
activations inside the SSD (`C B'`, the chunk's own product, the states', the
read of the state handed in) included.
"""

from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

from . import routed, sequence
from .base import Model, ModelConfig, register_model
from .embeddings import embedding_init, field_embed
from .routed import INIT_STD, gated_init, matrix, rms_norm, rope_table, rotate

# Pieces of the compute dtype a wider activation enters a product as: read at
# every call (tests and the benchmark's readings replace it by name).
OPERAND_PIECES = sequence.OPERAND_PIECES
# What the SSD's state is carried in from chunk to chunk; replaced by name as
# OPERAND_PIECES is, to plant a state of the precision below.
STATE_DTYPE = jnp.float32
STEP_STATS = ("attn.scores_computed", "attn.scores_seen", "ssd.rows", "ssd.handovers", "ssd.positions")
SLICES = ("z", "x", "B", "C", "dt")  # of the SSM's input projection, in the order `ssm_multipliers` names them


def _sizes(config: ModelConfig) -> dict:
    heads, kv = config.num_attention_heads, config.num_key_value_heads
    if kv <= 0 or heads % kv:
        raise ValueError(f"num_key_value_heads {kv} of num_attention_heads {heads}: whole groups of query heads")
    head = config.head_dim or config.embed_dim // heads
    if head <= 0 or head % 2:
        raise ValueError(f"head_dim {head}: the rotary turn takes pairs")
    ssm_heads, ssm_head, groups = config.mamba_n_heads, config.mamba_d_head, config.mamba_n_groups
    if min(ssm_heads, ssm_head) <= 0 or ssm_heads * ssm_head != config.mamba_d_ssm:
        raise ValueError(f"mamba_d_ssm {config.mamba_d_ssm}: mamba_n_heads {ssm_heads} heads of mamba_d_head {ssm_head}")
    if groups <= 0 or ssm_heads % groups:
        raise ValueError(f"mamba_n_groups {groups} of mamba_n_heads {ssm_heads}: whole groups of heads share B and C")
    if min(config.mamba_d_state, config.mamba_d_conv, config.mamba_chunk_size) <= 0:
        raise ValueError("mamba_d_state, mamba_d_conv, mamba_chunk_size: positive")
    if len(config.ssm_multipliers) != len(SLICES):
        raise ValueError(f"ssm_multipliers {config.ssm_multipliers}: one for each of {SLICES}")
    if len(config.mlp_multipliers) != 2:
        raise ValueError(f"mlp_multipliers {config.mlp_multipliers}: the gate's and the output's")
    if not config.key_multiplier:
        raise ValueError("key_multiplier 0: every score would be 0 (and a seeded tree draws its keys wider by 1 / it)")
    d_ssm, state = config.mamba_d_ssm, config.mamba_d_state
    return {
        "hidden": config.embed_dim, "inter": config.intermediate_size, "heads": heads, "kv": kv, "head": head,
        "theta": config.rope_theta, "d_ssm": d_ssm, "ssm_heads": ssm_heads, "ssm_head": ssm_head, "state": state,
        "groups": groups, "taps": config.mamba_d_conv, "chunk": config.mamba_chunk_size,
        # the slices of the input projection, in SLICES' order, and the convolution's channels (x, B, C)
        "widths": (d_ssm, d_ssm, groups * state, groups * state, ssm_heads), "channels": d_ssm + 2 * groups * state,
        "embed_mult": config.embedding_multiplier, "attn_in": config.attention_in_multiplier,
        "attn_out": config.attention_out_multiplier, "key_mult": config.key_multiplier,
        "ssm_in": config.ssm_in_multiplier, "ssm_out": config.ssm_out_multiplier,
        "ssm_mults": tuple(config.ssm_multipliers), "mlp_mults": tuple(config.mlp_multipliers),
    }


def _ssm_init(rng, s: dict, dtype) -> dict:
    """Mamba-2's own defaults (`mamba_ssm`'s `Mamba2`): `A` uniform in
    (1, 16), `dt` log-uniform in (1e-3, 1e-1) and `dt_bias` its inverse
    softplus, so that a step's decay exp(-A dt) spreads over about (0.2, 1),
    `D` 1, the gated norm's weight 1; the depthwise convolution and its bias as
    torch's Conv1d draws them (uniform, bound 1 / sqrt(taps))."""
    k_in, k_conv, k_bias, k_A, k_dt, k_out = jax.random.split(rng, 6)
    heads, bound = s["ssm_heads"], s["taps"] ** -0.5
    dt = jnp.exp(jax.random.uniform(k_dt, (heads,)) * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return {
        "in": matrix(k_in, (s["hidden"], sum(s["widths"])), dtype),
        "conv_w": jax.random.uniform(k_conv, (s["channels"], s["taps"]), dtype, -bound, bound),
        "conv_b": jax.random.uniform(k_bias, (s["channels"],), dtype, -bound, bound),
        "A_log": jnp.log(jax.random.uniform(k_A, (heads,), minval=1.0, maxval=16.0)).astype(dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "D": jnp.ones((heads,), dtype), "norm": jnp.ones((s["d_ssm"],), dtype),
        "out": matrix(k_out, (s["d_ssm"], s["hidden"]), dtype),
    }


def _layer_init(rng, s: dict, dtype) -> dict:
    """One layer's tree: every matrix N(0, INIT_STD) but the keys', which a
    seeded tree draws WIDER by the factor `key_multiplier` then takes away
    (N(0, INIT_STD / key_multiplier)). Trained keys have the size training
    gave them; seeded ones at INIT_STD under the published 0.011 give scores
    of deviation 0.03 at the published widths, a softmax that is uniform to
    3%, through which neither the rotary turn nor the multiplier itself shows
    in the score (at a small size with the published magnitudes: rotary left
    out moved the score by 3e-4, beside 8e-2 with scores of deviation 2.6)."""
    k_q, k_k, k_v, k_o, k_ssm, k_mlp = jax.random.split(rng, 6)
    hidden, heads, kv, head = s["hidden"], s["heads"], s["kv"], s["head"]
    return {
        "input_norm": jnp.ones((hidden,), dtype), "pre_ff_norm": jnp.ones((hidden,), dtype),
        "attn": {
            "q": matrix(k_q, (hidden, heads * head), dtype),
            "k": matrix(k_k, (hidden, kv * head), dtype) / jnp.asarray(abs(s["key_mult"]), dtype),
            "v": matrix(k_v, (hidden, kv * head), dtype), "o": matrix(k_o, (heads * head, hidden), dtype),
        },
        "ssm": _ssm_init(k_ssm, s, dtype),
        "mlp": gated_init(k_mlp, (hidden, s["inter"]), (s["inter"], hidden), dtype),
    }


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def _product(spec: str, x: jax.Array, y: jax.Array, cd, count: int | None = None) -> jax.Array:
    """einsum(spec, x, y) as `sequence.product`, at the caller's pieces (this
    family's where it names none)."""
    return sequence.product(spec, x, y, cd, OPERAND_PIECES if count is None else count)


def _dot(x: jax.Array, w: jax.Array, cd, count: int | None = None) -> jax.Array:
    """`routed.dot` at the caller's pieces (this family's where it names none)."""
    return routed.dot(x, w, cd, OPERAND_PIECES if count is None else count)


def slice_multipliers(s: dict) -> np.ndarray:
    """`ssm_multipliers` spread over the columns of the input projection: one
    multiplier a slice, the slices in SLICES' order. A float32 constant."""
    return np.repeat(np.asarray(s["ssm_mults"], np.float32), s["widths"])


def time_steps(p: dict, dt: jax.Array) -> jax.Array:
    """`softplus(dt + dt_bias)` of the projection's `dt [n, L, H]`; no clamp
    (`time_step_limit` is (0, inf) as published)."""
    return jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))


def skip(p: dict, y: jax.Array, x: jax.Array) -> jax.Array:
    """`y + D x`, `[..., H, P]`: the mixer's input past its state, a learned
    scalar a head."""
    return y + p["D"].astype(jnp.float32)[:, None] * x


def gated_norm(p: dict, y: jax.Array, z: jax.Array, s: dict, eps: float) -> jax.Array:
    """The SSD's output `y [n, q, d_ssm]` gated by `z` and THEN normalised
    (`mamba_norm_before_gate` false), the RMS over each of the `mamba_n_groups`
    groups of channels, one learned weight a channel."""
    y = y * jax.nn.silu(z)
    grouped = y.reshape(y.shape[:-1] + (s["groups"], -1))
    return rms_norm(p["norm"].reshape(s["groups"], -1), grouped, eps).reshape(y.shape)


def ssd_chunks(length: int, chunk: int) -> tuple[int, int]:
    """(positions a chunk of the SSD over rows of `length`, the chunks a row:
    its state hand-overs)."""
    chunk = max(1, min(chunk, length))
    return chunk, -(-length // chunk)


def takes_kernel(last_only: bool = False) -> bool:
    """Whether the Pallas kernel walks an SSD's chunks (ops/ssd_kernel.py: the
    states stay in VMEM along a row): where a served entry's kernels run
    (`sequence.kernels_run`: inside the batcher's one-chip entry on a TPU), at
    every length, for `y` at all positions. The last layer's hand-overs, whose
    `y` is read at one position, stay XLA's scan, as its one query stays XLA's
    attention."""
    return sequence.kernels_run() and not last_only


def ssd_choice(length: int, s: dict, last_only: bool = False) -> dict:
    """`{"path": "pallas" | "xla", "chunk", "state_bytes_a_row", "heads"}`: how
    the SSD walks rows of `length` positions: which path (`takes_kernel`;
    XLA's scan hands the state over through HBM), the positions a chunk, the
    float32 bytes of a row's state that a hand-over carries and the heads'
    shape, `[H, P, N]`: H heads, each a `[P, N]` state. A servable's
    `startup.ssd` stamp."""
    return {"path": "pallas" if takes_kernel(last_only) else "xla", "chunk": ssd_chunks(length, s["chunk"])[0],
            "state_bytes_a_row": s["ssm_heads"] * s["ssm_head"] * s["state"] * 4,
            "heads": [s["ssm_heads"], s["ssm_head"], s["state"]]}


def note_ssd(length: int, s: dict, last_only: bool = False) -> None:
    """`ssd_choice`, noted for the served entry being traced."""
    served = sequence.served_entry()
    choice = ssd_choice(length, s, last_only)
    if served is not None and served.ssd is not None and choice not in served.ssd:
        served.ssd.append(choice)


def conv_choice(length: int, s: dict) -> dict:
    """`{"path": "pallas" | "xla", "lanes", "positions"}`: what convolves the
    mixer's channels over rows of `length` positions: the Pallas kernel that
    reads them where they lie in the input projection's array and leaves x, B
    and C side by side in one (ops/conv_kernel.py: no array of their own on
    either side), in blocks of `lanes` lanes and `positions` positions, where
    a served entry's kernels run (`sequence.kernels_run`) and the shapes are
    whole blocks; else XLA's form (`sequence.causal_conv`), with `"why"`
    where a shape keeps the kernel out (`conv_kernel.whole_blocks` has the
    rule and the reasons: `lanes`, `positions`, `taps`). A servable's
    `startup.conv` stamp."""
    from ..ops import conv_kernel

    xla = {"path": "xla", "lanes": 0, "positions": 0}
    if not sequence.kernels_run():
        return xla
    lanes, positions, why = conv_kernel.whole_blocks(s["d_ssm"], s["channels"], length, s["taps"])
    return dict(xla, why=why) if why else {"path": "pallas", "lanes": lanes, "positions": positions}


def note_conv(length: int, s: dict) -> dict:
    """`conv_choice`, noted for the served entry being traced."""
    served = sequence.served_entry()
    choice = conv_choice(length, s)
    if served is not None and served.conv is not None and choice not in served.conv:
        served.conv.append(choice)
    return choice


def ssd(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
        initial_state: jax.Array | None = None, *, chunk: int, cd=jnp.float32,
        last_only: bool = False, count: int | None = None) -> tuple[jax.Array, jax.Array]:
    """Mamba-2's recurrence over rows of L positions, chunked (the module's
    docstring has the algebra):

      S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t;   y_t = S_t C_t

    `x [n, L, H, P]`, `dt [n, L, H]` (>= 0), `a [H]` (<= 0), `b`, `c [n, L, G, N]`
    (head h reads group `h // (H / G)`), all float32; `initial_state [n, H, P, N]`
    is S before the first position (zero where None). Returns `y [n, L, H, P]`
    and the state after the last position, float32. With `last_only`, `y` is
    `[n, 1, H, P]`, the last position's alone: the state's hand-overs are made
    and the chunks' own products are not. Activations enter the products as
    `count` pieces (this family's OPERAND_PIECES where None). The caller's
    `ssd` scope.

    Where a one-chip served entry's kernels run (`takes_kernel`) the walk at
    all positions is one Pallas kernel a layer that keeps the states in VMEM
    (ops/ssd_kernel.py); everywhere else (`shard_map`, the GSPMD executors,
    the trainer, a CPU) and for the last position alone it is XLA's, below:
    the plain form the kernel is tested against."""
    n, length, heads, width = x.shape
    groups, state_width = b.shape[2], b.shape[3]
    per = heads // groups
    count = OPERAND_PIECES if count is None else count
    product = functools.partial(_product, count=count)
    c_last = c[:, -1]
    chunk, steps = ssd_chunks(length, chunk)
    pad = steps * chunk - length

    def padded(v):  # the row's end padded with zeros (dt = 0)
        return jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))

    def chunks(v):  # [n, L, ...] -> [n, steps, chunk, ...]
        return padded(v).reshape((n, steps, chunk) + v.shape[2:])

    if takes_kernel(last_only):  # x, B, C and y cross as they lie: no turn on either side
        from ..ops import ssd_kernel

        with jax.named_scope("chunks"):
            dt = jnp.moveaxis(chunks(dt), 3, 1)  # [n, H, Z, C]: a chunk's positions in the lanes
            state = jnp.zeros((n, heads, width, state_width), jnp.float32) if initial_state is None else initial_state
            flat = [padded(v).reshape(n, steps * chunk, -1) for v in (x, b, c)]
            if ssd_kernel.windows_fit(heads, groups, width, state_width):
                # Side by side in ONE array, which the kernel reads three windows of. `ssm` hands x, B and C as
                # the three parts of the convolution's one array: cut and joined again is that array itself to
                # XLA's simplifier, and the compiled step holds neither a slice nor a copy of them
                # (tests/test_tpu_compile.py). Three arrays of their own (a test's, a planted fault's) are joined
                # by a copy.
                flat = [jnp.concatenate(flat, axis=-1), None, None]
            y, state = ssd_kernel.chunk_walk(
                dt, jnp.cumsum(dt * a.astype(jnp.float32)[:, None, None], axis=3), *flat,
                state.astype(STATE_DTYPE).astype(jnp.float32), heads=heads, groups=groups, cd=jnp.dtype(cd),
                count=count, state_dtype=jnp.dtype(STATE_DTYPE), interpret=sequence.served_entry().interpret)
        return y.reshape(n, steps * chunk, heads, width)[:, :length], state
    x = chunks(x).reshape(n, steps, chunk, groups, per, width)
    dt = chunks(dt).reshape(n, steps, chunk, groups, per)
    b, c = chunks(b), chunks(c)
    total = jnp.cumsum(dt * a.astype(jnp.float32).reshape(groups, per), axis=2)  # cum [n, Z, C, G, J], falling
    left = total[:, :, -1:]  # cum_last
    fed = x * dt[..., None]  # dt x
    with jax.named_scope("states"):  # what each chunk adds to the state it is handed, all chunks at once
        local = product("nzcgjp,nzcgs->nzgjps", fed * jnp.exp(left - total)[..., None], b, cd)
    with jax.named_scope("handover"):
        def body(state, step):
            local_z, decay_z = step
            out = decay_z[..., None, None] * state.astype(jnp.float32) + local_z
            return out.astype(STATE_DTYPE), state

        state = (jnp.zeros((n, groups, per, width, state_width), jnp.float32) if initial_state is None
                 else initial_state.reshape(n, groups, per, width, state_width))
        state, entered = jax.lax.scan(
            body, state.astype(STATE_DTYPE), (jnp.moveaxis(local, 1, 0), jnp.moveaxis(jnp.exp(left[:, :, 0]), 1, 0)))
        state = state.astype(jnp.float32)
    if last_only:
        with jax.named_scope("read"):
            y = product("ngjps,ngs->ngjp", state, c_last, cd)
        return y.reshape(n, 1, heads, width), state.reshape(n, heads, width, state_width)
    with jax.named_scope("within"):  # the chunk's own positions: (M o (C B')) (dt x)
        i, j = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
        falling = jnp.moveaxis(total, 2, -1)  # [n, Z, G, J, C]
        decay = jnp.exp(jnp.where(j <= i, falling[..., :, None] - falling[..., None, :], -jnp.inf))
        scores = product("nzigs,nzcgs->nzgic", c, b, cd)  # C B', once a group
        y = product("nzgjic,nzcgjp->nzigjp", decay * scores[:, :, :, None], fed, cd)
    with jax.named_scope("read"):  # the state handed in, read by every position of the chunk
        entered = jnp.moveaxis(entered, 0, 1).astype(jnp.float32)  # [n, Z, G, J, P, N]
        y = y + product("nzgjps,nzcgs->nzcgjp", entered, c, cd) * jnp.exp(total)[..., None]
    return y.reshape(n, steps * chunk, heads, width)[:, :length], state.reshape(n, heads, width, state_width)


def ssm(p: dict, a: jax.Array, s: dict, cd, eps: float, last_only: bool = False,
        count: int | None = None) -> jax.Array:
    """One layer's Mamba-2 mixer of the normed `a [n, L, hidden]`: `[n, L, hidden]`,
    or `[n, 1, hidden]` where the last position's output alone is asked for
    (the state still walks every position). `s` holds the mixer's sizes and
    multipliers (`_sizes`' keys `hidden` to `ssm_mults`; nemotron_h hands in
    its own, every multiplier 1) and `count` the pieces an activation enters
    a product as (this family's where None). The caller's `ssm` scope."""
    n, length, _ = a.shape
    heads, width, groups, state = s["ssm_heads"], s["ssm_head"], s["groups"], s["state"]
    note_ssd(length, s, last_only)
    conv = note_conv(length, s)
    with jax.named_scope("in_proj"):
        projected = _dot(a * s["ssm_in"], p["in"], cd, count) * slice_multipliers(s)
        z, mixed, dt = jnp.split(projected, (s["d_ssm"], s["d_ssm"] + s["channels"]), axis=-1)
    with jax.named_scope("conv"):
        if conv["path"] == "pallas":  # the channels read where they lie in the projection: no `mixed` of their own
            from ..ops import conv_kernel

            mixed = conv_kernel.causal_conv(projected, p["conv_w"], p["conv_b"], offset=s["d_ssm"],
                                            channels=s["channels"], interpret=sequence.served_entry().interpret)
        else:
            mixed = sequence.causal_conv(mixed, p["conv_w"], p["conv_b"])
        x, b, c = jnp.split(mixed, (s["d_ssm"], s["d_ssm"] + groups * state), axis=-1)
        x = x.reshape(n, length, heads, width)
    with jax.named_scope("ssd"):
        y, _ = ssd(x, time_steps(p, dt), -jnp.exp(p["A_log"].astype(jnp.float32)), b.reshape(n, length, groups, state),
                   c.reshape(n, length, groups, state), chunk=s["chunk"], cd=cd, last_only=last_only, count=count)
        if last_only:
            x, z = sequence.last_position(x, z)
        y = skip(p, y, x)
    with jax.named_scope("gate_norm"):
        y = gated_norm(p, y.reshape(n, -1, s["d_ssm"]), z, s, eps)
    with jax.named_scope("out_proj"):
        return _dot(y, p["out"], cd, count) * s["ssm_out"]


def attention(p: dict, a: jax.Array, s: dict, cd, last_only: bool = False) -> jax.Array:
    """One layer's attention of the normed `a [n, L, hidden]`: `[n, L, hidden]`,
    or `[n, 1, hidden]` for the last position's query alone against the keys
    and values of every position. The caller's `attn_full` scope."""
    n, length, _ = a.shape
    heads, kv, head = s["heads"], s["kv"], s["head"]
    a = a * s["attn_in"]
    at = sequence.last_position(a) if last_only else a
    queries = at.shape[1]
    with jax.named_scope("qkv"):
        q = _dot(at, p["q"], cd).reshape(n, queries, kv, heads // kv, head)
        k = (_dot(a, p["k"], cd) * s["key_mult"]).reshape(n, length, kv, head)
        v = _dot(a, p["v"], cd).reshape(n, length, kv, head)
    with jax.named_scope("rope"):
        cos, sin = rope_table(length, head, s["theta"])
        q = rotate(q, cos[length - queries:, None, None, :], sin[length - queries:, None, None, :])
        k = rotate(k, cos[:, None, :], sin[:, None, :])
    with jax.named_scope("softmax"):
        o = sequence.blocked_attention(q, k, v, None, cd, OPERAND_PIECES)
    return _dot(o.reshape(n, queries, heads * head), p["o"], cd) * s["attn_out"]


def mlp(p: dict, x: jax.Array, s: dict, cd) -> jax.Array:
    """`((silu((x W_gate) * m_0) * (x W_up)) W_down) * m_1`."""
    gate = jax.nn.silu(_dot(x, p["gate"], cd) * s["mlp_mults"][0])
    return _dot(gate * _dot(x, p["up"], cd), p["down"], cd) * s["mlp_mults"][1]


def step_counts(layers: int, length: int, chunk: int) -> tuple[int, ...]:
    """STEP_STATS a live row, from the shapes: the (query, key) pairs the
    layers' tiles compute and those their masks keep (the last layer's one
    query; every head computes the same pairs), 1, and the state hand-overs
    and the positions of the layers' SSDs."""
    whole, one = sequence.blocked_pairs(length, length), sequence.blocked_pairs(1, length)
    return ((layers - 1) * whole[0] + one[0], (layers - 1) * whole[1] + one[1], 1,
            layers * ssd_chunks(length, chunk)[1], layers * length)


def forward(config: ModelConfig, params, batch) -> tuple[jax.Array, jax.Array]:
    """(the logit of every row, the step's counters): of the last layer, what
    follows its mixing along the positions at the last position alone."""
    s, cd, eps = _sizes(config), config.cdtype, config.layer_norm_eps
    layers = params["layers"]
    with jax.named_scope("embed"):
        # The weighted embedding in float32, where a bfloat16 row times a
        # float32 weight is exact.
        x = field_embed(params["embedding"], batch["feat_ids"], batch["feat_wts"], jnp.float32, s["hidden"])
        x = x * s["embed_mult"]
        live = jnp.any(batch["feat_wts"] != 0, axis=1)  # a padded row is left out of every counter
    for i, layer in enumerate(layers):
        last = i == len(layers) - 1
        a = rms_norm(layer["input_norm"], x, eps)  # ONE norm, read by both mixers
        with jax.named_scope("attn_full"):
            mix = attention(layer["attn"], a, s, cd, last)
        with jax.named_scope("ssm"):
            mix = mix + ssm(layer["ssm"], a, s, cd, eps, last)
        if last:
            x = sequence.last_position(x)
        h = x + mix
        with jax.named_scope("mlp"):
            x = h + mlp(layer["mlp"], rms_norm(layer["pre_ff_norm"], h, eps), s, cd)
    with jax.named_scope("score"):
        final = rms_norm(params["final_norm"], x[:, -1], eps)
        counts = jnp.asarray(step_counts(len(layers), batch["feat_ids"].shape[1], s["chunk"]), jnp.int32)
        # The counters leave WITH the logits, as olmo_hybrid's and for its
        # reason: where they stand among an executable's results otherwise
        # decides which weights XLA prefetches (PERF.md section 6, PR 52).
        return jax.lax.optimization_barrier((
            jnp.sum(final * params["score"].astype(jnp.float32), axis=-1), jnp.sum(live, dtype=jnp.int32) * counts))


def attention_plan(config: ModelConfig) -> tuple[tuple[tuple[str, object], ...], ...]:
    """Each layer's two mixers as (name, value) pairs: the attention's kind,
    window, block of queries and keys a block, as `olmo_hybrid`'s full layers
    state theirs, its key-value heads and rotary base; and under `ssd` the
    SSM's kind, chunk, state hand-overs a row and the bytes of a row's state."""
    s, length = _sizes(config), config.num_fields
    walk = ssd_choice(length, s)
    layer = (("kind", "parallel"), ("window", 0), ("block", min(sequence.ATTN_BLOCK, length)),
             ("keys_a_block", length), ("kv_heads", s["kv"]), ("theta", s["theta"]),
             ("ssd", (("kind", "ssd"), ("chunk", walk["chunk"]),
                      ("handovers_a_row", ssd_chunks(length, s["chunk"])[1]),
                      ("state_bytes_a_row", walk["state_bytes_a_row"]))))
    return (layer,) * config.num_hidden_layers


@register_model("falcon_h1")
def build_falcon_h1(config: ModelConfig) -> Model:
    s = _sizes(config)
    if config.num_hidden_layers <= 0:
        raise ValueError(f"num_hidden_layers {config.num_hidden_layers}")

    def init(rng, packed: bool = False):
        k_emb, k_score, *k_layers = jax.random.split(rng, 2 + config.num_hidden_layers)
        dtype = config.pdtype
        # embedding_init scales by 1/sqrt(dim); INIT_STD is wanted.
        table = embedding_init(k_emb, config.vocab_size, s["hidden"], dtype, packed)
        return {
            "embedding": table * jnp.asarray(INIT_STD * s["hidden"] ** 0.5, dtype),
            "layers": [_layer_init(k, s, dtype) for k in k_layers],
            "final_norm": jnp.ones((s["hidden"],), dtype),
            "score": matrix(k_score, (s["hidden"],), dtype),
        }

    def apply_stats(params, batch):
        logits, stats = forward(config, params, batch)
        return {"prediction_node": jax.nn.sigmoid(logits), "logits": logits}, stats

    def apply(params, batch):
        return apply_stats(params, batch)[0]

    # The weights cross as float32, as phi4flash's and for its reason: a
    # token's weight scales its embedding in the residual stream.
    return Model(
        config=config, init=init, apply=apply, wts_in_compute_dtype=False,
        layer_plan=("parallel",) * config.num_hidden_layers, attention_plan=attention_plan(config),
        apply_stats=apply_stats, step_stats=STEP_STATS)
