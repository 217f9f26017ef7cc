"""Pallas TPU kernel: fused DCN-v2 cross-layer stack.

The cross network applies L layers of x = x0 * (x @ W_l + b_l) + x
(models/dcn.py cross_apply). Under plain XLA each layer's output round-trips
through HBM between matmuls; this kernel keeps the activation tile resident
in VMEM across ALL layers — one HBM read of the x0 tile, L MXU matmuls
against VMEM-resident weights, one HBM write — turning an
HBM-bandwidth-bound stack into an MXU-bound one for serving-sized tiles.

Numerics mirror cross_apply exactly: matmul in the model's compute dtype
with f32 accumulation (preferred_element_type), the elementwise update in
f32, the carried activation cast back to compute dtype per layer — so the
kernel is a drop-in for the XLA path (test_cross_kernel.py pins equality).

Shapes are padded to TPU tiling (d -> multiple of 128 lanes, rows -> the
row-tile size): zero-padded W rows/cols and b lanes keep padded activation
columns identically zero through every layer, so padding never leaks into
real outputs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
DEFAULT_ROW_TILE = 256
# The scoped-VMEM limit both kernels ask Mosaic for, and the resident set
# the fits_* guards admit under it (the rest is Mosaic's own scratch). The
# limit is passed explicitly so the guards and the compiler agree on one
# number instead of on a per-generation default.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
VMEM_BUDGET_BYTES = 24 * 1024 * 1024
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def fits_vmem(
    d: int,
    num_layers: int,
    compute_dtype=jnp.bfloat16,
    row_tile: int = DEFAULT_ROW_TILE,
) -> bool:
    """Whether the fused kernel's resident set fits in VMEM.

    The constant-index weight BlockSpec keeps ALL L (dp x dp) matrices
    resident at once — twice, since the pipeline double-buffers every
    blocked operand; past the budget Mosaic fails to lower, so callers must
    fall back to the per-layer XLA path."""
    dp = _pad_to(d, LANE)
    itemsize = jnp.dtype(compute_dtype).itemsize
    weights = num_layers * dp * dp * itemsize
    biases = _pad_to(num_layers, 8) * dp * 4
    tiles = 2 * row_tile * dp * itemsize  # x0 in + out
    temps = row_tile * dp * 12  # x0_f32 + f32 layer temps
    return 2 * (weights + biases + tiles) + temps <= VMEM_BUDGET_BYTES


def _cross_kernel(x0_ref, w_ref, b_ref, out_ref, *, num_layers: int, compute_dtype):
    x0 = x0_ref[:]  # (BN, dp) in compute dtype
    x0_f32 = x0.astype(jnp.float32)

    def layer(l, x):
        xw = jax.lax.dot_general(
            x,
            w_ref[l],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        b = b_ref[l].astype(jnp.float32)
        nxt = x0_f32 * (xw + b) + x.astype(jnp.float32)
        return nxt.astype(compute_dtype)

    out_ref[:] = jax.lax.fori_loop(0, num_layers, layer, x0)


def _pad_to(value: int, multiple: int) -> int:
    return (value + multiple - 1) // multiple * multiple


@functools.partial(
    jax.jit, static_argnames=("compute_dtype", "row_tile", "interpret")
)
def fused_cross_apply(
    x0: jax.Array,  # [n, d]
    w: jax.Array,  # [L, d, d]
    b: jax.Array,  # [L, d]
    *,
    compute_dtype=jnp.bfloat16,
    row_tile: int = DEFAULT_ROW_TILE,
    interpret: bool = False,
) -> jax.Array:
    """Apply the full DCN-v2 cross stack in one fused kernel; returns [n, d]
    in compute_dtype (matching models/dcn.py cross_apply output)."""
    n, d = x0.shape
    num_layers = w.shape[0]
    if not fits_vmem(d, num_layers, compute_dtype, row_tile):
        raise ValueError(
            f"fused cross stack (d={d}, L={num_layers}) exceeds the "
            f"{VMEM_BUDGET_BYTES >> 20} MB VMEM budget; use cross_apply "
            "(models/dcn.py falls back automatically via fits_vmem)"
        )
    dp = _pad_to(d, LANE)
    bn = min(row_tile, _pad_to(n, 8))
    np_ = _pad_to(n, bn)

    cd = jnp.dtype(compute_dtype)
    x0p = jnp.zeros((np_, dp), cd).at[:n, :d].set(x0.astype(cd))
    wp = jnp.zeros((num_layers, dp, dp), cd).at[:, :d, :d].set(w.astype(cd))
    bp = jnp.zeros((num_layers, dp), jnp.float32).at[:, :d].set(b.astype(jnp.float32))

    kernel = functools.partial(
        _cross_kernel, num_layers=num_layers, compute_dtype=cd
    )
    out = pl.pallas_call(
        kernel,
        grid=(np_ // bn,),
        in_specs=[
            pl.BlockSpec((bn, dp), lambda i: (i, 0), memory_space=pltpu.VMEM),
            # Constant index maps: weights/biases DMA'd into VMEM once and
            # stay resident across all row tiles.
            pl.BlockSpec((num_layers, dp, dp), lambda i: (0, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((num_layers, dp), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bn, dp), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((np_, dp), cd),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(x0p, wp, bp)
    return out[:n, :d]


def cross_params_to_stacked(cross_layers: list) -> tuple[jax.Array, jax.Array]:
    """models/dcn.py stores cross params as a list of {'w': [d,d], 'b': [d]};
    stack them for the kernel. Only full-matrix (DCN-v2) layers qualify."""
    if not cross_layers or cross_layers[0]["w"].ndim != 2:
        raise ValueError("fused cross kernel requires DCN-v2 (full-matrix) layers")
    w = jnp.stack([p["w"] for p in cross_layers])
    b = jnp.stack([p["b"] for p in cross_layers])
    return w, b


# ===========================================================================
# Fused SERVING kernel (ISSUE 12): cross + MLP + output head in one kernel
# ===========================================================================
#
# The cross-only kernel above fuses the one stage XLA already runs well. This
# kernel fuses everything AFTER the embedding lookup, so the [n, d] cross/MLP
# activations never round-trip through HBM:
#
#   ids --(XLA gather, models/embeddings.py field_embed)--> x0 [n, d] in HBM
#   x0 tile in VMEM --> L cross layers --> MLP stack --> output head --> sigmoid
#
# The lookup stays XLA's. The first version gathered inside the kernel — the
# whole [bucket, F] id matrix scalar-prefetched into SMEM, one (1, D) DMA per
# (row, field) from the HBM table, started and waited serially — and Mosaic
# refuses that on the v5e ("Not implemented: dynamic store with unaligned
# indices": the (1, 16) store of each gathered row at a dynamic sublane and
# an unaligned lane offset; PR 21, CHANGES.md). x0 therefore crosses HBM
# once, written by XLA's gather and read here as a lane-padded tile.
#
# int8 weights are FIRST-CLASS operands: the quantized variant streams the
# ops/quantize.py per-channel int8 matrices (4x fewer weight bytes than
# f32) and folds the per-output-channel scale into the f32 accumulator —
# the same algebra as models/base.py dense_apply, inside the kernel.
#
# ops/autotune.py enables this kernel per bucket ONLY where it measures
# faster than the XLA path on the live device; on a TPU backend a failure to
# lower stops start-up instead of becoming a table row.

_SERVE_ROW_TILE = 256


def serve_fits_vmem(
    d: int,
    num_layers: int,
    mlp_dims: tuple[int, ...],
    compute_dtype=jnp.bfloat16,
    row_tile: int = _SERVE_ROW_TILE,
    quantized: bool = False,
) -> bool:
    """Whether the fused serving kernel's VMEM-resident set fits: all cross
    + MLP + head weights (int8 when quantized) plus the per-tile
    activations. Blocked operands count twice — the pipeline double-buffers
    them, constant index map or not."""
    dp = _pad_to(d, LANE)
    cd_size = jnp.dtype(compute_dtype).itemsize
    itemsize = 1 if quantized else cd_size
    rows = 2 * 8 * 4  # scale + bias: a (1, n) f32 row occupies 8 sublanes
    weights = num_layers * dp * (dp * itemsize + rows)
    d_in = dp
    for m in mlp_dims:
        mp = _pad_to(m, LANE)
        weights += mp * (d_in * itemsize + rows)
        d_in = mp
    weights += (dp + d_in) * LANE * 4  # output head (f32 col block)
    tiles = row_tile * dp * cd_size + 2 * row_tile * LANE * 4  # x0 in, 2 out
    # f32 x0 + xw/update temps + the compute-dtype carried activation, and
    # one dequantized (dp, dp) weight when the operands are int8.
    temps = row_tile * dp * 16 + (dp * dp * (4 + cd_size) if quantized else 0)
    return 2 * (weights + tiles) + temps <= VMEM_BUDGET_BYTES


def serve_params_supported(params) -> bool:
    """True when a servable's param tree has the dcn_v2 shape the fused
    serving kernel understands: an embedding table, a full-matrix cross
    stack, an MLP list, and a 1-wide output head — in either the float
    {"w"} or the ops/quantize.py {"qw"} form."""
    try:
        emb = params["embedding"]
        cross, mlp, out = params["cross"], params["mlp"], params["out"]
    except (KeyError, TypeError):
        return False

    def dense_ok(p, out_dim=None):
        w = p.get("qw", p.get("w"))
        if w is None or w.ndim != 2:
            return False
        return out_dim is None or w.shape[1] == out_dim

    if getattr(emb, "ndim", 0) != 2 or not cross or not mlp:
        return False
    return (
        all(dense_ok(p) for p in cross)
        and all(dense_ok(p) for p in mlp)
        and dense_ok(out, out_dim=1)
    )


def _pad2(arr, rows: int, cols: int, dtype) -> jnp.ndarray:
    out = jnp.zeros((rows, cols), dtype)
    a = jnp.asarray(arr)
    return out.at[: a.shape[0], : a.shape[1]].set(a.astype(dtype))


def _pad_row(arr, cols: int, fill: float = 0.0) -> jnp.ndarray:
    """A per-channel vector as a (1, cols) f32 row: Mosaic wants 2-D
    operands, and a row broadcasts over the tile's sublanes as is."""
    a = jnp.asarray(arr, jnp.float32)
    return jnp.full((1, cols), fill, jnp.float32).at[0, : a.shape[0]].set(a)


def _prep_dense(p: dict, rows: int, cols: int, cd):
    """(w_padded, scale_row_or_None, b_row) for one dense layer in either
    param form. int8 weights stay int8 (the operand win); scales pad with
    ONES so padded output channels stay exactly zero after the zero-padded
    weights."""
    if "qw" in p:
        w = _pad2(p["qw"], rows, cols, jnp.int8)
        s = _pad_row(p["qscale"], cols, fill=1.0)
    else:
        w = _pad2(p["w"], rows, cols, cd)
        s = None
    return w, s, _pad_row(p["b"], cols)


def _const_spec(shape) -> pl.BlockSpec:
    """Whole-array VMEM block with a constant index map: DMA'd once,
    resident across every row tile."""
    zeros = (0,) * len(shape)
    return pl.BlockSpec(shape, lambda i: zeros, memory_space=pltpu.VMEM)


def build_fused_serve(params, config, *, interpret: bool = False,
                      row_tile: int = _SERVE_ROW_TILE):
    """Build the fused-serving callable for ONE servable's params
    (float or ops/quantize.py-quantized tree).

    Returns apply_fn(params, batch) -> {"prediction_node", "logits"} with
    the model.apply contract the batcher's jitted entries expect. The
    dense weight operands are prepared (padded/cast) HERE, once, and
    closed over — they enter the jaxpr as constants, so per-call tracing
    never re-pads them (ops/autotune.py rebuilds this callable when a
    servable's params object is swapped). The embedding table alone is
    read from the call's `params`: the one big operand stays an executable
    ARGUMENT, not a vocab-sized constant baked into every bucket's
    executable and persistent-cache entry. `batch` must carry host-folded
    int32 feat_ids and feat_wts."""
    from ..models.embeddings import field_embed

    cfg = config
    cd = cfg.cdtype
    F, D = cfg.num_fields, cfg.embed_dim
    d = F * D
    dp = _pad_to(d, LANE)
    L = len(params["cross"])
    mlp_dims = tuple(
        (p.get("qw", p.get("w"))).shape[1] for p in params["mlp"]
    )
    quantized = "qw" in params["cross"][0]
    if not serve_params_supported(params):
        raise ValueError("fused serving kernel requires a dcn_v2 param tree")
    if not serve_fits_vmem(d, L, mlp_dims, cd, row_tile, quantized):
        raise ValueError(
            f"fused serving kernel (d={d}, L={L}, mlp={mlp_dims}) exceeds "
            f"the {VMEM_BUDGET_BYTES >> 20} MB VMEM budget"
        )

    # Cross stack, one (w[, scale], bias) per layer, each a 2-D operand.
    cross_ops = [_prep_dense(p, dp, dp, cd) for p in params["cross"]]
    # MLP stack: per-layer padded operands (dims differ per layer).
    mlp_ops = []
    d_in = dp
    for p, m in zip(params["mlp"], mlp_dims):
        mp = _pad_to(m, LANE)
        mlp_ops.append(_prep_dense(p, d_in, mp, cd))
        d_in = mp
    mp_last = d_in
    # Output head: [dp + mp_last, LANE] f32 column block, col 0 real. The
    # head is one [*, 1] matvec — f32 operands cost nothing material and
    # skip a quantization step whose win would be ~512 bytes.
    out_p = params["out"]
    w_out = out_p.get("qw")
    if w_out is not None:
        w_full = np.asarray(w_out, np.float32) * np.asarray(
            out_p["qscale"], np.float32
        )[None, :]
    else:
        w_full = np.asarray(out_p["w"], np.float32)
    wo = jnp.zeros((dp + mp_last, LANE), jnp.float32)
    wo = wo.at[:d, 0].set(jnp.asarray(w_full[:d, 0]))
    wo = wo.at[dp: dp + mlp_dims[-1], 0].set(jnp.asarray(w_full[d:, 0]))
    bo = jnp.zeros((1, LANE), jnp.float32).at[0, 0].set(
        jnp.asarray(out_p["b"], jnp.float32)[0]
    )
    dense_args = [a for op in cross_ops + mlp_ops for a in op if a is not None]

    def dense(x, refs):
        """x @ w (int8 dequantized to the compute dtype on the way into
        the MXU), per-channel scale folded into the f32 result, + bias."""
        w_ref, s_ref, b_ref = refs
        w = w_ref[:, :]
        if s_ref is not None:
            w = w.astype(jnp.float32)
        y = jax.lax.dot_general(
            x, w.astype(cd), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if s_ref is not None:
            y = y * s_ref[:, :]
        return y + b_ref[:, :]

    def kernel(x0_ref, *refs):
        # Positional layout mirrors in_specs + out_specs: per cross layer
        # then per MLP layer (w[, scale], bias), head (w, b), two out tiles.
        it = iter(refs)
        layers = [
            (next(it), next(it) if s is not None else None, next(it))
            for _, s, _ in cross_ops + mlp_ops
        ]
        wo_ref, bo_ref = next(it), next(it)
        pred_ref, logit_ref = next(it), next(it)

        x0 = x0_ref[:, :]
        x0_f32 = x0.astype(jnp.float32)

        # ---- cross stack (models/dcn.py cross_apply math, quantized-
        # aware). L is small and static: unrolled, every index static.
        x = x0
        for refs_l in layers[:L]:
            x = (x0_f32 * dense(x, refs_l) + x.astype(jnp.float32)).astype(cd)

        # ---- MLP stack over x0 (models/base.py mlp_apply, final relu).
        h = x0
        for refs_l in layers[L:]:
            h = jax.nn.relu(dense(h, refs_l)).astype(cd)

        # ---- output head: logit = [xc | xd] @ w_out + b (col 0 real).
        lo = (
            jax.lax.dot_general(
                x.astype(jnp.float32), wo_ref[:dp, :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            + jax.lax.dot_general(
                h.astype(jnp.float32), wo_ref[dp:, :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            + bo_ref[:, :]
        )
        logit_ref[:, :] = lo
        pred_ref[:, :] = jax.nn.sigmoid(lo)

    def apply_fn(params, batch):
        emb = field_embed(
            params["embedding"], batch["feat_ids"], batch["feat_wts"], cd, D
        )
        n = emb.shape[0]
        bn = min(row_tile, _pad_to(n, 16))
        np_ = _pad_to(n, bn)
        # Zero lane tail [d, dp) and pad rows: the zero-padded weights keep
        # them exactly zero through every layer.
        x0 = jnp.zeros((np_, dp), cd).at[:n, :d].set(emb.reshape(n, d))

        row_spec = lambda cols: pl.BlockSpec(  # noqa: E731
            (bn, cols), lambda i: (i, 0), memory_space=pltpu.VMEM
        )
        pred, logit = pl.pallas_call(
            kernel,
            grid=(np_ // bn,),
            in_specs=[row_spec(dp)]
            + [_const_spec(a.shape) for a in dense_args + [wo, bo]],
            out_specs=[row_spec(LANE), row_spec(LANE)],
            out_shape=[
                jax.ShapeDtypeStruct((np_, LANE), jnp.float32),
                jax.ShapeDtypeStruct((np_, LANE), jnp.float32),
            ],
            compiler_params=_COMPILER_PARAMS,
            interpret=interpret,
        )(x0, *dense_args, wo, bo)
        return {
            "prediction_node": pred[:n, 0],
            "logits": logit[:n, 0],
        }

    return apply_fn
