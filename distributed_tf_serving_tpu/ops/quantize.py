"""Post-training int8 weight quantization for the serving hot path (ISSUE 12).

ROADMAP item 3 / the Gemma-on-TPU serving recipe: serving-time matmuls are
HBM-bandwidth-bound at CTR batch sizes, so shrinking the weight bytes the
MXU streams per step is a direct speedup — int8 weights are 4x smaller than
f32 (2x smaller than the bf16 compute cast) — and the "300M predictions/s"
paper's fleet argument applies to every byte the serving path moves.

Scheme: **per-channel symmetric weight-only** quantization of the 2-D dense
matrices (DCN cross W_l, MLP layers, the output head):

    scale[o] = max|w[:, o]| / 127        (per OUTPUT channel)
    qw[i, o] = round(w[i, o] / scale[o])   in int8 [-127, 127]

Activations stay in the model's compute dtype (bf16 by default). At apply
time the matmul runs  x_bf16 @ qw.astype(bf16)  (int8 magnitudes <= 127 are
exactly representable in bf16, so the cast is lossless) with float32
accumulation, and the per-channel scale folds into the OUTPUT —
algebraically identical to dequantizing the weights first, but the scale
multiplies an [n, out] tile instead of materializing an [in, out] f32
matrix:

    y[n, o] = (x @ qw)[n, o] * scale[o] + b[o]

Quantization happens ONCE per servable (at load / first autotune), never
per request. The quantized tree uses the key triplet {"qw", "qscale", "b"}
in place of {"w", "b"}; models/base.py dense_apply and models/dcn.py
cross_apply accept either form, so the SAME model.apply serves both — the
batcher's jit cache retraces on the different param-tree structure and the
f32 and int8 executables coexist per bucket (the autotune harness in
ops/autotune.py decides per bucket which one live traffic gets).

Embedding tables are deliberately NOT quantized: the gather is
row-sparse (HBM reads only the looked-up rows) and, on the v5e, bound by
the number of rows, not by their bytes (8-10 ns a lookup of a whole lane
row at 6-8% of the HBM bandwidth, PERF.md PR 25), so int8 tables would
save no time while adding a dequant to the dominant op; what pays there is
the table's layout (models/embeddings.py pack_table). The dense matmuls
are where the bytes-per-step win is.
"""

from __future__ import annotations

import numpy as np

Q8_MAX = 127  # symmetric int8 range [-127, 127]; -128 unused by design


def quantize_channelwise(w, axis: int = -1):
    """Per-channel symmetric int8 quantization of a float matrix.

    Returns (qw int8, scale float32) with scale shaped to broadcast along
    `axis` (the channel axis — the OUTPUT dim for dense weights). Works on
    numpy arrays and jax arrays alike (pure np on host is the load-time
    path); all-zero channels get scale 1.0 so dequant stays exact."""
    w = np.asarray(w, np.float32)
    reduce_axes = tuple(i for i in range(w.ndim) if i != (axis % w.ndim))
    amax = np.max(np.abs(w), axis=reduce_axes, keepdims=True)
    scale = np.where(amax > 0, amax / Q8_MAX, 1.0).astype(np.float32)
    qw = np.clip(np.rint(w / scale), -Q8_MAX, Q8_MAX).astype(np.int8)
    return qw, np.squeeze(scale, axis=reduce_axes).astype(np.float32)


def dequantize_channelwise(qw, scale, axis: int = -1) -> np.ndarray:
    """Inverse of quantize_channelwise (float32)."""
    qw = np.asarray(qw)
    shape = [1] * qw.ndim
    shape[axis % qw.ndim] = qw.shape[axis % qw.ndim]
    return qw.astype(np.float32) * np.asarray(scale, np.float32).reshape(shape)


def is_quantized_dense(p) -> bool:
    """True for the quantized dense-layer dict form {"qw","qscale","b"}."""
    return isinstance(p, dict) and "qw" in p


def _quantize_dense(p: dict) -> dict:
    qw, scale = quantize_channelwise(np.asarray(p["w"], np.float32), axis=-1)
    return {"qw": qw, "qscale": scale, "b": np.asarray(p["b"])}


def quantize_params(params, _top: bool = True):
    """Walk a model param tree and swap every 2-D float dense layer
    {"w": [in,out], "b": [out]} for its int8 weight-only form
    {"qw", "qscale", "b"}. Covers the DCN cross stack (full-matrix v2
    layers), MLP lists, and output heads across the zoo; everything else —
    embedding tables, DCN-v1 rank-1 cross vectors, low-rank cross layers
    ({"v", "w", "b"}: two matrices, one dict), biases — passes through
    unchanged (shared by reference, not copied: quantization never mutates
    the servable's live params)."""
    if isinstance(params, dict):
        w = params.get("w")
        if (
            w is not None
            and "b" in params
            and "v" not in params
            and getattr(w, "ndim", 0) == 2
            and np.issubdtype(np.asarray(w).dtype, np.floating)
        ):
            return _quantize_dense(params)
        return {k: quantize_params(v, _top=False) for k, v in params.items()}
    if isinstance(params, (list, tuple)):
        out = [quantize_params(v, _top=False) for v in params]
        return type(params)(out) if isinstance(params, tuple) else out
    return params


def count_quantized(params) -> int:
    """Number of dense layers in their quantized form (test/telemetry)."""
    if isinstance(params, dict):
        if "qw" in params:
            return 1
        return sum(count_quantized(v) for v in params.values())
    if isinstance(params, (list, tuple)):
        return sum(count_quantized(v) for v in params)
    return 0


def quantized_param_bytes(params) -> tuple[int, int]:
    """(quantized_bytes, f32_equivalent_bytes) over the dense layers —
    the weight-stream shrink the autotune table reports."""
    q = f = 0
    if isinstance(params, dict):
        if "qw" in params:
            n = int(np.prod(params["qw"].shape))
            return n + params["qscale"].nbytes, n * 4
        for v in params.values():
            a, b = quantized_param_bytes(v)
            q, f = q + a, f + b
    elif isinstance(params, (list, tuple)):
        for v in params:
            a, b = quantized_param_bytes(v)
            q, f = q + a, f + b
    return q, f
