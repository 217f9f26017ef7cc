"""Host->device transfer compression for the serving hot path.

The wire pays bytes per candidate, and every upload has a fixed submit
cost, so the batcher shrinks what crosses the host<->device boundary,
sends it as ONE buffer a batch, and undoes both on-device inside the jitted
executable (the entry's `unpack` scope).

Two lossless-under-the-model transforms:
- feat_ids: folded ids are < vocab_size; when vocab_size <= 2^24 the int32
  rows travel as 3 bytes each (u24), -25% id bytes.
- feat_wts: when the model's compute dtype is bfloat16 AND the model
  consumes weights only through that cast (Model.wts_in_compute_dtype — true
  for dcn/dcn_v2/two_tower/dlrm via field_embed, false for wide_deep/deepfm
  whose sparse-linear term is f32), the f32 weights are pre-cast on host and
  travel as bf16 (-50% weight bytes) with bit-identical scores.

Together: 344 -> 215 bytes/candidate at 43 fields for the reference
workload (DCNClient.java:98-108 shapes).

What the unpack costs depends on the FORMAT, and is not free (PERF.md
section 6, PRs 26-28): as a byte buffer rebuilt by `reshape((n, F, 3))` it
took 23-36% of the device's busy time in the bulk cells, because a byte
tensor with a 2-, 3- or 4-wide minor dimension tiles with that dimension
padded to 128 lanes. The combined buffer is therefore `uint32` words, with
sub-word values in planes of whole rows (see "combined single buffer"
below). The per-key path (`pack_host` / `unpack_device`: the mesh executor
and the rare servable whose inputs cannot ride the buffer) keeps the plain
`[..., 3]` byte form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from ..models.base import Model
from ..native import PLANES as _PLANES

U24_MAX = 1 << 24


def transfer_spec(model: Model) -> dict[str, str]:
    """Per-input packing spec for a model; keys absent = pass-through."""
    config = model.config
    spec: dict[str, str] = {}
    if config.vocab_size <= U24_MAX and model.folds_ids_on_host:
        # u24 presumes host-folded int32 ids; graph-executor models ship
        # raw int64 ids to the device untouched.
        spec["feat_ids"] = "u24"
    if config.compute_dtype == "bfloat16" and model.wts_in_compute_dtype:
        spec["feat_wts"] = "bf16"
    return spec


def pack_host(arrays: dict[str, np.ndarray], spec: dict[str, str]) -> dict[str, np.ndarray]:
    """Apply the spec on host numpy arrays (post-fold, post-pad).

    Each transform runs through the native one-pass kernels
    (native/hostops.cc) when built, with bit-identical numpy fallbacks.
    """
    from .. import native

    use_native = bool(spec) and native.available()
    out = {}
    for key, arr in arrays.items():
        how = spec.get(key)
        if how == "u24":
            if arr.dtype != np.int32:
                raise ValueError(f"u24 packing expects folded int32 ids, got {arr.dtype}")
            if use_native:
                out[key] = native.pack_u24_i32(arr)
            else:
                b = np.ascontiguousarray(arr).view(np.uint8).reshape(*arr.shape, 4)
                out[key] = np.ascontiguousarray(b[..., :3])  # LE low 3 bytes
        elif how == "bf16":
            if arr.dtype == ml_dtypes.bfloat16:
                out[key] = arr  # compact-wire client already cast (RNE)
            elif use_native:
                out[key] = native.f32_to_bf16(arr)
            else:
                out[key] = arr.astype(ml_dtypes.bfloat16)
        else:
            out[key] = arr
    return out


@jax.named_scope("unpack")
def unpack_device(packed: dict[str, jnp.ndarray], spec: dict[str, str]) -> dict[str, jnp.ndarray]:
    """Inverse of pack_host, traced inside the jitted executable."""
    out = {}
    for key, arr in packed.items():
        how = spec.get(key)
        if how == "u24":
            b = arr.astype(jnp.int32)
            out[key] = b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16)
        else:
            out[key] = arr  # bf16 weights feed the model directly
    return out


# ------------------------------------------------- combined single buffer
#
# Beyond shrinking bytes, the number of host->device TRANSFERS matters:
# each transfer has a fixed submit cost. The combined path packs every input
# into ONE uint32 buffer — one upload per batch — and splits it back inside
# the jitted executable. Each input's segment is whole words. A value of
# `bits` < 32 bits travels in PLANES of whole rows: plane p holds rows
# [p * q, (p + 1) * q) of the padded [n, ...] array, q = ceil(n / planes)
# (rows past n are zero), and the planes' values at one position are
# concatenated, little-endian, into whole words:
#    32 bits: 1 plane, 1 word (the word is the value)
#    16 bits: 2 planes, 1 word   lo | hi << 16
#     8 bits: 4 planes, 1 word   v0 | v1 << 8 | v2 << 16 | v3 << 24
#    24 bits: 4 planes, 3 words  v0 | v1 << 24, v1 >> 8 | v2 << 16,
#                                v2 >> 16 | v3 << 8
# so the device rebuilds every plane with element-wise shifts and masks on a
# uint32 array of the plane's own shape and joins the planes along the row
# axis: no tensor narrower than 32 bits with a minor dimension of 2, 3 or 4
# (which would tile 128 lanes wide) exists in the unpack. The bytes a row
# over the link are the packed widths' (3 an id, 2 a weight), plus the zero
# rows of the last plane where n is no multiple of the plane count.

# --------------------------------------------------- output compaction
#
# The inverse problem of the input spec above: the serving path must never
# ship full fp32 output tensors synchronously back to the host (the
# "300M predictions/s" paper attributes its serving wins to exactly this).
# Scores are downcast to a wire dtype ON-DEVICE (traced into the jitted
# entry, so the D2H transfer carries the small bytes) and widened back to
# float32 on the host by the batch completer before anything user-visible
# sees them; retrieval-style servables can go further and return only the
# top-k (score, index) pairs.

_WIRE_DTYPES = {"float32": None, "bfloat16": "bf16", "float16": "f16",
                "int8": "q8"}

# int8 score wire (ISSUE 12): f32 outputs cross the D2H link as affine-
# quantized int8 — 4x fewer bytes than f32, 2x fewer than the bf16
# compaction — with the per-tensor (scale, min) pair riding along as two
# 4-byte sidecar outputs the completer consumes (and strips) when it
# dequantizes back to f32. 254 levels over the tensor's live range keeps
# the worst-case error at range/508 (~0.002 for sigmoid CTR scores).
Q8_LEVELS = 254.0
Q8_SCALE_SUFFIX = "::q8scale"
Q8_MIN_SUFFIX = "::q8min"


def is_wire_sidecar(key: str) -> bool:
    """True for the scale/min sidecar keys the int8 wire mints — they must
    ride the D2H fetch even when an output filter narrowed the batch (the
    quantized score is undecodable without them), and they are stripped by
    restore_outputs_host before anything user-visible sees the dict."""
    return key.endswith(Q8_SCALE_SUFFIX) or key.endswith(Q8_MIN_SUFFIX)


def output_wire_dtype(name: str) -> np.dtype | None:
    """Validated numpy dtype for an output wire-dtype knob; None means
    float32 (no downcast — the full-precision fallback path)."""
    if name not in _WIRE_DTYPES:
        raise ValueError(
            f"unknown output wire dtype {name!r}; have {sorted(_WIRE_DTYPES)}"
        )
    if name == "float32":
        return None
    if name == "int8":
        return np.dtype(np.int8)
    return np.dtype(ml_dtypes.bfloat16 if name == "bfloat16" else np.float16)


def quantize_output_device(v: jnp.ndarray):
    """Traced affine int8 quantization of one f32 output tensor: returns
    (q int8, scale [1] f32, min [1] f32). Dynamic per-tensor range so
    logits (unbounded) quantize as well as sigmoid scores; a constant
    tensor gets the epsilon scale and round-trips exactly."""
    v32 = v.astype(jnp.float32)
    mn = jnp.min(v32)
    scale = jnp.maximum((jnp.max(v32) - mn) / Q8_LEVELS, 1e-8)
    q = jnp.clip(jnp.round((v32 - mn) / scale), 0.0, Q8_LEVELS) - 127.0
    return q.astype(jnp.int8), scale.reshape(1), mn.reshape(1)


@jax.named_scope("wire")
def compact_outputs_device(
    outputs: dict[str, jnp.ndarray], wire_dt
) -> dict[str, jnp.ndarray]:
    """Traced into the jitted entry: downcast float32 outputs to the wire
    dtype on-device so only the compact bytes cross the D2H boundary.
    Non-f32 outputs (int tensors, an imported graph's f64) pass through —
    the transform must stay losslessly invertible by restore_outputs_host.
    The int8 wire additionally emits the per-tensor (scale, min) sidecar
    pair restore_outputs_host dequantizes with (and strips)."""
    if wire_dt is None:
        return dict(outputs)
    if wire_dt == np.dtype(np.int8):
        out: dict[str, jnp.ndarray] = {}
        for k, v in outputs.items():
            if v.dtype == jnp.float32:
                q, scale, mn = quantize_output_device(v)
                out[k] = q
                out[k + Q8_SCALE_SUFFIX] = scale
                out[k + Q8_MIN_SUFFIX] = mn
            else:
                out[k] = v
        return out
    return {
        k: v.astype(wire_dt) if v.dtype == jnp.float32 else v
        for k, v in outputs.items()
    }


def restore_outputs_host(host: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    """Completer-side inverse of compact_outputs_device: widen wire-dtype
    arrays back to float32 (dequantizing int8 entries via their sidecars,
    which are consumed here and never reach response assembly) so every
    downstream consumer (codec encode, Classify/Regress, request slicing)
    sees the signature dtype."""
    # Lazy: codec pulls the vendored proto bindings, and this module must
    # stay importable in the TF-export process (interop/export.py), which
    # forbids them at import time (descriptor-pool collision).
    from ..codec import dequantize_scores as _dequantize_scores

    out = {}
    for k, v in host.items():
        if is_wire_sidecar(k):
            continue
        if v.dtype == ml_dtypes.bfloat16 or v.dtype == np.float16:
            v = v.astype(np.float32)
        elif v.dtype == np.int8:
            scale = host.get(k + Q8_SCALE_SUFFIX)
            mn = host.get(k + Q8_MIN_SUFFIX)
            if scale is not None and mn is not None:
                # Genuine int8 model outputs carry no sidecars and pass
                # through untouched — only the wire's own quantization
                # (which minted the pair) is undone. ONE dequant
                # implementation (codec.dequantize_scores) serves both
                # the D2H and the response wires, so they cannot drift.
                v = _dequantize_scores(v, float(scale[0]), float(mn[0]))
        out[k] = v
    return out


def topk_compact_device(scores: jnp.ndarray, n_valid, k: int, wire_dt) -> dict:
    """Top-k output compaction, traced into the jitted entry: only the k
    best (score, index) pairs of the first `n_valid` rows cross the wire
    (padding rows are masked to -inf so they can never outrank a real
    candidate). `n_valid` is a traced scalar — one executable per
    (bucket, k), not per request size."""
    import jax

    mask = jnp.arange(scores.shape[0]) < n_valid
    masked = jnp.where(mask, scores.astype(jnp.float32), -jnp.inf)
    vals, idx = jax.lax.top_k(masked, k)
    if wire_dt is not None:
        if wire_dt == np.dtype(np.int8):
            # The top-k wire is already k pairs — int8 would save a
            # handful of bytes while complicating the host scatter with
            # sidecars; bf16 keeps the compaction without the machinery.
            wire_dt = np.dtype(ml_dtypes.bfloat16)
        vals = vals.astype(wire_dt)
    return {"topk_scores": vals, "topk_indices": idx.astype(jnp.int32)}


def cascade_prune_device(scores: jnp.ndarray, n_valid, k: int, wire_dt) -> dict:
    """Stage-1 prune for the multi-stage cascade, traced into the jitted
    entry: the k best (score, index) survivor pairs PLUS the full stage-1
    score vector cross the wire — the vector because cascade responses
    fill non-survivor positions from stage-1 scores, so it must come back
    anyway, and shipping it at wire dtype alongside the pairs is one
    readback instead of a second submit. Padding rows are masked to -inf
    for the selection exactly like topk_compact_device (they can never
    survive); the returned vector is unmasked because the completer
    slices it to the request's n rows before anything user-visible sees
    it. `n_valid` is a traced scalar — one executable per (bucket, k)."""
    import jax

    mask = jnp.arange(scores.shape[0]) < n_valid
    masked = jnp.where(mask, scores.astype(jnp.float32), -jnp.inf)
    vals, idx = jax.lax.top_k(masked, k)
    full = scores.astype(jnp.float32)
    if wire_dt is not None:
        if wire_dt == np.dtype(np.int8):
            # Same call as the top-k wire: int8 would drag quantization
            # sidecars through the survivor scatter for a handful of
            # bytes; bf16 keeps the compaction without the machinery.
            wire_dt = np.dtype(ml_dtypes.bfloat16)
        vals = vals.astype(wire_dt)
        full = full.astype(wire_dt)
    return {
        "survivor_scores": vals,
        "survivor_indices": idx.astype(jnp.int32),
        "stage1_scores": full,
    }


def topk_restore_host(vals, idx, n: int, score_key: str) -> dict[str, np.ndarray]:
    """Host-side inverse of topk_compact_device: scatter the k pairs back
    into a full-length float32 vector with 0.0 off the head. Sigmoid CTR
    scores are strictly positive, so ranking consumers (the reference
    client sorts and takes the head) see the exact same top-k order; the
    tail is explicitly "not ranked", not an approximation."""
    scores = np.zeros(n, np.float32)
    scores[np.asarray(idx)] = np.asarray(vals).astype(np.float32)
    return {score_key: scores}


def combined_supported(arrays: dict[str, np.ndarray]) -> bool:
    """True when every array can be reconstructed by the device-side
    bitcast: fixed-width numerics up to 4 bytes. ml_dtypes.bfloat16 is
    explicitly included — its numpy dtype.kind is 'V' (void), not 'f', so
    a kind test alone rejects exactly the compact-wire weights this path
    exists to carry (round-4 review finding: the first compact request
    permanently demoted the servable to the per-key path). Excluded (these
    pin the per-key fallback in the batcher): bool (bitcast_convert_type
    rejects it), 8-byte dtypes (x32 canonicalization makes the
    8-trailing-bytes bitcast unsatisfiable — the per-key path's device_put
    downcast is the documented behavior for those), strings/objects."""
    return all(
        (a.dtype.kind in "iuf" and a.dtype.itemsize in (1, 2, 4))
        or a.dtype == ml_dtypes.bfloat16
        for a in arrays.values()
    )




def _plane_shifts(bits: int):
    """(plane, word, shift) for every word a plane's value lies in: the
    value is word >> shift where shift >= 0, else word << -shift."""
    planes = _PLANES[bits]
    return [
        (p, g, p * bits - 32 * g)
        for p in range(planes)
        for g in range(planes * bits // 32)
        if -bits < p * bits - 32 * g < 32
    ]


def _segment_words(n: int, trailing: tuple, bits: int) -> int:
    planes = _PLANES[bits]
    inner = int(np.prod(trailing)) if trailing else 1
    return -(-n // planes) * inner * planes * bits // 32


def combined_layout(
    arrays: dict[str, np.ndarray], spec: dict[str, str], rows: int | None = None
) -> tuple:
    """Pure-metadata layout for the combined buffer: (n, entries), n the
    padded batch's rows (`rows` where `arrays` are one request's, not the
    batch's) and entries a key-sorted tuple of (key, bits, trailing_shape,
    dtype_str) per input: the packed width of one value and the dtype it
    unpacks to. Hashable and static under jit (the entry closes over it),
    and computable WITHOUT packing — the content cache derives its key from
    the raw arrays plus this layout, so a hit skips the pack entirely."""
    entries = []
    for key in sorted(arrays):
        arr = arrays[key]
        kind = spec.get(key, "raw")
        trailing = tuple(int(t) for t in arr.shape[1:])
        if kind == "u24":
            entries.append((key, 24, trailing, "int32"))
        elif kind == "bf16":
            entries.append((key, 16, trailing, "bfloat16"))
        else:
            entries.append((key, arr.dtype.itemsize * 8, trailing, arr.dtype.name))
    n = next(iter(arrays.values())).shape[0] if rows is None else rows
    return (int(n), tuple(entries))


def combined_words(layout: tuple) -> int:
    """Length of the layout's buffer, in 32-bit words."""
    n, entries = layout
    return sum(_segment_words(n, e[2], e[1]) for e in entries)


def describe_layout(layout: tuple) -> str:
    """The format as /monitoring's `startup.upload_format` names it."""
    return "uint32 words, row planes: " + ", ".join(
        f"{key} {dtype_str}/{bits}b x{_PLANES[bits]}"
        for key, bits, _trailing, dtype_str in layout[1]
    )


def pack_planes_numpy(arr: np.ndarray, bits: int, out: np.ndarray) -> None:
    """The numpy form of native.pack_planes, byte for byte: the low `bits`
    bits of every value of the padded [n, ...] array `arr`, as row planes in
    whole words."""
    planes = _PLANES[bits]
    n = arr.shape[0]
    q = -(-n // planes)
    v = np.zeros((planes * q, arr.size // max(n, 1)), np.uint32)
    v[:n] = arr.view(f"u{arr.dtype.itemsize}").reshape(n, -1) & ((1 << bits) - 1)
    v = v.reshape(planes, -1)
    words = out.reshape(planes * bits // 32, -1)
    words[:] = 0
    for p, g, shift in _plane_shifts(bits):
        words[g] |= v[p] << np.uint32(shift) if shift >= 0 else v[p] >> np.uint32(-shift)


def pack_host_combined(
    arrays: dict[str, np.ndarray], spec: dict[str, str]
) -> np.ndarray:
    """Spec-pack every input straight into its segment of ONE uint32 buffer
    (same sorted key order as combined_layout): a native pass an input
    (native/hostops.cc pack_planes), numpy with the same bytes otherwise."""
    from .. import native

    layout = combined_layout(arrays, spec)
    n, entries = layout
    out = np.empty(combined_words(layout), np.uint32)
    use_native = native.available()
    off = 0
    for key, bits, trailing, _dtype_str in entries:
        arr = np.ascontiguousarray(arrays[key])
        seg = out[off:off + _segment_words(n, trailing, bits)]
        off += seg.size
        kind = spec.get(key)
        if kind == "u24" and arr.dtype != np.int32:
            raise ValueError(f"u24 packing expects folded int32 ids, got {arr.dtype}")
        if kind == "bf16" and arr.dtype != ml_dtypes.bfloat16:
            # float32 weights: the native pass casts them (RNE) as it packs.
            # (bf16 already: a compact-wire client cast it.)
            arr = arr.astype(np.float32, copy=False)
            if not use_native:
                arr = arr.astype(ml_dtypes.bfloat16)
        if bits == 32:
            seg[:] = arr.reshape(-1).view(np.uint32)
        elif use_native:
            native.pack_planes(arr, bits, seg)
        else:
            pack_planes_numpy(arr, bits, seg)
    return out


@jax.named_scope("unpack")
def unpack_device_combined(buf: jnp.ndarray, layout: tuple) -> dict[str, jnp.ndarray]:
    """Inverse of pack_host_combined, traced inside the jitted executable:
    static slices of the word buffer, each reshaped to its planes' own
    [words, rows, ...] shape, shifted and masked element-wise, joined along
    the row axis and bitcast (or narrowed, then bitcast) to the input's
    dtype."""
    from jax import lax

    n, entries = layout
    out = {}
    off = 0
    for key, bits, trailing, dtype_str in entries:
        planes = _PLANES[bits]
        nw = _segment_words(n, trailing, bits)
        w = buf[off:off + nw].reshape((planes * bits // 32, -(-n // planes), *trailing))
        off += nw
        vals = [None] * planes
        for p, g, shift in _plane_shifts(bits):
            part = w[g] if shift == 0 else w[g] >> shift if shift > 0 else w[g] << -shift
            vals[p] = part if vals[p] is None else vals[p] | part
        v = vals[0] if planes == 1 else jnp.concatenate(vals, axis=0)[:n]
        if bits < 32:
            v = v & jnp.uint32((1 << bits) - 1)
        if bits in (8, 16):
            v = v.astype(f"uint{bits}")
        out[key] = lax.bitcast_convert_type(v, jnp.dtype(dtype_str))
    return out
