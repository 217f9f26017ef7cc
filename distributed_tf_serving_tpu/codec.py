"""TensorProto <-> numpy array codec.

Implements both wire encodings of the reference's tensor data plane
(tensor.proto:14-84 in the reference's vendored protos): the raw
little-endian `tensor_content` fast path (zero-copy via np.frombuffer) and
the per-dtype repeated fields (the encoding the reference's Java client emits
— int64_val/float_val, DCNClient.java:98-108). Every real dtype in
types.proto:11-67 is covered, including DT_BFLOAT16 (TPU-native) and DT_HALF
via the int32-widened `half_val` bit-pattern field.

Unlike the external tensorflow_model_server the reference talked to, this
codec *validates* element counts against the declared shape — the reference's
smoke client (DCNClientSimple.java:26-51) declares [1500,43] but sends ~2 rows
and the external server accepted it; here that is an explicit CodecError.
"""

from __future__ import annotations

import numpy as np
import ml_dtypes

from .proto import tf_framework_pb2 as fw

DataType = fw.DataType


class CodecError(ValueError):
    """Raised for malformed, inconsistent, or unsupported TensorProtos."""


# DataType -> (numpy dtype, repeated-field name). Quantized dtypes decode to
# their underlying integer layout; DT_STRING is handled separately (ragged
# bytes, no fixed itemsize).
_DTYPES: dict[int, tuple[np.dtype, str]] = {
    DataType.DT_FLOAT: (np.dtype(np.float32), "float_val"),
    DataType.DT_DOUBLE: (np.dtype(np.float64), "double_val"),
    DataType.DT_INT32: (np.dtype(np.int32), "int_val"),
    DataType.DT_UINT8: (np.dtype(np.uint8), "int_val"),
    DataType.DT_INT16: (np.dtype(np.int16), "int_val"),
    DataType.DT_INT8: (np.dtype(np.int8), "int_val"),
    DataType.DT_COMPLEX64: (np.dtype(np.complex64), "scomplex_val"),
    DataType.DT_INT64: (np.dtype(np.int64), "int64_val"),
    DataType.DT_BOOL: (np.dtype(np.bool_), "bool_val"),
    DataType.DT_QINT8: (np.dtype(np.int8), "int_val"),
    DataType.DT_QUINT8: (np.dtype(np.uint8), "int_val"),
    DataType.DT_QINT32: (np.dtype(np.int32), "int_val"),
    DataType.DT_BFLOAT16: (np.dtype(ml_dtypes.bfloat16), "half_val"),
    DataType.DT_QINT16: (np.dtype(np.int16), "int_val"),
    DataType.DT_QUINT16: (np.dtype(np.uint16), "int_val"),
    DataType.DT_UINT16: (np.dtype(np.uint16), "int_val"),
    DataType.DT_COMPLEX128: (np.dtype(np.complex128), "dcomplex_val"),
    DataType.DT_HALF: (np.dtype(np.float16), "half_val"),
    DataType.DT_UINT32: (np.dtype(np.uint32), "uint32_val"),
    DataType.DT_UINT64: (np.dtype(np.uint64), "uint64_val"),
}

# numpy dtype -> DataType, for encoding. bfloat16 first so it wins the lookup.
_NP_TO_DT: dict[np.dtype, int] = {
    np.dtype(ml_dtypes.bfloat16): DataType.DT_BFLOAT16,
    np.dtype(np.float32): DataType.DT_FLOAT,
    np.dtype(np.float64): DataType.DT_DOUBLE,
    np.dtype(np.float16): DataType.DT_HALF,
    np.dtype(np.int64): DataType.DT_INT64,
    np.dtype(np.int32): DataType.DT_INT32,
    np.dtype(np.int16): DataType.DT_INT16,
    np.dtype(np.int8): DataType.DT_INT8,
    np.dtype(np.uint64): DataType.DT_UINT64,
    np.dtype(np.uint32): DataType.DT_UINT32,
    np.dtype(np.uint16): DataType.DT_UINT16,
    np.dtype(np.uint8): DataType.DT_UINT8,
    np.dtype(np.bool_): DataType.DT_BOOL,
    np.dtype(np.complex64): DataType.DT_COMPLEX64,
    np.dtype(np.complex128): DataType.DT_COMPLEX128,
}


# Little-endian (wire byte order) dtype per DataType, precomputed: dtype
# object construction per call is measurable at 500 QPS, and on LE hosts the
# post-frombuffer astype is a no-op against these.
_DTYPES_LE: dict[int, np.dtype] = {
    dt: np_dtype.newbyteorder("<") for dt, (np_dtype, _f) in _DTYPES.items()
}


def dtype_to_numpy(dt: int) -> np.dtype:
    if dt not in _DTYPES:
        raise CodecError(f"unsupported DataType: {DataType.Name(dt) if dt in DataType.values() else dt}")
    return _DTYPES[dt][0]


def numpy_to_dtype(dtype: np.dtype) -> int:
    dtype = np.dtype(dtype)
    if dtype not in _NP_TO_DT:
        raise CodecError(f"no DataType mapping for numpy dtype {dtype}")
    return _NP_TO_DT[dtype]


def shape_from_proto(shape: fw.TensorShapeProto) -> tuple[int, ...]:
    if shape.unknown_rank:
        raise CodecError("unknown_rank shapes are not servable")
    dims = tuple(d.size for d in shape.dim)
    if any(d < 0 for d in dims):
        raise CodecError(f"negative dimension in shape {dims}")
    return dims


def shape_to_proto(shape: tuple[int, ...]) -> fw.TensorShapeProto:
    return fw.TensorShapeProto(dim=[fw.TensorShapeProto.Dim(size=int(s)) for s in shape])


def _num_elements(dims: tuple[int, ...]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def to_ndarray(tp: fw.TensorProto) -> np.ndarray:
    """Decode a TensorProto to a numpy array, validating shape vs payload."""
    dt = tp.dtype
    dims = shape_from_proto(tp.tensor_shape)
    n = _num_elements(dims)

    if dt == DataType.DT_STRING:
        vals = list(tp.string_val)
        if len(vals) != n:
            raise CodecError(f"DT_STRING: {len(vals)} values for shape {dims} ({n} elements)")
        out = np.empty(n, dtype=object)
        out[:] = vals
        return out.reshape(dims)

    np_dtype, field = _DTYPES.get(dt, (None, None))
    if np_dtype is None:
        raise CodecError(
            f"unsupported DataType: {DataType.Name(dt) if dt in DataType.values() else dt}"
        )

    # Bind ONCE: every upb bytes-field access copies the payload (~9 us per
    # half-MB); the frombuffer view below aliases this specific
    # bytes object, keeping the decode zero-copy end to end.
    content = tp.tensor_content
    if content:
        buf = np.frombuffer(content, dtype=_DTYPES_LE[dt])
        if buf.size != n:
            raise CodecError(
                f"tensor_content holds {buf.size} {np_dtype} elements, shape {dims} needs {n}"
            )
        return buf.astype(np_dtype, copy=False).reshape(dims)

    vals = getattr(tp, field)
    nvals = len(vals)

    if field == "half_val":
        # uint16 bit patterns widened to int32 on the wire.
        if nvals != n:
            raise CodecError(f"half_val holds {nvals} elements, shape {dims} needs {n}")
        bits = np.asarray(vals, dtype=np.int32).astype(np.uint16)
        return bits.view(np_dtype).reshape(dims)

    if field in ("scomplex_val", "dcomplex_val"):
        # Interleaved (real, imag) pairs.
        if nvals != 2 * n:
            raise CodecError(f"{field} holds {nvals} floats, shape {dims} needs {2 * n}")
        real_dtype = np.float32 if field == "scomplex_val" else np.float64
        flat = np.asarray(vals, dtype=real_dtype)
        return flat.view(np_dtype).reshape(dims)

    if nvals == n:
        return np.asarray(vals, dtype=np_dtype).reshape(dims)
    if nvals == 1 and n >= 1:
        # Proto3 scalar-broadcast convention: a single value fills the tensor.
        return np.full(dims, np.asarray(vals[0], dtype=np_dtype), dtype=np_dtype)
    raise CodecError(f"{field} holds {nvals} elements, shape {dims} needs {n}")


# ------------------------------------------------------ int8 score encoding
#
# The host side of the batcher's int8 D2H output wire (ops/transfer.py
# quantize_output_device / restore_outputs_host): the same 254-level affine
# scheme, in pure numpy (this module must stay jax-free).

Q8_WIRE_LEVELS = 254.0


def quantize_scores(arr: np.ndarray) -> tuple[np.ndarray, float, float]:
    """Affine int8 quantization of a float score array on host; returns
    (q int8, scale, min). Worst-case dequant error is range/508."""
    v = np.asarray(arr, np.float32)
    mn = float(v.min()) if v.size else 0.0
    mx = float(v.max()) if v.size else 0.0
    scale = max((mx - mn) / Q8_WIRE_LEVELS, 1e-8)
    q = (np.clip(np.rint((v - mn) / scale), 0.0, Q8_WIRE_LEVELS) - 127.0)
    return q.astype(np.int8), scale, mn


def dequantize_scores(q: np.ndarray, scale: float, mn: float) -> np.ndarray:
    """Inverse of quantize_scores (float32)."""
    return (np.asarray(q, np.float32) + 127.0) * float(scale) + float(mn)


# ---------------------------------------------------- wire integrity (CRC)
#
# ISSUE 20: CRC32C (Castagnoli — the polynomial every storage/RPC stack
# uses for exactly this job) sidecars over tensor bytes, stamped into
# gRPC metadata on both directions so silent wire corruption is DETECTED
# instead of served. Both ends checksum the same canonical form — the
# DECODED ndarray's dtype/shape header + contiguous payload bytes — so
# the check is encoding-independent (tensor_content and repeated fields
# verify identically). Lives here because
# this module is the one tensor-bytes authority both the client package
# (jax-free) and the server share.

try:  # C-speed when the wheel is present; the table fallback keeps the
    # client package dependency-free (same rationale as staying jax-free).
    import google_crc32c as _crc32c_native
except ImportError:  # pragma: no cover - exercised only without the wheel
    _crc32c_native = None

_CRC32C_POLY = 0x82F63B78
_CRC32C_TABLE = []
for _i in range(256):
    _c = _i
    for _ in range(8):
        _c = (_c >> 1) ^ (_CRC32C_POLY if _c & 1 else 0)
    _CRC32C_TABLE.append(_c)
del _i, _c

CRC_INPUT_MD = "x-dts-input-crc"
CRC_SCORE_MD = "x-dts-score-crc"


def crc32c(data, crc: int = 0) -> int:
    """CRC32C over a bytes-like; pass a prior value to chain."""
    if _crc32c_native is not None:
        return _crc32c_native.extend(crc, bytes(data))
    crc ^= 0xFFFFFFFF
    table = _CRC32C_TABLE
    for b in bytes(data):
        crc = (crc >> 8) ^ table[(crc ^ b) & 0xFF]
    return crc ^ 0xFFFFFFFF


def ndarray_crc(arr: np.ndarray) -> int:
    """Canonical tensor checksum: dtype/shape header chained with the
    contiguous payload bytes, so a flipped shape dim is as detectable as
    a flipped payload bit."""
    a = np.ascontiguousarray(arr)
    head = f"{a.dtype.str}:{a.shape}".encode()
    return crc32c(a.tobytes(), crc32c(head))


def crc_sidecar(arrays: dict) -> str:
    """Encode per-tensor checksums as one metadata value:
    ``name=%08x`` pairs joined by commas, name order sorted so the
    sidecar is deterministic regardless of map iteration order."""
    return ",".join(
        f"{name}={ndarray_crc(arrays[name]):08x}" for name in sorted(arrays)
    )


def parse_crc_sidecar(value: str) -> dict[str, int]:
    """Inverse of crc_sidecar. Malformed entries raise CodecError — a
    corrupted SIDECAR must fail the integrity check, not pass it."""
    out: dict[str, int] = {}
    for pair in filter(None, (p.strip() for p in value.split(","))):
        name, sep, hexcrc = pair.rpartition("=")
        if not sep or not name:
            raise CodecError(f"malformed crc sidecar entry {pair!r}")
        try:
            out[name] = int(hexcrc, 16)
        except ValueError as e:
            raise CodecError(f"malformed crc sidecar entry {pair!r}") from e
    return out


def verify_crc_sidecar(arrays: dict, sidecar: str) -> list[str]:
    """Names whose decoded bytes mismatch their stamped checksum.
    Names stamped but absent from `arrays` are reported too (a dropped
    tensor is corruption); names present but unstamped are NOT (the
    sidecar may cover a subset, e.g. score-only response stamping)."""
    stamped = parse_crc_sidecar(sidecar)
    return sorted(
        name for name, want in stamped.items()
        if name not in arrays or ndarray_crc(arrays[name]) != want
    )


class EncodeArena:
    """Preallocated encode scratch (ISSUE 9 transport satellite).

    The response-encode path allocates transient numpy buffers per call —
    the contiguity copy for a strided tensor, the float32 widen for a
    wire-dtype leak, the dense (n, num_fields) batches the Example decoder
    builds — and at streamed-sub-batch rates those allocations churn the
    allocator for bytes whose lifetime is one encode. An arena hands back
    the SAME backing storage each time, grown geometrically and keyed by
    dtype, so steady-state encode performs zero large allocations.

    NOT thread-safe by design: hold one arena per thread (the service
    keeps a threading.local). Scratch returned by ndarray()/contiguous()/
    widen_f32() is valid only until the next call for the same dtype —
    callers must finish consuming (protobuf copies on field assignment;
    the batcher's prepare_inputs copies writable inputs) before reusing.
    Off by default everywhere ([transport] response_arena = false keeps
    the historical allocate-per-call behavior)."""

    def __init__(self):
        self._bufs: dict[str, bytearray] = {}
        self.reuses = 0
        self.grows = 0

    def ndarray(self, shape: tuple, dtype) -> np.ndarray:
        """A writable scratch array of the requested geometry over reused
        backing storage (contents undefined — callers overwrite fully)."""
        dt = np.dtype(dtype)
        nbytes = int(np.prod(shape)) * dt.itemsize
        buf = self._bufs.get(dt.str)
        if buf is None or len(buf) < nbytes:
            # Geometric growth: successive request sizes within 2x reuse
            # one allocation instead of reallocating per high-water mark.
            buf = bytearray(max(nbytes, 2 * len(buf) if buf else 0, 1024))
            self._bufs[dt.str] = buf
            self.grows += 1
        else:
            self.reuses += 1
        return np.frombuffer(buf, dtype=dt, count=int(np.prod(shape))).reshape(shape)

    def contiguous(self, arr: np.ndarray) -> np.ndarray:
        """C-contiguous view of `arr`'s data: the array itself when already
        contiguous, else a copy into arena scratch (what
        np.ascontiguousarray would allocate fresh)."""
        if arr.flags.c_contiguous:
            return arr
        out = self.ndarray(arr.shape, arr.dtype)
        np.copyto(out, arr)
        return out

    def widen_f32(self, arr: np.ndarray) -> np.ndarray:
        """`arr.astype(np.float32)` into arena scratch (the signature-dtype
        widen for half-precision wire leaks)."""
        out = self.ndarray(arr.shape, np.float32)
        np.copyto(out, arr, casting="unsafe")
        return out


def from_ndarray(
    arr: np.ndarray,
    *,
    dtype_enum: int | None = None,
    use_tensor_content: bool = True,
    out: fw.TensorProto | None = None,
    arena: EncodeArena | None = None,
) -> fw.TensorProto:
    """Encode a numpy array as a TensorProto.

    use_tensor_content=True emits the raw-bytes fast path; False emits the
    per-dtype repeated fields (what grpc-java clients typically build).
    dtype_enum overrides the inferred DataType (needed for quantized dtypes,
    which share numpy layouts with plain integers). `out` fills an existing
    (empty) message in place — e.g. a request's map entry — skipping the
    CopyFrom of the encoded bytes (one fewer half-MB copy per request on
    the serving hot path). `arena` (EncodeArena) reuses scratch storage for
    any transient copy this encode needs instead of allocating fresh.
    """
    arr = np.asarray(arr)
    if not arr.flags.c_contiguous:
        # Note: ascontiguousarray would also promote 0-d to 1-d, so only call
        # it when actually needed (0-d arrays are always contiguous).
        arr = (
            arena.contiguous(arr) if arena is not None
            else np.ascontiguousarray(arr)
        )

    if arr.dtype == object or arr.dtype.kind in ("S", "U"):
        tp = out if out is not None else fw.TensorProto()
        tp.dtype = DataType.DT_STRING
        tp.tensor_shape.CopyFrom(shape_to_proto(arr.shape))
        for v in arr.ravel():
            tp.string_val.append(v.encode() if isinstance(v, str) else bytes(v))
        return tp

    dt = dtype_enum if dtype_enum is not None else numpy_to_dtype(arr.dtype)
    np_dtype, field = _DTYPES[dt]
    if np_dtype != arr.dtype:
        raise CodecError(f"array dtype {arr.dtype} does not match {DataType.Name(dt)}")

    tp = out if out is not None else fw.TensorProto()
    tp.dtype = dt
    tp.tensor_shape.CopyFrom(shape_to_proto(arr.shape))
    if use_tensor_content:
        tp.tensor_content = arr.astype(_DTYPES_LE[dt], copy=False).tobytes()
        return tp

    flat = arr.ravel()
    if field == "half_val":
        tp.half_val.extend(flat.view(np.uint16).astype(np.int32).tolist())
    elif field in ("scomplex_val", "dcomplex_val"):
        real_dtype = np.float32 if field == "scomplex_val" else np.float64
        getattr(tp, field).extend(flat.view(real_dtype).tolist())
    else:
        getattr(tp, field).extend(flat.tolist())
    return tp
