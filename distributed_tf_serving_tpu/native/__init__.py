"""ctypes bindings for the native host-ops library, with lazy build.

The shared library builds from hostops.cc on first use (g++ -O3) into
native/build/, under a name that carries a hash of the source's bytes: only
a library built from the hostops.cc on disk is ever loaded, whatever a
copied tree did to file times. DTS_TPU_NO_NATIVE=1 is the explicit opt-out
to the numpy implementations in ops/transfer.py. A build or load that FAILS
raises NativeBuildError from ensure() (the CLI server calls it at start-up)
— it is never a silent change of host path; hot-path callers probe
`available()`, which never blocks or raises. Bindings use ctypes because
pybind11 is not in this image; the C ABI keeps them trivial.
"""

from __future__ import annotations

import ctypes
import functools
import hashlib
import logging
import math
import os
import pathlib
import subprocess
import threading

import numpy as np

log = logging.getLogger("dts_tpu.native")

_DIR = pathlib.Path(__file__).resolve().parent
_SRC = _DIR / "hostops.cc"
_BUILD_DIR = _DIR / "build"

_lib: ctypes.CDLL | None = None
_error: "NativeBuildError | None" = None
_tried = False
_lock = threading.Lock()


class NativeBuildError(RuntimeError):
    """hostops.cc did not compile, or the built library did not load."""


def _so_path() -> pathlib.Path:
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _BUILD_DIR / f"libhostops-{digest}.so"


def _build(so: pathlib.Path) -> None:
    so.parent.mkdir(exist_ok=True)
    # Build to a temp path + atomic rename: a killed/failed compile must
    # never leave a partial .so under the final name.
    tmp = so.with_suffix(f".tmp{os.getpid()}.so")
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-o", str(tmp), str(_SRC)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
    except (OSError, subprocess.SubprocessError) as e:
        tmp.unlink(missing_ok=True)
        detail = getattr(e, "stderr", b"") or b""
        raise NativeBuildError(
            f"native hostops build failed ({e}): "
            f"{detail.decode(errors='replace')[-2000:]}"
        ) from e
    # Libraries of other source revisions are dead weight now. Not the
    # `.tmp<pid>.so` files: another process (a test worker, a second server)
    # may be between its link and its rename of one.
    for old in so.parent.glob("libhostops*.so"):
        if old != so and ".tmp" not in old.name:
            old.unlink(missing_ok=True)


def _load() -> ctypes.CDLL | None:
    global _lib, _error, _tried
    with _lock:
        if not _tried:
            # _tried flips only after the outcome is final, under the lock,
            # so concurrent first callers cannot race the compile or CDLL a
            # half-written file.
            try:
                _lib = _load_locked()
            except NativeBuildError as e:
                _error = e
            _tried = True
    if _error is not None:
        raise _error
    return _lib


def _probe() -> ctypes.CDLL | None:
    """Non-blocking, non-building _load: never compiles (that is exclusively
    warm_async/_load territory — a g++ run on the dispatch thread would stall
    every in-flight request) and never waits on the build lock. Until a
    library for this source exists, hot-path callers fall back to numpy;
    _tried stays unset so they pick the library up once the build lands."""
    global _lib, _tried
    if _tried:
        return _lib
    if not _lock.acquire(blocking=False):
        return None
    try:
        if _tried:
            return _lib
        try:
            lib = _load_locked(build=False)
        except NativeBuildError:
            return None  # _load (ensure / warm_async) reports it
        if lib is not None:
            # Only a successful load is final here; a missing .so may still
            # be produced by an in-flight/future warm_async build.
            _lib = lib
            _tried = True
        return lib
    finally:
        _lock.release()


def _load_locked(build: bool = True) -> ctypes.CDLL | None:
    if os.environ.get("DTS_TPU_NO_NATIVE") == "1":
        return None
    so = _so_path()
    if not so.exists():
        if not build:
            return None
        _build(so)
    try:
        lib = ctypes.CDLL(str(so))
    except OSError as e:
        # A cached .so that will not load is useless; drop it so the next
        # process attempts a fresh build instead of failing forever.
        so.unlink(missing_ok=True)
        raise NativeBuildError(f"native hostops load failed: {e}") from e
    lib.fold_i32.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
    ]
    lib.pack_u24_i32.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p]
    lib.f32_to_bf16.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p]
    for form in _PLANE_FORMS.values():
        getattr(lib, form).argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
        ]
    lib.hash128.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p]
    lib.hash128_rows.argtypes = [
        ctypes.c_char_p, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
    ]
    lib.assemble_batch.argtypes = [
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_void_p,
    ]
    lib.assemble_batch.restype = ctypes.c_int64
    return lib


_warm_kicked = False


def available() -> bool:
    """True once the native library is loaded. Never blocks: while the
    library isn't ready it kicks the build off-thread (once) and returns
    False, so callers use their numpy fallbacks and transparently upgrade
    to the native path when the build lands."""
    global _warm_kicked
    lib = _probe()
    if lib is None and not _tried and not _warm_kicked:
        _warm_kicked = True
        warm_async()
    return lib is not None


def ensure() -> bool:
    """Blocking availability: builds the library if needed (seconds of g++).
    False only under the DTS_TPU_NO_NATIVE=1 opt-out; a failed build or load
    raises NativeBuildError. For start-up, tests and setup paths that need a
    definite answer, never for the serving hot path."""
    return _load() is not None


def _load_logged() -> None:
    try:
        _load()
    except NativeBuildError:
        log.exception("native hostops unavailable; numpy host path in use")


def warm_async() -> None:
    """Kick the (possibly compiling) load off-thread so no request pays the
    first-use g++ latency; callers keep using the numpy fallback until the
    native path is ready."""
    threading.Thread(target=_load_logged, name="native-build", daemon=True).start()


def _ptr(arr: np.ndarray) -> ctypes.c_void_p:
    return ctypes.c_void_p(arr.ctypes.data)


def fold_i32(ids: np.ndarray, vocab: int) -> np.ndarray:
    """int64 ids -> int32 ids mod vocab (one pass)."""
    lib = _load()
    assert lib is not None
    ids = np.ascontiguousarray(ids, dtype=np.int64)
    out = np.empty(ids.shape, np.int32)
    lib.fold_i32(_ptr(ids), ids.size, vocab, _ptr(out))
    return out


def fold_ids(ids: np.ndarray, vocab: int) -> np.ndarray:
    """THE canonical exact host fold (int64 -> int32 mod vocab): native
    one-pass kernel when built, numpy remainder+astype otherwise —
    bit-identical either way. Lives here (jax-free, importable by the
    client) so the server's batcher and the client's compact_payload cannot
    drift on the fold contract."""
    if ids.dtype == np.int64 and available():
        return fold_i32(ids, vocab)
    return np.remainder(ids, np.int64(vocab)).astype(np.int32)


def pack_u24_i32(ids: np.ndarray) -> np.ndarray:
    """Folded int32 ids [..] -> u24 bytes [.., 3] (one pass)."""
    lib = _load()
    assert lib is not None
    ids = np.ascontiguousarray(ids, dtype=np.int32)
    out = np.empty(ids.shape + (3,), np.uint8)
    lib.pack_u24_i32(_ptr(ids), ids.size, _ptr(out))
    return out


# (packed bits, what is read: a 4-byte dtype by name, else the value's bytes)
# -> the exported plane form.
_PLANE_FORMS = {
    (24, "int32"): "pack_planes_u24_i32",
    (16, "float32"): "pack_planes_bf16_f32",
    (16, 2): "pack_planes_raw16",
    (8, 1): "pack_planes_raw8",
}


def pack_planes(arr: np.ndarray, bits: int, out: np.ndarray) -> None:
    """One padded [n, ...] array -> its segment `out` (contiguous uint32) of
    the combined upload, as row planes in whole words (hostops.cc
    pack_planes; the numpy form of the same bytes is ops/transfer.py
    pack_planes_numpy). bits 24: int32 ids to u24; bits 16: float32 to bf16
    (RNE), any 2-byte dtype as it is; bits 8: any 1-byte dtype as it is.
    One pass, GIL released."""
    lib = _load()
    assert lib is not None
    arr = np.ascontiguousarray(arr)
    wide = arr.dtype.itemsize == 4
    form = _PLANE_FORMS.get((bits, arr.dtype.name if wide else arr.dtype.itemsize))
    if form is None:
        raise ValueError(f"pack_planes: {arr.dtype} cannot travel as {bits} bits")
    n = arr.shape[0]
    getattr(lib, form)(_ptr(arr), n, arr.size // max(n, 1), _ptr(out))


def hash128(arr: np.ndarray) -> bytes:
    """16-byte content digest of a contiguous array's bytes (one pass)."""
    lib = _load()
    assert lib is not None
    arr = np.ascontiguousarray(arr)
    out = np.empty(2, np.uint64)
    lib.hash128(_ptr(arr), arr.nbytes, _ptr(out))
    return out.tobytes()


def hash128_rows(blob: np.ndarray, header: bytes = b"") -> np.ndarray:
    """Batched per-row blake2b-128 (ISSUE 15 satellite): a [n, B] uint8
    row matrix -> [n, 16] uint8 digests, row i = blake2b(header +
    blob[i].tobytes(), digest_size=16) — BYTE-IDENTICAL to hashlib's
    blake2b (RFC 7693 in hostops.cc), because these digests are wire
    contracts (row-cache keys, dedup identity, client label-join keys)
    that must not depend on whether the host ops are built. One
    GIL-released call hashes the whole batch."""
    lib = _load()
    assert lib is not None
    blob = np.ascontiguousarray(blob, dtype=np.uint8)
    if blob.ndim != 2:
        raise ValueError(f"hash128_rows wants [n, B] uint8, got {blob.shape}")
    header = bytes(header)
    out = np.empty((blob.shape[0], 16), np.uint8)
    lib.hash128_rows(
        header, len(header), _ptr(blob), blob.shape[0], blob.shape[1],
        _ptr(out),
    )
    return out


def f32_to_bf16(wts: np.ndarray) -> np.ndarray:
    """f32 -> bf16 with round-to-nearest-even (one pass)."""
    import ml_dtypes

    lib = _load()
    assert lib is not None
    wts = np.ascontiguousarray(wts, dtype=np.float32)
    out = np.empty(wts.shape, ml_dtypes.bfloat16)
    lib.f32_to_bf16(_ptr(wts), wts.size, _ptr(out))
    return out


# Planes a word group of the combined upload holds, by the packed width of a
# value in bits (the word format: hostops.cc pack_words, ops/transfer.py).
PLANES = {32: 1, 16: 2, 8: 4, 24: 4}

# How assemble_batch reads one part (hostops.cc's enum).
_RAW32, _FOLD64, _BF16_F32, _RAW16, _RAW8 = range(5)


@functools.lru_cache(maxsize=None)
def part_kinds(bits: int, dtype_str: str, folds: bool) -> dict[np.dtype, int]:
    """The part dtypes assemble_batch can read for one entry of a combined
    layout (packed width, dtype the device unpacks to), each with how it is
    read: the entry's own dtype as it is; int32 ids for a u24 entry; float32
    for a bf16 entry (cast here, RNE); int64 for an int32 entry whose ids
    are folded (`folds`). A part of another dtype keeps the batch on the
    generic pad+pack path. One shared table an entry kind: read, never
    written, by its callers."""
    import ml_dtypes

    target = np.dtype(ml_dtypes.bfloat16 if dtype_str == "bfloat16" else dtype_str)
    if bits == 24:
        kinds = {np.dtype(np.int32): _RAW32}
    else:
        kinds = {target: {32: _RAW32, 16: _RAW16, 8: _RAW8}[bits]}
    if dtype_str == "bfloat16":
        kinds[np.dtype(np.float32)] = _BF16_F32
    if folds and dtype_str == "int32":
        kinds[np.dtype(np.int64)] = _FOLD64
    return kinds


def assemble_batch(
    layout: tuple,
    parts: dict[str, list[np.ndarray]],
    fold: dict[str, int] | None = None,
) -> tuple[np.ndarray, int]:
    """One native pass from a batch's per-request arrays to its combined
    upload buffer, and the nanoseconds the pass itself took by its own clock
    (the GIL released: a caller's clock around this call reads ctypes and the
    wait to take the GIL back on top). See hostops.cc: `layout` is
    ops/transfer.py's combined_layout of the PADDED batch, (bucket, entries); `parts[key]` the
    requests' [n_p, *trailing] arrays of that input, in batch order; `fold`
    names the inputs whose int64 parts are ids to fold, with their vocab.
    The result is bit for bit pack_host_combined over the padded, folded
    batch: uint32 words, zero padding included. A part's dtype must be one
    part_kinds names for its entry; a part that is not C-contiguous is made
    so here."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native hostops library unavailable")
    bucket, entries = layout
    num_inputs = len(entries)
    num_parts = len(parts[entries[0][0]]) if entries else 0
    if not num_parts:
        raise ValueError("assemble_batch needs at least one input and one part")
    ns = np.fromiter(
        (a.shape[0] for a in parts[entries[0][0]]), np.int64, num_parts
    )
    if int(ns.sum()) > bucket:
        raise ValueError(f"{int(ns.sum())} rows exceed bucket {bucket}")
    bits = np.empty(num_inputs, np.int32)
    inner = np.empty(num_inputs, np.int64)
    vocab = np.zeros(num_inputs, np.int64)
    ptrs = np.empty(num_inputs * num_parts, np.uint64)
    kinds = np.empty(num_inputs * num_parts, np.uint8)
    keep = []  # contiguous copies must outlive the call
    words = 0
    # Real raises, not asserts: these are the ONLY guards between caller
    # mistakes and an out-of-bounds access in C (asserts vanish under
    # python -O, turning a shape bug into heap corruption).
    for k, (key, width, trailing, dtype_str) in enumerate(entries):
        key_parts = parts[key]
        if width not in PLANES or len(key_parts) != num_parts:
            raise ValueError(
                f"{key}: {width}-bit entry with {len(key_parts)} parts, "
                f"the batch has {num_parts}"
            )
        folds = bool(fold) and key in fold
        if folds:
            vocab[k] = fold[key]
            if vocab[k] <= 0:
                raise ValueError(f"{key}: vocab {fold[key]} is not positive")
        accepted = part_kinds(width, dtype_str, folds)
        bits[k] = width
        inner[k] = math.prod(trailing)
        for p, a in enumerate(key_parts):
            kind = accepted.get(a.dtype)
            if kind is None:
                raise ValueError(
                    f"{key} part {p}: dtype {a.dtype} cannot travel as "
                    f"{dtype_str}/{width}b"
                )
            if a.shape != (int(ns[p]),) + tuple(trailing):
                raise ValueError(
                    f"{key} part {p}: shape {a.shape} is not "
                    f"{(int(ns[p]),) + tuple(trailing)}"
                )
            if not a.flags.c_contiguous:
                a = np.ascontiguousarray(a)
                keep.append(a)
            ptrs[k * num_parts + p] = a.ctypes.data
            kinds[k * num_parts + p] = kind
        planes = PLANES[width]
        words += -(-bucket // planes) * int(inner[k]) * planes * width // 32
    out = np.empty(words, np.uint32)
    native_ns = lib.assemble_batch(
        num_inputs, _ptr(bits), _ptr(inner), _ptr(vocab), _ptr(ptrs),
        _ptr(kinds), _ptr(ns), num_parts, bucket, _ptr(out),
    )
    return out, native_ns
