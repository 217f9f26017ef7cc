"""Native hostops tests: build, and bit-exact equality with the numpy
reference implementations for every kernel (including negative ids, u24
boundaries, bf16 rounding/NaN)."""

import ml_dtypes
import numpy as np
import pytest

from distributed_tf_serving_tpu import native


@pytest.fixture(scope="module", autouse=True)
def lib_available():
    # ensure() builds if needed: on a fresh checkout the non-blocking
    # available() would report False and silently skip the whole suite.
    if not native.ensure():
        pytest.skip("native hostops unavailable (no compiler?)")


def test_fold_i32_matches_numpy():
    rng = np.random.RandomState(0)
    ids = rng.randint(-(1 << 62), 1 << 62, size=(257, 43), dtype=np.int64)
    vocab = 1 << 20
    want = np.remainder(ids, np.int64(vocab)).astype(np.int32)
    got = native.fold_i32(ids, vocab)
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.int32


def test_fold_i32_pow2_mask_path():
    """Power-of-two vocab takes the mask fast path; must still equal numpy
    remainder, including for negative ids."""
    rng = np.random.RandomState(1)
    ids = rng.randint(-(1 << 60), 1 << 60, size=(64, 43), dtype=np.int64)
    vocab = 1 << 20
    want = np.remainder(ids, np.int64(vocab)).astype(np.int32)
    np.testing.assert_array_equal(native.fold_i32(ids, vocab), want)


def test_fold_ids_canonical_helper(monkeypatch):
    """native.fold_ids is THE shared fold (server batcher + client
    compact_payload): native and numpy fallback must be bit-identical, and
    non-int64 input passes through the numpy path unchanged in value."""
    rng = np.random.RandomState(2)
    ids = rng.randint(-(1 << 61), 1 << 61, size=(97, 7), dtype=np.int64)
    for vocab in (1 << 20, 1009):
        a = native.fold_ids(ids, vocab)
        monkeypatch.setattr(native, "available", lambda: False)
        b = native.fold_ids(ids, vocab)
        monkeypatch.undo()
        np.testing.assert_array_equal(a, b)
        assert a.dtype == np.int32
    already = np.arange(12, dtype=np.int32).reshape(3, 4)
    np.testing.assert_array_equal(native.fold_ids(already, 1 << 20), already)


def test_pack_u24_boundaries():
    ids = np.array([[0, 1, 255, 256, 65535, 65536, (1 << 24) - 1]], np.int32)
    got = native.pack_u24_i32(ids)
    want = ids.view(np.uint8).reshape(1, -1, 4)[..., :3]
    np.testing.assert_array_equal(got, want)


def test_f32_to_bf16_matches_ml_dtypes():
    rng = np.random.RandomState(2)
    vals = np.concatenate(
        [
            rng.randn(10_000).astype(np.float32) * rng.lognormal(0, 8, 10_000).astype(np.float32),
            np.array([0.0, -0.0, 1.0, np.inf, -np.inf, np.nan,
                      np.float32(3.0000001), 65504.0, 1e-40], np.float32),
        ]
    )
    want = vals.astype(ml_dtypes.bfloat16)
    got = native.f32_to_bf16(vals)
    np.testing.assert_array_equal(
        got.view(np.uint16) & 0xFFBF,  # ignore the quiet-bit choice on NaN payloads
        want.view(np.uint16) & 0xFFBF,
    )
    # Non-NaN values must be fully bit-exact.
    finite = ~np.isnan(vals)
    np.testing.assert_array_equal(got[finite].view(np.uint16), want[finite].view(np.uint16))


def test_pack_host_native_equals_numpy_path():
    import os

    from distributed_tf_serving_tpu.ops.transfer import pack_host

    rng = np.random.RandomState(3)
    arrays = {
        "feat_ids": rng.randint(0, 1 << 20, size=(32, 43)).astype(np.int32),
        "feat_wts": rng.rand(32, 43).astype(np.float32),
    }
    spec = {"feat_ids": "u24", "feat_wts": "bf16"}
    native_out = pack_host(arrays, spec)
    os.environ["DTS_TPU_NO_NATIVE"] = "1"
    try:
        # Force the numpy path by resetting the cached load state.
        native._tried, native._lib = True, None
        numpy_out = pack_host(arrays, spec)
    finally:
        del os.environ["DTS_TPU_NO_NATIVE"]
        native._tried = False
    for k in spec:
        np.testing.assert_array_equal(
            np.asarray(native_out[k]).view(np.uint8), np.asarray(numpy_out[k]).view(np.uint8)
        )


@pytest.mark.parametrize("fields", [13, 26, 43, 214])
@pytest.mark.parametrize("n", [1, 6, 7, 512])
@pytest.mark.parametrize("source,bits", [
    ("int32", 24), ("float32", 16), ("uint16", 16), ("uint8", 8),
])
def test_pack_planes_native_equals_numpy_byte_for_byte(source, bits, n, fields):
    """The plane forms of the combined upload (hostops.cc pack_planes): the
    same words as ops/transfer.py's numpy form, whether or not the rows
    fill the last plane."""
    from distributed_tf_serving_tpu.ops.transfer import _segment_words, pack_planes_numpy

    rng = np.random.RandomState(n * 1000 + fields + bits)
    if source == "float32":
        arr = (rng.randn(n, fields) * rng.lognormal(0, 6, (n, fields))).astype(np.float32)
        arr.flat[:5] = [np.inf, -0.0, 65504.0, 1e-40, 3.0000001]
        values = arr.astype(ml_dtypes.bfloat16).view(np.uint16)
    else:
        top = (1 << 24) if bits == 24 else (1 << bits)
        arr = rng.randint(0, top, size=(n, fields)).astype(source)
        arr[0, 0], arr[-1, -1] = 0, top - 1
        values = arr.view(np.uint32) if bits == 24 else arr
    words = _segment_words(n, (fields,), bits)
    got = np.full(words, 0xDEADBEEF, np.uint32)
    native.pack_planes(arr, bits, got)
    want = np.full(words, 0xDEADBEEF, np.uint32)
    pack_planes_numpy(values, bits, want)
    np.testing.assert_array_equal(got, want)


def test_pack_planes_u24_keeps_the_low_three_bytes():
    """Out-of-contract ids (past 2^24, negative) are truncated to their low
    three bytes, as the per-key byte pack does: neighbours in the word are
    not touched."""
    from distributed_tf_serving_tpu.ops.transfer import pack_planes_numpy

    ids = np.array([[-1, 1 << 24, (1 << 31) - 1, -(1 << 31)], [5, 6, 7, 8],
                    [9, 10, 11, 12], [-2, -3, -4, -5]], np.int32)
    got = np.empty(12, np.uint32)
    native.pack_planes(ids, 24, got)
    want = np.empty(12, np.uint32)
    pack_planes_numpy(ids.view(np.uint32), 24, want)
    np.testing.assert_array_equal(got, want)
    assert got[0] == 0xFFFFFF | (5 << 24)  # row 0 low three bytes, row 1's first


def test_pack_host_combined_native_equals_numpy_path(monkeypatch):
    from distributed_tf_serving_tpu.ops.transfer import pack_host_combined

    rng = np.random.RandomState(4)
    arrays = {
        "feat_ids": rng.randint(0, 1 << 24, size=(30, 43)).astype(np.int32),
        "feat_wts": rng.rand(30, 43).astype(np.float32),
        "dense_features": rng.rand(30, 13).astype(np.float32),
        "flags": rng.randint(-128, 128, size=(30,)).astype(np.int8),
        "half": rng.rand(30, 7).astype(np.float16),
    }
    spec = {"feat_ids": "u24", "feat_wts": "bf16"}
    native_out = pack_host_combined(arrays, spec)
    monkeypatch.setattr(native, "available", lambda: False)
    numpy_out = pack_host_combined(arrays, spec)
    assert native_out.dtype == numpy_out.dtype == np.uint32
    np.testing.assert_array_equal(native_out, numpy_out)
    # bf16 from a compact-wire client travels as it is: the same words.
    arrays["feat_wts"] = arrays["feat_wts"].astype(ml_dtypes.bfloat16)
    np.testing.assert_array_equal(pack_host_combined(arrays, spec), numpy_out)
    monkeypatch.undo()
    np.testing.assert_array_equal(pack_host_combined(arrays, spec), numpy_out)


def test_hash128_content_addressing():
    """Equal bytes -> equal digest (any buffer), any flipped bit -> new
    digest; shape/dtype enter the cache key elsewhere, so the digest only
    needs to be a function of the raw bytes."""
    rng = np.random.RandomState(0)
    a = rng.randint(0, 256, size=(64, 43, 3)).astype(np.uint8)
    assert native.hash128(a) == native.hash128(a.copy())
    assert len(native.hash128(a)) == 16
    b = a.copy()
    b[13, 7, 1] ^= 1
    assert native.hash128(b) != native.hash128(a)


def test_hash128_tail_sizes():
    """The 32-byte main loop plus zero-padded tail: every tail length must
    round-trip deterministically and differ from its neighbors."""
    digests = set()
    for n in (0, 1, 7, 8, 15, 31, 32, 33, 63, 64, 100):
        x = np.arange(n, dtype=np.uint8)
        d = native.hash128(x)
        assert d == native.hash128(x.copy())
        digests.add(d)
    assert len(digests) == 11  # all lengths distinct (length is seeded in)


def test_hash128_no_small_collisions():
    rng = np.random.RandomState(7)
    seen = {native.hash128(rng.randint(0, 256, size=40).astype(np.uint8)) for _ in range(2000)}
    assert len(seen) == 2000


# ----------------------------------------------------------- hash128_rows
# (ISSUE 15 satellite): the batched per-row blake2b-128. Unlike hash128
# above (a private fast mix), these digests are a WIRE contract — the
# row-cache keys, dedup identity, and client label-join keys — so the
# native path must be BYTE-IDENTICAL to hashlib.blake2b(digest_size=16).


def test_hash128_rows_byte_identical_to_hashlib():
    import hashlib

    rng = np.random.RandomState(3)
    for n, width, header in (
        (1, 1, b""),
        (5, 43, b""),
        (7, 130, b"feat_ids:<i8:(8,);feat_wts:<f4:(8,);"),
        (2, 127, b"h"),
        (2, 128, b""),
        (3, 129, b"z" * 200),  # header + row spanning several blocks
        (4, 0, b"only-header"),
    ):
        blob = rng.randint(0, 256, size=(n, width)).astype(np.uint8)
        got = native.hash128_rows(blob, header)
        assert got.shape == (n, 16)
        for i in range(n):
            ref = hashlib.blake2b(
                header + blob[i].tobytes(), digest_size=16
            ).digest()
            assert got[i].tobytes() == ref, (n, width, header, i)


def test_hash128_rows_empty_message_and_shapes():
    import hashlib

    empty = np.zeros((1, 0), np.uint8)
    assert (
        native.hash128_rows(empty)[0].tobytes()
        == hashlib.blake2b(b"", digest_size=16).digest()
    )
    assert native.hash128_rows(np.zeros((0, 8), np.uint8)).shape == (0, 16)
    with pytest.raises(ValueError):
        native.hash128_rows(np.zeros(8, np.uint8))  # 1-D refused


def test_digest_rows_native_equals_fallback(monkeypatch):
    """cache/row_cache.py digest_rows — the row-cache key mint — must
    produce the same bytes with the native path armed and with it forced
    off, including the subset-rows form the dedup plan uses."""
    from distributed_tf_serving_tpu.cache.row_cache import digest_rows

    rng = np.random.RandomState(5)
    blob = rng.randint(0, 256, size=(20, 43)).astype(np.uint8)
    header = b"feat_ids:<i8:(8,);"
    for rows in (None, [0, 3, 19], range(5), []):
        with_native = digest_rows(blob, header, rows=rows)
        monkeypatch.setattr(native, "available", lambda: False)
        without = digest_rows(blob, header, rows=rows)
        monkeypatch.undo()
        assert with_native == without
        assert all(len(d) == 16 for d in with_native)


def test_row_label_keys_native_equals_fallback(monkeypatch):
    """The label-join keys clients compute over the bytes they SENT must
    equal the server's — whichever side has the host ops built."""
    from distributed_tf_serving_tpu.cache.digest import row_label_keys

    rng = np.random.RandomState(6)
    arrays = {
        "feat_ids": rng.randint(0, 1 << 40, size=(9, 8)).astype(np.int64),
        "feat_wts": rng.rand(9, 8).astype(np.float32),
    }
    with_native = row_label_keys(arrays)
    monkeypatch.setattr(native, "available", lambda: False)
    without = row_label_keys(arrays)
    monkeypatch.undo()
    assert with_native == without
    assert all(len(k) == 32 for k in with_native)  # 16-byte hex


def test_library_of_other_source_is_not_loaded(tmp_path, monkeypatch):
    """The built file is keyed on the source's bytes, not on file times (a
    copied tree does not preserve them): once hostops.cc differs from what
    the library on disk was built from, that library is not a candidate."""
    built = native._so_path()
    assert built.exists()  # the fixture's ensure() built it
    other = tmp_path / "hostops.cc"
    other.write_bytes(native._SRC.read_bytes() + b"\n// edited\n")
    monkeypatch.setattr(native, "_SRC", other)
    assert native._so_path() != built
    assert native._load_locked(build=False) is None


def test_failed_build_raises(tmp_path, monkeypatch):
    """A compile failure is an error the caller sees (the CLI server calls
    ensure() at start-up), never a quiet switch to the numpy host path."""
    broken = tmp_path / "hostops.cc"
    broken.write_text("this is not C++\n")
    monkeypatch.setattr(native, "_SRC", broken)
    monkeypatch.setattr(native, "_BUILD_DIR", tmp_path / "build")
    with pytest.raises(native.NativeBuildError, match="build failed"):
        native._load_locked()


def test_build_leaves_another_process_its_unrenamed_library(tmp_path, monkeypatch):
    """Several processes may build at once (test workers on a fresh
    checkout, two servers): a finished build clears libraries of other
    source revisions, but not a `.tmp<pid>.so` that another build has
    linked and not yet renamed (that build then failed, one run in three)."""
    build = tmp_path / "build"
    build.mkdir()
    stale = build / "libhostops-0000000000000000.so"
    in_flight = build / "libhostops-1111111111111111.tmp4242.so"
    stale.write_bytes(b"old")
    in_flight.write_bytes(b"being linked")
    monkeypatch.setattr(native, "_BUILD_DIR", build)
    so = native._so_path()
    native._build(so)
    assert so.exists() and in_flight.exists() and not stale.exists()
