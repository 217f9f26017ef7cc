"""Transfer-compression tests: u24 id packing is exact, bf16 weight packing
is bit-identical to the model's own bf16 cast, and the batcher produces the
same scores with compression on and off."""

import re

import jax
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import ModelConfig, Servable, build_model, ctr_signatures
from distributed_tf_serving_tpu.ops.transfer import pack_host, transfer_spec, unpack_device
from distributed_tf_serving_tpu.serving import DynamicBatcher
from distributed_tf_serving_tpu.serving.batcher import fold_ids_host


def test_u24_roundtrip_exact():
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 1 << 24, size=(7, 43)).astype(np.int32)
    spec = {"feat_ids": "u24"}
    packed = pack_host({"feat_ids": ids}, spec)
    assert packed["feat_ids"].shape == (7, 43, 3)
    assert packed["feat_ids"].nbytes == ids.nbytes * 3 // 4
    out = np.asarray(unpack_device({"feat_ids": packed["feat_ids"]}, spec)["feat_ids"])
    np.testing.assert_array_equal(out, ids)


def test_u24_boundary_values():
    ids = np.array([[0, 1, (1 << 24) - 1, 12345678]], np.int32)
    spec = {"feat_ids": "u24"}
    out = np.asarray(unpack_device(pack_host({"feat_ids": ids}, spec), spec)["feat_ids"])
    np.testing.assert_array_equal(out, ids)


def test_spec_follows_model():
    assert transfer_spec(
        build_model("dcn_v2", ModelConfig(vocab_size=1 << 20, compute_dtype="bfloat16"))
    ) == {"feat_ids": "u24", "feat_wts": "bf16"}
    # Big vocab: ids can't shrink; f32 parity mode: weights can't shrink.
    assert (
        transfer_spec(
            build_model("dcn_v2", ModelConfig(vocab_size=1 << 25, compute_dtype="float32"))
        )
        == {}
    )


def test_spec_respects_f32_weight_consumers():
    """wide_deep/deepfm consume raw f32 weights in their sparse-linear term;
    bf16 weight compression would change their scores and must not engage."""
    cfg = ModelConfig(vocab_size=1 << 20, compute_dtype="bfloat16")
    for kind in ("wide_deep", "deepfm"):
        assert transfer_spec(build_model(kind, cfg)) == {"feat_ids": "u24"}, kind
    for kind in ("dcn", "dcn_v2", "two_tower", "dlrm"):
        assert transfer_spec(build_model(kind, cfg))["feat_wts"] == "bf16", kind


@pytest.mark.parametrize("kind", ["dcn_v2", "wide_deep"])
@pytest.mark.parametrize("compute_dtype", ["bfloat16", "float32"])
def test_batcher_scores_identical_with_compression(compute_dtype, kind):
    cfg = ModelConfig(
        num_fields=8, vocab_size=1 << 16, embed_dim=4, mlp_dims=(16,),
        num_cross_layers=1, compute_dtype=compute_dtype,
    )
    model = build_model(kind, cfg)
    sv = Servable(
        name="DCN", version=1, model=model,
        params=model.init(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(cfg.num_fields),
    )
    rng = np.random.RandomState(1)
    arrays = {
        "feat_ids": rng.randint(0, 1 << 40, size=(11, 8)).astype(np.int64),
        "feat_wts": rng.rand(11, 8).astype(np.float32),
    }
    results = {}
    for compress in (True, False):
        b = DynamicBatcher(buckets=(32,), max_wait_us=0, compress_transfer=compress).start()
        try:
            results[compress] = b.submit(sv, dict(arrays)).result(timeout=30)["prediction_node"]
        finally:
            b.stop()
    # bf16 path: the model casts weights to bf16 anyway, so pre-casting on
    # host is bit-identical; f32 path: spec only packs ids, which is exact.
    np.testing.assert_array_equal(results[True], results[False])


# ----------------------------------------------- combined single buffer


@pytest.mark.parametrize("spec", [
    {"feat_ids": "u24", "feat_wts": "bf16"},
    {"feat_ids": "u24"},
    {},
])
def test_combined_roundtrip(spec):
    import ml_dtypes

    from distributed_tf_serving_tpu.ops.transfer import (
        combined_layout,
        combined_supported,
        combined_words,
        pack_host_combined,
        unpack_device_combined,
    )

    rng = np.random.RandomState(1)
    arrays = {
        "feat_ids": rng.randint(0, 1 << 20, size=(6, 5)).astype(np.int32),
        "feat_wts": rng.rand(6, 5).astype(np.float32),
        "dense_features": rng.rand(6, 3).astype(np.float32),
    }
    assert combined_supported(arrays)
    layout = combined_layout(arrays, spec)
    buf = pack_host_combined(arrays, spec)
    assert buf.dtype == np.uint32 and buf.ndim == 1
    assert buf.size == combined_words(layout)
    # 6 rows: two planes of 3 (bf16), four of 2 (u24, in 3 words a position).
    want_words = {"u24": 3 * 2 * 5, "bf16": 3 * 5}
    assert buf.size == sum(
        want_words.get(spec.get(k), 6 * w)
        for k, w in (("dense_features", 3), ("feat_ids", 5), ("feat_wts", 5))
    )
    out = jax.jit(
        lambda b: unpack_device_combined(b, layout), static_argnums=()
    )(buf)
    np.testing.assert_array_equal(np.asarray(out["feat_ids"]), arrays["feat_ids"])
    np.testing.assert_array_equal(
        np.asarray(out["dense_features"]), arrays["dense_features"]
    )
    if spec.get("feat_wts") == "bf16":
        np.testing.assert_array_equal(
            np.asarray(out["feat_wts"]),
            arrays["feat_wts"].astype(ml_dtypes.bfloat16),
        )
    else:
        np.testing.assert_array_equal(np.asarray(out["feat_wts"]), arrays["feat_wts"])


_KINDS = {
    # kind -> (host dtype, spec entry, values drawn from)
    "u24": (np.int32, "u24", (0, 1 << 24)),
    "bf16": (np.float32, "bf16", None),
    "int32": (np.int32, None, (-(1 << 31), (1 << 31) - 1)),
    "float32": (np.float32, None, None),
    "int8": (np.int8, None, (-128, 128)),
}


def _kind_arrays(kind, n, fields, seed=0):
    """One input of `kind`, between two others so that its segment starts
    and ends inside the buffer."""
    dtype, how, span = _KINDS[kind]
    rng = np.random.RandomState(seed + n * 1000 + fields)
    x = (
        rng.randint(*span, size=(n, fields)).astype(dtype) if span
        else (rng.randn(n, fields) * 100).astype(dtype)
    )
    if kind == "u24":
        x[0, 0], x[-1, -1] = 0, (1 << 24) - 1
    arrays = {
        "a_dense": rng.randn(n, 13).astype(np.float32),
        "b_under_test": x,
        "c_ids": rng.randint(0, 1 << 24, size=(n, 3)).astype(np.int32),
    }
    spec = {"c_ids": "u24"}
    if how:
        spec["b_under_test"] = how
    return arrays, spec


@pytest.mark.parametrize("fields", [13, 26, 43, 214])
@pytest.mark.parametrize("n", [1, 6, 7, 512])
@pytest.mark.parametrize("kind", sorted(_KINDS))
def test_combined_roundtrip_bit_exact(kind, n, fields):
    """Every kind the word buffer carries, at row counts that fill the last
    plane and that do not, comes back bit for bit (bf16: the RNE cast)."""
    import ml_dtypes

    from distributed_tf_serving_tpu.ops.transfer import (
        combined_layout,
        combined_words,
        pack_host_combined,
        unpack_device_combined,
    )

    arrays, spec = _kind_arrays(kind, n, fields)
    layout = combined_layout(arrays, spec)
    buf = pack_host_combined(arrays, spec)
    assert buf.dtype == np.uint32 and buf.shape == (combined_words(layout),)
    out = jax.jit(lambda b: unpack_device_combined(b, layout))(buf)
    assert sorted(out) == sorted(arrays)
    for key, sent in arrays.items():
        want = sent.astype(ml_dtypes.bfloat16) if spec.get(key) == "bf16" else sent
        got = np.asarray(out[key])
        assert got.dtype == want.dtype and got.shape == want.shape, key
        np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8), err_msg=key)


def test_combined_layout_names_the_padded_rows():
    """The buffer's length does not give the rows back (7 and 8 rows of
    sub-word values fill the same planes), so the layout carries them; a
    layout made from one request's arrays takes the bucket through `rows`."""
    from distributed_tf_serving_tpu.ops.transfer import (
        combined_layout,
        combined_words,
        describe_layout,
    )

    spec = {"feat_ids": "u24", "feat_wts": "bf16"}
    seven, eight = (
        {"feat_ids": np.zeros((n, 5), np.int32), "feat_wts": np.zeros((n, 5), np.float32)}
        for n in (7, 8)
    )
    assert combined_words(combined_layout(seven, spec)) == combined_words(
        combined_layout(eight, spec)
    )
    assert combined_layout(seven, spec) != combined_layout(eight, spec)
    assert combined_layout(seven, spec, rows=8) == combined_layout(eight, spec)
    assert hash(combined_layout(eight, spec)) is not None  # a jit-variant key
    assert describe_layout(combined_layout(eight, spec)) == (
        "uint32 words, row planes: feat_ids int32/24b x4, feat_wts bfloat16/16b x2"
    )


_SUBWORD = re.compile(r"tensor<([0-9x]*)x(ui8|i8|ui16|i16|bf16|f16|i1)>")


@pytest.mark.parametrize("kinds", [("u24", "bf16", "float32"), ("int32", "bf16"), ("int8", "bf16")])
@pytest.mark.parametrize("n", [512, 8192])
def test_unpack_is_lane_dense_in_the_lowered_text(kinds, n):
    """What the jitted entry's unpack is made of: the argument is uint32, no
    byte tensor appears but under a 1-byte kind, and no tensor narrower than
    32 bits has a minor dimension of 2, 3 or 4 (the parent's [n, F, 3] bytes
    tiled 128 lanes wide: 36% of the device's busy time, PERF.md, PR 26)."""
    from distributed_tf_serving_tpu.ops.transfer import (
        combined_layout,
        combined_words,
        unpack_device_combined,
    )

    arrays, spec = {}, {}
    for i, kind in enumerate(kinds):
        dtype, how, _ = _KINDS[kind]
        arrays[f"x{i}"] = np.zeros((n, 13 if kind == "float32" else 214), dtype)
        if how:
            spec[f"x{i}"] = how
    layout = combined_layout(arrays, spec)
    text = jax.jit(lambda b: unpack_device_combined(b, layout)).lower(
        jax.ShapeDtypeStruct((combined_words(layout),), np.uint32)
    ).as_text()
    assert re.search(rf"@main\(%arg0: tensor<{combined_words(layout)}xui32>", text), text[:400]
    narrow = _SUBWORD.findall(text)
    assert narrow, "the bf16 weights are narrower than a word"
    for dims, dtype in narrow:
        assert dims.split("x")[-1] not in ("2", "3", "4"), (dims, dtype)
        assert dtype not in ("ui8", "i8") or "int8" in kinds, (dims, dtype)
    assert ("i8" in {d for _, d in narrow}) == ("int8" in kinds)


def test_combined_not_supported_for_strings_bool_and_8byte():
    """Excluded classes pin the batcher's per-key fallback: strings cannot
    ride bytes at all, bitcast rejects bool, and 8-byte dtypes cannot be
    reconstructed under x32 canonicalization (round-3 review findings)."""
    from distributed_tf_serving_tpu.ops.transfer import combined_supported

    obj = np.empty(3, object)
    obj[:] = [b"a", b"b", b"c"]
    assert not combined_supported({"s": obj})
    assert not combined_supported({"m": np.ones(3, bool)})
    assert not combined_supported({"i": np.ones(3, np.int64)})
    assert not combined_supported({"d": np.ones(3, np.float64)})
    assert combined_supported({"a": np.ones(3, np.float32), "b": np.ones(3, np.uint8)})


def test_batcher_combined_entry_scores_match_eager():
    """The default (combined-transfer) batcher entry must score identically
    to the eager forward, requests coalesced or not."""
    from distributed_tf_serving_tpu.serving.batcher import fold_ids_host, prepare_inputs

    cfg = ModelConfig(
        num_fields=8, vocab_size=1 << 16, embed_dim=4, mlp_dims=(16,),
        num_cross_layers=1, compute_dtype="bfloat16",
    )
    model = build_model("dcn_v2", cfg)
    servable = Servable(
        name="M", version=1, model=model,
        params=model.init(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(cfg.num_fields),
    )
    batcher = DynamicBatcher(buckets=(16, 64), max_wait_us=0).start()
    try:
        fn, spec, combined = batcher.jit_entry(servable)
        assert combined, "default zoo path should use the combined buffer"
        rng = np.random.RandomState(5)
        arrays = {
            "feat_ids": rng.randint(0, 1 << 40, size=(10, 8)).astype(np.int64),
            "feat_wts": rng.rand(10, 8).astype(np.float32),
        }
        got = batcher.submit(servable, arrays).result(timeout=60)["prediction_node"]
        want = np.asarray(
            model.apply(servable.params, prepare_inputs(model, arrays))["prediction_node"]
        )
        np.testing.assert_array_equal(got, want[:10])
    finally:
        batcher.stop()


# ------------------------------------------------- int8 D2H output wire
# (ops/transfer.py quantize_output_device / restore_outputs_host, and the
# host encoding they share with codec.py)

CFG = ModelConfig(
    num_fields=6, vocab_size=1009, embed_dim=8, mlp_dims=(32, 16),
    num_cross_layers=2, cross_full_matrix=True, compute_dtype="float32",
)


@pytest.fixture(scope="module")
def servable():
    model = build_model("dcn_v2", CFG)
    return Servable(
        name="DCN", version=1, model=model,
        params=model.init(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(CFG.num_fields),
    )


def make_arrays(n, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "feat_ids": rng.randint(0, 1 << 40, size=(n, CFG.num_fields)).astype(np.int64),
        "feat_wts": rng.rand(n, CFG.num_fields).astype(np.float32),
    }


def golden(servable, arrays, params=None):
    batch = {
        "feat_ids": fold_ids_host(arrays["feat_ids"], CFG.vocab_size),
        "feat_wts": arrays["feat_wts"],
    }
    return np.asarray(
        servable.model.apply(params or servable.params, batch)["prediction_node"]
    )


def test_int8_d2h_wire_roundtrip_and_bytes(servable):
    """output_wire_dtype="int8": scores cross D2H as int8 + two 4-byte
    sidecars, the completer dequantizes to f32, and no sidecar key ever
    reaches the caller."""
    batcher = DynamicBatcher(
        buckets=(32,), max_wait_us=0, output_wire_dtype="int8"
    ).start()
    try:
        arrays = make_arrays(32, seed=2)
        res = batcher.submit(
            servable, arrays, output_keys=("prediction_node",)
        ).result(timeout=30)
        assert set(res) == {"prediction_node"}
        got = res["prediction_node"]
        assert got.dtype == np.float32
        want = golden(servable, arrays)
        # Affine over the live range: error <= range/508 (sigmoid: ~2e-3).
        assert np.max(np.abs(got - want)) <= (want.max() - want.min()) / 254
        # 1 byte/score + 8 sidecar bytes vs the 8 B/row f32 baseline.
        assert batcher.stats.bytes_downloaded == 32 * 1 + 8
        assert batcher.stats.bytes_download_full_f32 == 32 * 2 * 4
    finally:
        batcher.stop()


def test_int8_wire_unfiltered_outputs(servable):
    """All-outputs requests (no filter) quantize every f32 output — the
    logits' unbounded range rides its own per-tensor (scale, min)."""
    batcher = DynamicBatcher(
        buckets=(32,), max_wait_us=0, output_wire_dtype="int8"
    ).start()
    try:
        arrays = make_arrays(20, seed=3)
        res = batcher.submit(servable, arrays).result(timeout=30)
        assert set(res) == {"prediction_node", "logits"}
        want = golden(servable, arrays)
        rng = want.max() - want.min()
        assert np.max(np.abs(res["prediction_node"] - want)) <= rng / 254
    finally:
        batcher.stop()


def test_quantize_scores_numpy_roundtrip():
    rng = np.random.RandomState(5)
    from distributed_tf_serving_tpu import codec

    v = rng.rand(257).astype(np.float32)
    q, scale, mn = codec.quantize_scores(v)
    assert q.dtype == np.int8
    back = codec.dequantize_scores(q, scale, mn)
    assert np.max(np.abs(back - v)) <= scale / 2 + 1e-9
    # Constant vector: exact round-trip through the epsilon scale.
    c = np.full(7, 0.25, np.float32)
    q, scale, mn = codec.quantize_scores(c)
    np.testing.assert_allclose(codec.dequantize_scores(q, scale, mn), c, atol=1e-6)
