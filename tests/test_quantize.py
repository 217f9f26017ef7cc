"""Quantized inference path + fused Pallas serving kernel + per-bucket
autotune harness tests (ISSUE 12):

- per-channel symmetric int8 weight quantization: round-trip error bounds,
  exact per-channel scales, quantized dense/cross apply parity;
- the int8 SCORE wire: on-device D2H quantization with (scale, min)
  sidecars round-tripping through the batcher completer, and the
  response-wire bit path (service encode -> codec client dequant);
- quantized-entry AUC on a genuinely TRAINED model within the 0.005 gate;
- the fused serving kernel (interpret mode): cross + MLP + head parity
  against model.apply, f32 and int8 weight operands;
- the autotune harness: gates, measure-only, persistence + stale-table
  invalidation on version swap, decision routing through live submits,
  disabled-mode inertness (bit-identical serving with the plane off).
"""

import json
import os

import jax
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import (
    ModelConfig,
    Servable,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.ops.autotune import (
    BASELINE,
    XLA_INT8,
    KernelManager,
)
from distributed_tf_serving_tpu.ops.quantize import (
    count_quantized,
    dequantize_channelwise,
    quantize_channelwise,
    quantize_params,
    quantized_param_bytes,
)
from distributed_tf_serving_tpu.serving.batcher import (
    DynamicBatcher,
    fold_ids_host,
)
from distributed_tf_serving_tpu.utils.config import KernelsConfig, load_config

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


# ------------------------------------------------------------- quantization


def test_channelwise_roundtrip_error_bound():
    """Per-channel symmetric quantization: |w - dequant(q)| <= scale/2
    per channel (half a quantization step), and the scale IS the channel's
    max-abs over 127."""
    rng = np.random.RandomState(0)
    w = rng.randn(64, 24).astype(np.float32) * rng.rand(24)[None, :] * 3
    q, scale = quantize_channelwise(w, axis=-1)
    assert q.dtype == np.int8 and scale.shape == (24,)
    np.testing.assert_allclose(
        scale, np.abs(w).max(axis=0) / 127.0, rtol=1e-6
    )
    back = dequantize_channelwise(q, scale, axis=-1)
    assert np.all(np.abs(back - w) <= scale[None, :] / 2 + 1e-9)
    assert np.abs(q).max() <= 127  # -128 never used (symmetric)


def test_zero_channel_is_exact():
    w = np.zeros((8, 4), np.float32)
    w[:, 1] = 0.5
    q, scale = quantize_channelwise(w)
    back = dequantize_channelwise(q, scale)
    np.testing.assert_array_equal(back[:, 0], 0.0)
    np.testing.assert_allclose(back[:, 1], 0.5, atol=0.5 / 254)


def test_quantize_params_walks_dense_layers_only(servable):
    qp = quantize_params(servable.params)
    # cross (2) + mlp (2) + out (1) = 5 dense layers; embedding untouched.
    assert count_quantized(qp) == 5
    assert "qw" not in str(type(qp["embedding"]))
    assert qp["embedding"] is servable.params["embedding"]
    assert qp["cross"][0]["qw"].dtype == np.int8
    qbytes, fbytes = quantized_param_bytes(qp)
    assert 0 < qbytes < fbytes and fbytes / qbytes > 3.5  # ~4x shrink
    # Original tree untouched (shared, not mutated).
    assert "w" in servable.params["cross"][0]


def test_quantized_apply_parity(servable):
    """The SAME model.apply serves the quantized tree; scores stay within
    the per-layer rounding budget of f32."""
    arrays = make_arrays(64, seed=1)
    want = golden(servable, arrays)
    got = golden(servable, arrays, params=quantize_params(servable.params))
    assert np.max(np.abs(got - want)) < 0.01
    assert not np.array_equal(got, want)  # it genuinely quantized


# --------------------------------------------------------- int8 score wire


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


def test_int8_response_wire_codec_bit_path(servable):
    """The network twin: service-level Predict with int8_wire encodes the
    score tensor DT_INT8 + sidecar outputs; the client-side codec helper
    dequantizes within the affine bound; a non-opted request is untouched."""
    from distributed_tf_serving_tpu import codec
    from distributed_tf_serving_tpu.models.registry import ServableRegistry
    from distributed_tf_serving_tpu.proto import serving_apis_pb2 as apis
    from distributed_tf_serving_tpu.proto import tf_framework_pb2 as fw
    from distributed_tf_serving_tpu.serving.service import PredictionServiceImpl

    registry = ServableRegistry()
    registry.load(servable)
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0).start()
    impl = PredictionServiceImpl(registry, batcher)
    try:
        arrays = make_arrays(16, seed=4)
        req = apis.PredictRequest()
        req.model_spec.name = "DCN"
        for k, v in arrays.items():
            codec.from_ndarray(v, out=req.inputs[k])
        req.output_filter.append("prediction_node")

        plain = impl.predict(req)
        assert plain.outputs["prediction_node"].dtype == fw.DataType.DT_FLOAT

        resp = impl.predict(req, int8_wire=True)
        tp = resp.outputs["prediction_node"]
        assert tp.dtype == fw.DataType.DT_INT8
        assert "prediction_node" + codec.Q8_WIRE_SCALE_SUFFIX in resp.outputs
        got = codec.dequantize_response_output(resp.outputs, "prediction_node")
        want = codec.to_ndarray(plain.outputs["prediction_node"])
        assert got.dtype == np.float32
        assert np.max(np.abs(got - want)) <= (want.max() - want.min()) / 254
        # Wire bytes: the int8 tensor_content is 4x smaller than f32.
        assert len(tp.tensor_content) * 4 == len(
            plain.outputs["prediction_node"].tensor_content
        )
        # The helper passes non-quantized outputs through bit-identically.
        np.testing.assert_array_equal(
            codec.dequantize_response_output(plain.outputs, "prediction_node"),
            want,
        )
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


# ----------------------------------------------------- fused serving kernel


@pytest.mark.parametrize("quantized", [False, True])
def test_fused_serve_kernel_parity(servable, quantized):
    """The fused cross+MLP+head kernel (interpret mode) matches
    model.apply over the same params — float and int8 weight operands."""
    from distributed_tf_serving_tpu.ops.cross_kernel import build_fused_serve

    params = quantize_params(servable.params) if quantized else servable.params
    apply_fn = build_fused_serve(params, CFG, interpret=True)
    arrays = make_arrays(13, seed=6)
    batch = {
        "feat_ids": fold_ids_host(arrays["feat_ids"], CFG.vocab_size),
        "feat_wts": arrays["feat_wts"],
    }
    want = np.asarray(
        servable.model.apply(params, batch)["prediction_node"]
    )
    out = apply_fn(params, batch)
    got = np.asarray(out["prediction_node"])
    assert got.shape == (13,)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert np.all(np.isfinite(np.asarray(out["logits"])))


def test_fused_serve_rejects_unsupported_trees():
    from distributed_tf_serving_tpu.ops.cross_kernel import (
        build_fused_serve,
        serve_params_supported,
    )

    model = build_model("dcn", CFG)  # v1 rank-1 cross: not supported
    params = model.init(jax.random.PRNGKey(0))
    assert not serve_params_supported(params)
    with pytest.raises(ValueError, match="dcn_v2"):
        build_fused_serve(params, CFG, interpret=True)


# ------------------------------------------------------------ the autotune


def _manager(tmp_path=None, **over):
    kw = dict(enabled=True, table_file="", measure_iters=2,
              min_speedup=0.01)
    if tmp_path is not None:
        kw["table_file"] = str(tmp_path / "kernel_autotune.json")
    kw.update(over)
    return KernelManager(KernelsConfig(**kw))


def _batcher(**kw):
    kw.setdefault("buckets", (16, 32))
    kw.setdefault("max_wait_us", 0)
    return DynamicBatcher(**kw).start()


def test_autotune_decides_and_routes_live_traffic(servable):
    """min_speedup at the floor forces the int8 decision on CPU; a live
    submit must then serve through the quantized entry (counter moves,
    scores within the quantization budget of the baseline)."""
    batcher = _batcher()
    try:
        batcher.warmup(servable)
        km = _manager()
        batcher.kernels = km
        table = km.autotune(batcher, servable)
        row = table["buckets"]["32"][XLA_INT8]
        assert row["enabled"] and row["max_abs_delta"] <= 0.005
        assert row["auc_gate"] == "skipped"  # no eval data supplied
        assert km.decision(servable, 32) == (True, False)
        arrays = make_arrays(20, seed=7)
        got = batcher.submit(servable, arrays).result(30)["prediction_node"]
        assert km.quantized_batches >= 1
        want = golden(servable, arrays)
        assert np.max(np.abs(got - want)) < 0.01
    finally:
        batcher.stop()


def test_autotune_accuracy_gate_disables(servable):
    """A variant outside the max|dScore| bound must never enable, however
    fast it measured."""
    batcher = _batcher()
    try:
        batcher.warmup(servable)
        km = _manager(max_abs_delta=1e-9)  # nothing quantized passes this
        batcher.kernels = km
        table = km.autotune(batcher, servable)
        for row in table["buckets"].values():
            assert row["decision"] == BASELINE
            assert not row[XLA_INT8]["enabled"]
        assert km.decision(servable, 32) is None
    finally:
        batcher.stop()


def test_autotune_auc_gate(servable):
    """With a labeled eval supplied the AUC gate is evaluated and
    recorded; an impossible margin fails the gate and disables."""
    batcher = _batcher()
    try:
        batcher.warmup(servable)
        rng = np.random.RandomState(8)
        eval_arrays = make_arrays(64, seed=9)
        labels = (rng.rand(64) < 0.5).astype(np.float32)
        km = _manager()
        batcher.kernels = km
        table = km.autotune(batcher, servable, eval_data=(eval_arrays, labels))
        assert table["gates"]["auc_evaluated"]
        assert table["auc"][BASELINE] is not None
        row = table["buckets"]["32"][XLA_INT8]
        assert row["auc_gate"] in ("pass", "fail")
        assert "auc_delta" in row
    finally:
        batcher.stop()


def test_measure_only_enables_nothing(servable):
    batcher = _batcher()
    try:
        batcher.warmup(servable)
        km = _manager(measure_only=True)
        batcher.kernels = km
        table = km.autotune(batcher, servable)
        assert table["measure_only"]
        for row in table["buckets"].values():
            assert row["decision"] == BASELINE
            assert not row[XLA_INT8]["enabled"]
            # The harness still MEASURED (gates evaluated, numbers real).
            assert row[XLA_INT8]["step_us"] > 0
            assert "max_abs_delta" in row[XLA_INT8]
        assert km.decision(servable, 32) is None
    finally:
        batcher.stop()


def test_forced_pallas_variant_on_cpu(servable, monkeypatch):
    """DTS_KERNELS_FORCE_PALLAS=1 lets CPU tests measure the fused kernel
    (interpret mode) through the same harness; its scores must sit within
    the accuracy gate even though timing loses by orders of magnitude."""
    monkeypatch.setenv("DTS_KERNELS_FORCE_PALLAS", "1")
    batcher = _batcher(buckets=(16,))
    try:
        batcher.warmup(servable)
        km = _manager(measure_iters=1, quantize=False)
        batcher.kernels = km
        table = km.autotune(batcher, servable, buckets=(16,))
        assert table["pallas_eligible"]
        row = table["buckets"]["16"]["pallas_f32"]
        assert "error" not in row, row
        assert row["max_abs_delta"] <= 0.005
        # Interpret mode is orders slower: measured, recorded, NOT chosen.
        assert row["speedup"] < 1.0 or row["enabled"] in (True, False)
    finally:
        batcher.stop()


def test_pallas_refused_by_an_accelerator_stops_the_tune(servable, monkeypatch):
    """Off the CPU a Pallas variant that raises is the device's compiler
    refusing the kernel the operator asked for: KernelLoweringError carries
    the message out (server start-up stops on it) instead of the failure
    becoming a disabled row while XLA serves on. Faked here by reporting a
    "tpu" backend: the kernel then lowers for real (interpret off), which
    the CPU backend refuses."""
    from distributed_tf_serving_tpu.ops.autotune import KernelLoweringError

    batcher = _batcher(buckets=(16,))
    try:
        batcher.warmup(servable)
        km = _manager(measure_iters=1, quantize=False)
        batcher.kernels = km
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(KernelLoweringError, match="pallas_f32 did not compile"):
            km.autotune(batcher, servable, buckets=(16,))
    finally:
        batcher.stop()


def test_table_persistence_and_reuse(servable, tmp_path):
    batcher = _batcher()
    try:
        batcher.warmup(servable)
        km = _manager(tmp_path)
        batcher.kernels = km
        km.autotune(batcher, servable)
        path = km.config.table_file
        assert os.path.exists(path)
        data = json.load(open(path))
        assert "DCN:1" in data["entries"]

        # A fresh manager (restart) adopts the table without re-measuring.
        km2 = _manager(tmp_path)
        km2.prepare(batcher, servable)
        assert km2.autotunes == 0 and km2.table_reuses == 1
        assert km2.decision(servable, 32) == (True, False)
    finally:
        batcher.stop()


def test_stale_table_invalidation_on_version_swap(servable, tmp_path):
    """A different VERSION (hot swap) must never adopt v1's table; and
    invalidate_model drops live decisions for the model."""
    batcher = _batcher()
    try:
        batcher.warmup(servable)
        km = _manager(tmp_path)
        batcher.kernels = km
        km.autotune(batcher, servable)
        assert km.decision(servable, 32) is not None

        v2 = Servable(
            name="DCN", version=2, model=servable.model,
            params=servable.model.init(jax.random.PRNGKey(1)),
            signatures=servable.signatures,
        )
        km2 = _manager(tmp_path, autotune=False)  # adopt-only mode
        km2.prepare(batcher, v2)
        assert km2.table_reuses == 0  # v2 has no entry: nothing adopted
        assert km2.decision(v2, 32) is None

        # Watcher hook: a version change drops the model's live decisions.
        km.invalidate_model("DCN")
        assert km.decision(servable, 32) is None
    finally:
        batcher.stop()


def test_gate_fingerprint_mismatch_retunes(servable, tmp_path):
    """A persisted table measured under DIFFERENT gates must not be
    adopted (its enablement decisions embody the old thresholds)."""
    batcher = _batcher()
    try:
        batcher.warmup(servable)
        km = _manager(tmp_path)
        batcher.kernels = km
        km.autotune(batcher, servable)
        km2 = _manager(tmp_path, max_abs_delta=0.004, autotune=False)
        km2.prepare(batcher, servable)
        assert km2.table_reuses == 0
        assert km2.decision(servable, 32) is None
    finally:
        batcher.stop()


def test_disabled_plane_is_bit_identical(servable):
    """[kernels] off = batcher.kernels None: served scores are
    bit-identical to a batcher that never heard of the plane, and the hot
    path reads ONE attribute."""
    plain = _batcher()
    gated = _batcher()
    try:
        arrays = make_arrays(24, seed=11)
        a = plain.submit(servable, arrays).result(30)["prediction_node"]
        assert gated.kernels is None  # the one attribute read
        b = gated.submit(servable, arrays).result(30)["prediction_node"]
        np.testing.assert_array_equal(a, b)
    finally:
        plain.stop()
        gated.stop()


def test_trained_model_quantized_auc_within_gate():
    """The acceptance gate on a model that actually LEARNED: train a
    small dcn_v2 on the synthetic CTR task (dense id catalog — the bench
    CPU finding), then check quantized held-out AUC within 0.005 of f32
    and max|dScore| under the default bound."""
    import optax

    from distributed_tf_serving_tpu.train.data import (
        SyntheticCTRConfig,
        SyntheticCTRStream,
        auc,
    )
    from distributed_tf_serving_tpu.train.trainer import Trainer

    cfg = ModelConfig(
        num_fields=6, vocab_size=4096, embed_dim=8, mlp_dims=(32,),
        num_cross_layers=2, cross_full_matrix=True, compute_dtype="float32",
    )
    model = build_model("dcn_v2", cfg)
    trainer = Trainer(
        model, learning_rate=optax.cosine_decay_schedule(3e-2, 200), seed=0,
        stream_config=SyntheticCTRConfig(
            num_fields=6, id_space=1 << 10, seed=0
        ),
    )
    trainer.fit(200, batch_size=256)
    params = trainer.state.params
    stream = SyntheticCTRStream(SyntheticCTRConfig(
        num_fields=6, id_space=1 << 10, seed=0
    ))
    held = stream.batch(1024, 999_983)
    batch = {
        "feat_ids": fold_ids_host(held["feat_ids"], cfg.vocab_size),
        "feat_wts": held["feat_wts"],
    }
    s_f32 = np.asarray(model.apply(params, batch)["prediction_node"])
    s_q = np.asarray(
        model.apply(quantize_params(params), batch)["prediction_node"]
    )
    auc_f32 = auc(held["labels"], s_f32)
    auc_q = auc(held["labels"], s_q)
    assert auc_f32 > 0.65  # it learned (well clear of coin flip)
    assert abs(auc_f32 - auc_q) <= 0.005
    assert np.max(np.abs(s_f32 - s_q)) <= 0.02


# ------------------------------------------------------------------ config


def test_kernels_config_parsing(tmp_path):
    path = tmp_path / "cfg.toml"
    path.write_text(
        "[kernels]\nenabled = true\npallas = false\nmin_speedup = 1.1\n"
        "max_abs_delta = 0.003\nmeasure_only = true\n"
        "autotune_buckets = [64, 256]\nint8_score_wire = true\n"
    )
    cfg = load_config(path)["kernels"]
    assert cfg.enabled and not cfg.pallas and cfg.measure_only
    assert cfg.min_speedup == 1.1 and cfg.autotune_buckets == (64, 256)
    assert cfg.int8_score_wire


def test_kernels_config_validation():
    with pytest.raises(ValueError, match="min_speedup"):
        KernelsConfig(min_speedup=0)
    with pytest.raises(ValueError, match="measure_iters"):
        KernelsConfig(measure_iters=-1)
    with pytest.raises(ValueError, match="autotune_buckets"):
        KernelsConfig(autotune_buckets=(0,))


def test_kernels_config_build_sets_wire_gate():
    from distributed_tf_serving_tpu.ops import autotune as autotune_mod

    assert KernelsConfig().build() is None
    try:
        km = KernelsConfig(
            enabled=True, table_file="", int8_score_wire=True
        ).build()
        assert km is not None and autotune_mod.wire_active()
    finally:
        autotune_mod.set_wire_active(False)


def test_kernels_snapshot_shape(servable):
    batcher = _batcher()
    try:
        batcher.warmup(servable)
        km = _manager()
        batcher.kernels = km
        km.autotune(batcher, servable)
        snap = km.snapshot()
        assert snap["enabled"] and "DCN:1" in snap["decisions"]
        assert snap["counters"]["autotunes"] == 1
        assert snap["gates"]["max_abs_delta"] == 0.005
    finally:
        batcher.stop()


# ------------------------------------------------ review-finding regressions


def test_pallas_int8_apply_builds_without_deadlock(servable):
    """pallas_apply_for(servable, quantized=True) resolves the quantized
    params BEFORE taking the manager lock (params_for acquires the same
    non-reentrant lock — the original nested acquire deadlocked the
    dispatch thread forever on the first pallas_int8 batch)."""
    import threading

    km = _manager()
    out = {}

    def build():
        out["fn"] = km.pallas_apply_for(servable, True)

    t = threading.Thread(target=build, daemon=True)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive(), "pallas_apply_for deadlocked"
    assert callable(out["fn"])
    # And the built kernel actually serves the quantized params.
    arrays = make_arrays(8, seed=12)
    batch = {
        "feat_ids": fold_ids_host(arrays["feat_ids"], CFG.vocab_size),
        "feat_wts": arrays["feat_wts"],
    }
    # Called as the batcher calls it: with the variant's own param tree
    # (the kernel reads the embedding table from it).
    qparams = km.params_for(servable, True)
    got = np.asarray(out["fn"](qparams, batch)["prediction_node"])
    want = golden(servable, arrays, params=quantize_params(servable.params))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_measure_only_table_is_never_adopted(servable, tmp_path):
    """A table persisted under measure_only (decisions recorded as
    baseline BY DESIGN) must not satisfy a real serving process's
    prepare(): adopting it would skip the harness and serve the baseline
    forever without ever measuring."""
    batcher = _batcher()
    try:
        batcher.warmup(servable)
        km = _manager(tmp_path, measure_only=True)
        batcher.kernels = km
        km.autotune(batcher, servable)
        assert os.path.exists(km.config.table_file)

        km2 = _manager(tmp_path, autotune=False)  # adopt-only real config
        km2.prepare(batcher, servable)
        assert km2.table_reuses == 0  # measure-only table refused
    finally:
        batcher.stop()


def test_disabled_build_disarms_wire_gate():
    """A later stack built WITHOUT the plane must drop a previous armed
    stack's module-level int8 score-wire gate (same-process rebuild —
    the test-suite/embedded pattern)."""
    from distributed_tf_serving_tpu.ops import autotune as autotune_mod

    try:
        KernelsConfig(enabled=True, table_file="", int8_score_wire=True).build()
        assert autotune_mod.wire_active()
        assert KernelsConfig().build() is None
        assert not autotune_mod.wire_active()
    finally:
        autotune_mod.set_wire_active(False)


def test_decisions_are_identity_guarded(servable):
    """A DIFFERENT Servable object with the same (name, version) — a
    same-version reload, possibly retrained in place — must never inherit
    the tuned object's enablement; the original keeps its win."""
    batcher = _batcher()
    try:
        batcher.warmup(servable)
        km = _manager()
        batcher.kernels = km
        km.autotune(batcher, servable)
        assert km.decision(servable, 32) == (True, False)
        clone = Servable(
            name=servable.name, version=servable.version,
            model=servable.model,
            params=servable.model.init(jax.random.PRNGKey(9)),
            signatures=servable.signatures,
        )
        assert km.decision(clone, 32) is None
        assert km.decision(servable, 32) == (True, False)  # win retained
    finally:
        batcher.stop()


def test_persisted_table_refused_on_params_digest_mismatch(servable, tmp_path):
    """Same (name, version, device, gates) but DIFFERENT weights (the
    retrained-in-place / bench-always-v1 case): the persisted table's
    params digest must refuse adoption — its accuracy gates were measured
    against other weights."""
    batcher = _batcher()
    try:
        batcher.warmup(servable)
        km = _manager(tmp_path)
        batcher.kernels = km
        km.autotune(batcher, servable)

        retrained = Servable(
            name=servable.name, version=servable.version,
            model=servable.model,
            params=servable.model.init(jax.random.PRNGKey(10)),
            signatures=servable.signatures,
        )
        km2 = _manager(tmp_path, autotune=False)  # adopt-only
        km2.prepare(batcher, retrained)
        assert km2.table_reuses == 0
        assert km2.decision(retrained, 32) is None
        # The exact same servable DOES adopt.
        km3 = _manager(tmp_path, autotune=False)
        km3.prepare(batcher, servable)
        assert km3.table_reuses == 1
        assert km3.decision(servable, 32) == (True, False)
    finally:
        batcher.stop()


def test_auc_gate_fails_closed_on_eval_error(servable, monkeypatch):
    """Eval data supplied but the variant's AUC evaluation errors: the
    gate must record 'error' and the variant must NOT enable — an
    un-evaluated ranking gate never reads as passed."""
    batcher = _batcher()
    try:
        batcher.warmup(servable)
        km = _manager()
        batcher.kernels = km
        monkeypatch.setattr(
            KernelManager, "_auc_of",
            lambda self, *a, **k: (_ for _ in ()).throw(RuntimeError("boom")),
        )
        eval_arrays = make_arrays(32, seed=13)
        labels = (np.random.RandomState(13).rand(32) < 0.5).astype(np.float32)
        table = km.autotune(batcher, servable, eval_data=(eval_arrays, labels))
        assert table["auc_errors"]
        for row in table["buckets"].values():
            assert row[XLA_INT8]["auc_gate"] == "error"
            assert not row[XLA_INT8]["enabled"]
            assert row["decision"] == BASELINE
    finally:
        batcher.stop()


def test_save_table_merges_on_disk_entries(servable, tmp_path):
    """A process persisting its (model, version) entry must MERGE with
    the on-disk table, not rewrite it: v2's save must not erase v1's
    measured entry (a rollback would re-pay the measurement)."""
    batcher = _batcher()
    try:
        batcher.warmup(servable)
        km = _manager(tmp_path)
        batcher.kernels = km
        km.autotune(batcher, servable)

        v2 = Servable(
            name="DCN", version=2, model=servable.model,
            params=servable.model.init(jax.random.PRNGKey(14)),
            signatures=servable.signatures,
        )
        batcher.warmup(v2)
        km2 = _manager(tmp_path)
        km2.autotune(batcher, v2)
        data = json.load(open(km2.config.table_file))
        assert set(data["entries"]) == {"DCN:1", "DCN:2"}
        assert km2.table_saves == 1
    finally:
        batcher.stop()


def test_autotune_force_skips_adoption(servable, tmp_path):
    """force=True (the bench A/B) must re-measure even when the persisted
    entry digest-matches — fresh per-round numbers, never replayed ones."""
    batcher = _batcher()
    try:
        batcher.warmup(servable)
        km = _manager(tmp_path)
        batcher.kernels = km
        km.autotune(batcher, servable)
        km2 = _manager(tmp_path)
        km2.autotune(batcher, servable, force=True)
        assert km2.table_reuses == 0 and km2.autotunes == 1
    finally:
        batcher.stop()
