"""Model-quality observability plane (serving/quality.py, ISSUE 7):
sketch windowing/merge under a fake clock, PSI/JS on known shifted
distributions, the label join (in-order, late, orphaned), reservoir AUC
vs the exact train/data.py::auc, version-pair drift through a REAL
VersionWatcher swap, warmup/cache-serve exclusion, drift-linked exemplar
force-keep into the tail sampler, reference save/load, disabled-mode
inertness, [quality] parsing + the build_stack master switch, and the
/qualityz + /labelz + /monitoring?section= surfaces."""

import asyncio
import dataclasses
import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")
aiohttp = pytest.importorskip("aiohttp")

from distributed_tf_serving_tpu.cache.digest import row_label_keys
from distributed_tf_serving_tpu.models import (
    ModelConfig,
    Servable,
    ServableRegistry,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.serving import DynamicBatcher, PredictionServiceImpl
from distributed_tf_serving_tpu.serving.quality import (
    QualityMonitor,
    ScoreSketch,
    calibration_report,
    histogram_percentile,
    js_divergence,
    psi,
)
from distributed_tf_serving_tpu.serving.rest import start_rest_gateway
from distributed_tf_serving_tpu.train.data import auc as exact_auc
from distributed_tf_serving_tpu.utils import tracing
from distributed_tf_serving_tpu.utils.config import QualityConfig

F = 6
VOCAB = 1 << 10
CFG = ModelConfig(
    name="DCN", num_fields=F, vocab_size=VOCAB, embed_dim=4,
    mlp_dims=(8,), num_cross_layers=1, compute_dtype="float32",
)


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture(scope="module")
def servable():
    model = build_model("dcn", CFG)
    return Servable(
        name="DCN", version=1, model=model,
        params=model.init(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(F),
    )


def make_arrays(n, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "feat_ids": rng.randint(0, 1 << 40, size=(n, F)).astype(np.int64),
        "feat_wts": rng.rand(n, F).astype(np.float32),
    }


def make_monitor(clock=None, **kw):
    kw.setdefault("window_s", 60.0)
    kw.setdefault("slices", 6)
    kw.setdefault("drift_check_interval_s", 0.0)
    kw.setdefault("min_drift_count", 10)
    if clock is not None:
        kw["clock"] = clock
    return QualityMonitor(**kw)


# ----------------------------------------------------------------- sketch


def test_sketch_windowing_fake_clock():
    clock = FakeClock()
    sk = ScoreSketch(bins=10, window_s=60.0, slices=6, clock=clock)
    sk.observe(np.full(100, 0.15))
    clock.advance(120.0)  # both the 0.15 slices age out of the window
    sk.observe(np.full(50, 0.85))
    lifetime = sk.lifetime_counts()
    window = sk.window_counts()
    assert lifetime[1] == 100 and lifetime[8] == 50
    assert window[1] == 0 and window[8] == 50
    snap = sk.snapshot()
    assert snap["count"] == 150
    assert snap["window"]["count"] == 50
    assert snap["window"]["mean"] == pytest.approx(0.85, abs=1e-6)


def test_sketch_clamps_out_of_range_and_merges_binwise():
    sk = ScoreSketch(bins=4, window_s=60.0)
    sk.observe(np.array([-1.0, 0.1, 0.6, 2.0]))
    counts = sk.lifetime_counts()
    assert counts.sum() == 4  # nothing silently dropped
    assert counts[0] == 2 and counts[-1] == 1
    # Mergeable by construction: a merged distribution is the bin-wise
    # sum, so drift over a merge equals drift over the union stream.
    other = ScoreSketch(bins=4, window_s=60.0)
    other.observe(np.array([0.3, 0.3]))
    merged = sk.lifetime_counts() + other.lifetime_counts()
    assert merged.sum() == 6
    assert psi(merged, merged) == 0.0


def test_histogram_percentile_interpolates():
    counts = [0, 100, 0, 0]  # all mass in [0.25, 0.5)
    assert 0.25 <= histogram_percentile(counts, 0.0, 1.0, 50) <= 0.5
    assert histogram_percentile([0, 0, 0, 0], 0.0, 1.0, 99) == 0.0


# ------------------------------------------------------------------ drift


def test_psi_js_on_known_shifted_distributions():
    base = np.array([100, 400, 400, 100])
    same = np.array([50, 200, 200, 50])  # same shape, half the mass
    shifted = np.array([400, 100, 100, 400])  # mass inverted
    assert psi(base, same) == pytest.approx(0.0, abs=1e-6)
    assert js_divergence(base, same) == pytest.approx(0.0, abs=1e-6)
    assert psi(base, shifted) > 0.5  # a major shift on the PSI scale
    assert 0.0 < js_divergence(base, shifted) <= 1.0  # base-2 bound
    # Symmetry (JS) and finiteness on empty-bin overlap (the textbook
    # PSI blowup the smoothing must absorb).
    assert js_divergence(base, shifted) == pytest.approx(
        js_divergence(shifted, base)
    )
    assert np.isfinite(psi([100, 0, 0], [0, 0, 100]))


def test_reference_drift_and_exceeded_flag():
    clock = FakeClock()
    m = make_monitor(clock, drift_threshold_psi=0.2)
    rng = np.random.RandomState(0)
    m.observe("DCN", 1, rng.uniform(0.4, 0.6, 500))
    m.pin_reference(save=False)
    # Same distribution: drift stays below threshold.
    m.observe("DCN", 1, rng.uniform(0.4, 0.6, 500))
    drift = m.snapshot()["models"]["DCN"]["drift"]
    assert drift["reference"]["psi"] < 0.2
    assert drift["exceeded"] is False
    # Shifted segment: the window mass moves, PSI crosses the threshold.
    clock.advance(70.0)  # old windowed mass ages out
    m.observe("DCN", 1, rng.uniform(0.85, 0.95, 500))
    drift = m.snapshot()["models"]["DCN"]["drift"]
    assert drift["reference"]["psi"] >= 0.2
    assert drift["exceeded"] is True


def test_reference_save_load_roundtrip(tmp_path):
    path = str(tmp_path / "artifacts" / "quality_reference.json")
    m = make_monitor(reference_file=path)
    m.observe("DCN", 1, np.random.RandomState(0).uniform(0.2, 0.4, 300))
    pinned = m.pin_reference()
    assert pinned["models"]["DCN"] == 300 and pinned["path"] == path
    doc = json.loads(open(path).read())
    assert doc["bins"] == m.bins and "DCN" in doc["models"]
    # A fresh monitor loads the artifact at construction and drifts
    # against it without ever re-pinning.
    m2 = make_monitor(reference_file=path)
    m2.observe("DCN", 1, np.random.RandomState(1).uniform(0.8, 0.9, 300))
    drift = m2.snapshot()["models"]["DCN"]["drift"]
    assert drift["reference"] is not None
    assert drift["reference"]["psi"] > 0.2
    # Mismatched bin geometry is refused, not silently compared.
    m3 = QualityMonitor(bins=7, drift_check_interval_s=0.0)
    assert m3.load_reference(path) == 0


def test_version_pair_drift_through_real_watcher_swap(tmp_path, servable):
    """The canary-vs-stable signal: a REAL VersionWatcher loads v2 next
    to v1, the servable-change hook ticks the monitor, live traffic under
    both versions feeds per-version sketches, and the version-pair drift
    compares the two live windowed distributions."""
    from distributed_tf_serving_tpu.serving.server import _servable_change_hook
    from distributed_tf_serving_tpu.serving.version_watcher import (
        VersionWatcher,
        VersionWatcherConfig,
    )
    from distributed_tf_serving_tpu.train.checkpoint import save_servable

    monitor = make_monitor()
    registry = ServableRegistry()
    save_servable(tmp_path / "1", servable, kind="dcn")
    watcher = VersionWatcher(
        tmp_path, registry,
        VersionWatcherConfig(poll_interval_s=3600, model_name="DCN"),
        on_servable_change=_servable_change_hook(None, monitor),
    )
    watcher.poll_once()
    assert monitor.version_changes == 1
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0, quality=monitor).start()
    try:
        sv1 = registry.resolve("DCN")
        arrays = make_arrays(20, seed=3)
        for _ in range(3):
            batcher.submit(sv1, arrays).result(timeout=30)
        save_servable(
            tmp_path / "2", dataclasses.replace(servable, version=2), kind="dcn"
        )
        watcher.poll_once()
        assert monitor.version_changes >= 2
        sv2 = registry.resolve("DCN")
        assert sv2.version == 2
        for _ in range(3):
            batcher.submit(sv2, arrays).result(timeout=30)
    finally:
        batcher.stop()
    snap = monitor.snapshot()
    versions = snap["models"]["DCN"]["versions"]
    assert set(versions) == {"1", "2"}
    assert versions["1"]["count"] == 60 and versions["2"]["count"] == 60
    pair = snap["models"]["DCN"]["drift"]["version_pair"]
    assert pair is not None and pair["versions"] == [1, 2]
    # Identical params serve identical scores: the pair is comparable
    # and NOT drifted — the rollout-gate green case.
    assert pair["psi"] == pytest.approx(0.0, abs=1e-6)
    # A genuinely shifted canary (v2 scoring differently) must read as
    # pair drift.
    monitor.observe("DCN", 2, np.random.RandomState(5).uniform(0.9, 1.0, 200))
    monitor._drift_tick(monitor._clock())
    pair = monitor.snapshot()["models"]["DCN"]["drift"]["version_pair"]
    assert pair["psi"] > 0.2


# ------------------------------------------------------------- label join


def test_label_join_in_order_late_orphaned():
    clock = FakeClock()
    m = make_monitor(clock)
    arrays = make_arrays(4, seed=1)
    scores = np.array([0.1, 0.2, 0.8, 0.9])
    m.observe("DCN", 1, scores, arrays=arrays)
    keys = row_label_keys(arrays)
    # In-order join by row digest.
    out = m.ingest_labels([{"id": keys[0], "label": 0}, {"id": keys[2], "label": 1}])
    assert out == {"joined": 2, "orphaned": 0}
    # Late: the impression aged past the window but the key survives —
    # joined AND counted late, so a slow feedback loop is visible.
    clock.advance(120.0)
    out = m.ingest_labels([{"id": keys[1], "label": 1}])
    assert out["joined"] == 1
    # Orphaned: a key the reservoir never held (or already evicted).
    out = m.ingest_labels([{"id": "f" * 32, "label": 1}])
    assert out == {"joined": 0, "orphaned": 1}
    blk = m.snapshot()["labels"]
    assert blk["joined"] == 3 and blk["orphaned"] == 1 and blk["late"] == 1


def test_label_join_by_trace_id_and_row_suffix():
    m = make_monitor()
    m.observe("DCN", 1, np.array([0.3, 0.7]), trace_id="a" * 32)
    assert m.ingest_labels([{"id": "a" * 32, "label": 0}])["joined"] == 1  # row 0
    assert m.ingest_labels([{"id": "a" * 32 + "#1", "label": 1}])["joined"] == 1
    assert m.ingest_labels([{"id": "a" * 32 + "#9", "label": 1}])["orphaned"] == 1
    assert m.ingest_labels([{"id": "a" * 32 + "#x", "label": 1}])["orphaned"] == 1


def test_label_validation():
    m = make_monitor()
    with pytest.raises(ValueError):
        m.ingest_labels([{"id": "x"}])  # no label
    with pytest.raises(ValueError):
        m.ingest_labels([{"id": "x", "label": 3.0}])  # out of range
    with pytest.raises(ValueError):
        # Fractional labels would silently break the rank AUC (labels ==
        # 1 selects nothing, pos goes fractional): refused up front.
        m.ingest_labels([{"id": "x", "label": 0.5}])


def test_label_batch_validated_before_any_item_applies():
    """A malformed item mid-batch must not leave a joined prefix behind
    the 400 — the client's retry of the whole batch would double-count
    those (score, label) pairs in the windowed AUC."""
    m = make_monitor()
    m.observe("DCN", 1, np.array([0.3, 0.7]), trace_id="t" * 32)
    with pytest.raises(ValueError):
        m.ingest_labels([
            {"id": "t" * 32, "label": 1},
            {"id": "t" * 32 + "#1", "label": 0.25},  # invalid mid-batch
        ])
    blk = m.snapshot()["labels"]
    assert blk["joined"] == 0 and blk["window_pairs"] == 0


def test_label_ts_feeds_feedback_delay_not_windowing():
    import time as time_mod

    clock = FakeClock()
    m = make_monitor(clock)
    m.observe("DCN", 1, np.array([0.4]), trace_id="t")
    m.ingest_labels([{"id": "t", "label": 1, "ts": time_mod.time() - 5.0}])
    blk = m.snapshot()["labels"]
    assert blk["feedback_delay"]["count"] == 1
    assert blk["feedback_delay"]["mean_s"] == pytest.approx(5.0, abs=1.0)
    # ts never decides window membership: the pair joined on the
    # monitor's own clock and is in-window regardless of the old ts.
    assert blk["window_pairs"] == 1 and blk["late"] == 0


def test_topk_restored_batches_are_not_sketched(servable):
    """Top-k output compaction back-fills 0.0 off the head — the restored
    vector is not the model's prediction over the request, so the quality
    hook must skip those batches entirely (no fake-zero sketching, no
    labels joining against synthetic scores)."""
    monitor = make_monitor()
    batcher = DynamicBatcher(
        buckets=(32,), max_wait_us=0, output_top_k=2, quality=monitor,
    ).start()
    try:
        arrays = make_arrays(8, seed=33)
        batcher.submit(
            servable, arrays, output_keys=("prediction_node",)
        ).result(timeout=60)
        assert batcher.stats.topk_batches == 1
        assert monitor.observed_requests == 0
        # A full-vector request on the same batcher still sketches.
        batcher.submit(servable, arrays).result(timeout=60)
        assert monitor.observed_requests == 1
    finally:
        batcher.stop()


def test_reservoir_auc_matches_exact_auc_and_calibration():
    """The acceptance bound, exactly: the monitor's windowed AUC over the
    joined pairs IS train/data.py::auc over the same (score, label)
    sample — one implementation, zero drift."""
    m = make_monitor()
    rng = np.random.RandomState(7)
    scores = rng.rand(64)
    labels = (rng.rand(64) < scores).astype(np.float32)
    arrays = make_arrays(64, seed=7)
    m.observe("DCN", 1, scores, arrays=arrays)
    keys = row_label_keys(arrays)
    out = m.ingest_labels(
        [{"id": k, "label": float(lb)} for k, lb in zip(keys, labels)]
    )
    assert out["joined"] == 64
    blk = m.snapshot()["labels"]
    assert blk["auc"] == pytest.approx(exact_auc(labels, scores), abs=1e-6)
    cal = blk["calibration"]
    assert cal["error"] is not None and 0.0 <= cal["error"] <= 1.0
    assert sum(d["count"] for d in cal["deciles"]) == 64
    # Single-class windows have no defined AUC: reported as None, never
    # a crash or a fake 0.5.
    m2 = make_monitor()
    m2.observe("DCN", 1, np.array([0.5]), trace_id="t")
    m2.ingest_labels([{"id": "t", "label": 1}])
    assert m2.snapshot()["labels"]["auc"] is None


def test_calibration_report_perfectly_calibrated():
    scores = np.concatenate([np.full(100, 0.25), np.full(100, 0.75)])
    labels = np.concatenate([
        np.r_[np.ones(25), np.zeros(75)], np.r_[np.ones(75), np.zeros(25)],
    ])
    rep = calibration_report(scores, labels)
    assert rep["error"] == pytest.approx(0.0, abs=1e-6)


# ------------------------------------------------ batcher feed + exclusion


def test_batcher_feeds_monitor_and_excludes_warmup_and_cache_serves(servable):
    from distributed_tf_serving_tpu.cache import ScoreCache

    monitor = make_monitor()
    batcher = DynamicBatcher(
        buckets=(32,), max_wait_us=0, score_cache=ScoreCache(),
        quality=monitor,
    ).start()
    try:
        # Warmup exclusion: the whole ladder warms through the completer
        # path and the sketch must see none of it.
        batcher.warmup_via_queue(servable, buckets=(32,))
        assert monitor.observed_requests == 0
        arrays = make_arrays(5, seed=11)
        got = batcher.submit(servable, arrays).result(timeout=30)
        assert monitor.observed_requests == 1
        snap = monitor.snapshot()["models"]["DCN"]["versions"]["1"]
        assert snap["count"] == 5
        # The sketched scores are the scores the client received.
        assert snap["min"] >= 0.0 and snap["max"] <= 1.0
        assert snap["mean"] == pytest.approx(
            float(np.mean(got["prediction_node"])), abs=1e-6
        )
        # Cache-served repeats never re-observe (structural exclusion:
        # hits — and brownout stale-serves — return before the completer;
        # the same mechanism is why degraded serves are never sketched).
        batcher.submit(servable, arrays).result(timeout=30)
        assert monitor.observed_requests == 1
        # The criticality lane rides as a label.
        batcher.submit(
            servable, make_arrays(3, seed=12), criticality="sheddable"
        ).result(timeout=30)
        lanes = monitor.snapshot()["models"]["DCN"]["versions"]["1"]["lanes"]
        assert lanes.get("sheddable") == 1 and lanes.get("default") == 1
    finally:
        batcher.stop()


def test_disabled_mode_inert(servable):
    """No monitor: one attribute read on the completer, no sketches, and
    the surfaces report the plane off."""
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0).start()
    try:
        assert batcher.quality is None
        batcher.submit(servable, make_arrays(4)).result(timeout=30)
        impl = PredictionServiceImpl(ServableRegistry(), batcher)
        assert impl.quality_stats() is None
        from distributed_tf_serving_tpu.serving.service import ServiceError

        with pytest.raises(ServiceError) as ei:
            impl.quality_ingest_labels([{"id": "x", "label": 1}])
        assert ei.value.code == "FAILED_PRECONDITION"
        with pytest.raises(ServiceError):
            impl.quality_pin_reference()
    finally:
        batcher.stop()


def test_drift_exemplars_force_kept_in_tail_sampler(servable):
    """Drift over threshold arms exemplar capture: the next traced
    requests get the `quality.drift` annotation, and annotated spans are
    ALWAYS retained by the recorder — /tracez shows WHICH requests moved
    the distribution even at sample_rate 0."""
    rec = tracing.enable(buffer_size=64, sample_rate=0.0, slowest_n=0)
    try:
        monitor = make_monitor(drift_threshold_psi=0.1, exemplar_traces=4)
        rng = np.random.RandomState(0)
        monitor.observe("DCN", 1, rng.uniform(0.1, 0.3, 200))
        monitor.pin_reference(save=False)
        batcher = DynamicBatcher(buckets=(32,), max_wait_us=0, quality=monitor).start()
        try:
            # Drive the windowed distribution away from the pin, then
            # serve traced requests — the completer annotates them.
            monitor.observe("DCN", 1, rng.uniform(0.7, 0.9, 400))
            arrays = make_arrays(4, seed=2)
            with tracing.start_root("client.predict") as span:
                batcher.submit(servable, arrays, span=span).result(timeout=30)
        finally:
            batcher.stop()
        assert monitor.exemplars_marked >= 1
        kept = [
            s for s in rec.spans()
            if any(a["message"] == "quality.drift" for a in s.annotations)
        ]
        assert kept, "annotated exemplar span must be force-kept"
        ann = next(
            a for a in kept[0].annotations if a["message"] == "quality.drift"
        )
        assert ann["model"] == "DCN" and ann["psi"] >= 0.1
        assert monitor.snapshot()["exemplars"]["marked"] >= 1
    finally:
        tracing.disable()


def test_series_space_is_bounded():
    m = make_monitor()
    for i in range(m.MAX_SERIES + 10):
        m.observe(f"model-{i}", 1, np.array([0.5]))
    assert len(m._sketches) == m.MAX_SERIES
    assert m.series_overflow == 10


# ------------------------------------------------- config + build_stack


def test_quality_config_parsing(tmp_path):
    from distributed_tf_serving_tpu.utils.config import load_config

    p = tmp_path / "cfg.toml"
    p.write_text(
        "[quality]\nenabled = true\nbins = 20\nwindow_seconds = 30.0\n"
        'drift_threshold_psi = 0.3\nreference_file = ""\n'
    )
    cfg = load_config(p)["quality"]
    assert cfg.enabled and cfg.bins == 20 and cfg.window_seconds == 30.0
    assert cfg.drift_threshold_psi == 0.3
    monitor = cfg.build()
    assert isinstance(monitor, QualityMonitor)
    assert monitor.bins == 20 and monitor.window_s == 30.0
    assert QualityConfig().build() is None  # disabled default builds nothing
    with pytest.raises(ValueError):
        load_config(_write(tmp_path, "[quality]\nbogus_knob = 1\n"))


def _write(tmp_path, text):
    p = tmp_path / "bad.toml"
    p.write_text(text)
    return p


def test_build_stack_quality_master_switch():
    from distributed_tf_serving_tpu.serving.server import build_stack
    from distributed_tf_serving_tpu.utils.config import ServerConfig

    cfg = ServerConfig(warmup=False, buckets=(32,), num_fields=F)
    for enabled in (False, True):
        _r, batcher, impl, _s, _m, _w = build_stack(
            cfg, model_config=CFG,
            quality_config=QualityConfig(enabled=enabled, reference_file=""),
        )
        try:
            assert (batcher.quality is not None) == enabled
            if enabled:
                assert impl.quality_stats()["enabled"] is True
            else:
                assert impl.quality_stats() is None
        finally:
            batcher.stop()


# ------------------------------------------------------------- Prometheus


def test_quality_prometheus_series_and_lint():
    import os
    import sys

    sys.path.insert(
        0,
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"),
    )
    from check_prom import lint_text

    from distributed_tf_serving_tpu.utils.metrics import ServerMetrics

    m = make_monitor()
    rng = np.random.RandomState(0)
    m.observe("DCN", 1, rng.uniform(0.2, 0.4, 300), arrays=make_arrays(8))
    m.pin_reference(save=False)
    m.observe("DCN", 2, rng.uniform(0.6, 0.9, 300))
    m.observe('we"ird', 1, rng.rand(10))  # label escaping must hold
    text = ServerMetrics().prometheus_text(quality=m.snapshot())
    assert 'dts_tpu_quality_scores_total{model_name="DCN",version="1"} 300' in text
    assert 'dts_tpu_quality_drift_psi{model_name="DCN",kind="reference"}' in text
    assert 'dts_tpu_quality_drift_psi{model_name="DCN",kind="version_pair"}' in text
    assert "dts_tpu_quality_score_bucket" in text
    assert lint_text(text) == []


# ---------------------------------------------------------------- surfaces


def _run_rest(impl, handler):
    async def go():
        runner, port = await start_rest_gateway(impl, port=0)
        try:
            async with aiohttp.ClientSession(
                f"http://127.0.0.1:{port}"
            ) as session:
                return await handler(session)
        finally:
            await runner.cleanup()

    return asyncio.run(go())


def test_qualityz_labelz_and_monitoring_section_routes(servable):
    monitor = make_monitor()
    registry = ServableRegistry()
    registry.load(servable)
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0, quality=monitor).start()
    impl = PredictionServiceImpl(registry, batcher)
    try:
        arrays = make_arrays(4, seed=9)
        batcher.submit(servable, arrays).result(timeout=30)
        keys = row_label_keys(arrays)

        async def drive(session):
            out = {}
            async with session.get("/qualityz") as r:
                out["qualityz"] = (r.status, await r.json())
            async with session.get("/qualityz?model=DCN&version=1") as r:
                out["filtered"] = await r.json()
            async with session.get("/qualityz?model=nope") as r:
                out["missing"] = await r.json()
            async with session.get("/qualityz?version=x") as r:
                out["bad_version"] = r.status
            async with session.post("/labelz", json={"labels": [
                {"id": keys[0], "label": 1}, {"id": "f" * 32, "label": 0},
            ]}) as r:
                out["labelz"] = (r.status, await r.json())
            async with session.post("/labelz", json={"id": keys[1], "label": 0}) as r:
                out["labelz_single"] = await r.json()
            async with session.post("/labelz", json=[1, 2]) as r:
                out["labelz_bad"] = r.status
            async with session.post("/qualityz/snapshot") as r:
                out["snapshot"] = (r.status, await r.json())
            async with session.get("/monitoring?section=quality") as r:
                out["section"] = await r.json()
            async with session.get("/monitoring?section=nope") as r:
                out["section_bad"] = r.status
            async with session.get("/monitoring?section=cache") as r:
                out["section_disabled"] = await r.json()
            async with session.get("/monitoring") as r:
                out["monitoring"] = await r.json()
            async with session.get("/monitoring/prometheus/metrics") as r:
                out["prom"] = await r.text()
            return out

        out = _run_rest(impl, drive)
        status, qz = out["qualityz"]
        assert status == 200 and qz["enabled"] is True
        assert qz["models"]["DCN"]["versions"]["1"]["count"] == 4
        assert out["filtered"]["models"]["DCN"]["versions"].keys() == {"1"}
        assert out["missing"]["models"] == {}
        assert out["bad_version"] == 400
        status, joined = out["labelz"]
        assert status == 200 and joined == {"joined": 1, "orphaned": 1}
        assert out["labelz_single"] == {"joined": 1, "orphaned": 0}
        assert out["labelz_bad"] == 400
        status, pinned = out["snapshot"]
        assert status == 200 and pinned["pinned"] is True
        assert pinned["models"]["DCN"] == 4
        # ?section=NAME serves exactly one block; a disabled plane's
        # section answers null; unknown names are client errors.
        assert set(out["section"]) == {"quality"}
        assert out["section"]["quality"]["enabled"] is True
        assert out["section_bad"] == 400
        assert out["section_disabled"] == {"cache": None}
        assert out["monitoring"]["quality"]["labels"]["joined"] == 2
        assert "cache" not in out["monitoring"]  # disabled plane absent
        assert "dts_tpu_quality_scores_total" in out["prom"]
    finally:
        batcher.stop()


def test_qualityz_disabled_surface(servable):
    registry = ServableRegistry()
    registry.load(servable)
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0).start()
    impl = PredictionServiceImpl(registry, batcher)
    try:
        async def drive(session):
            out = {}
            async with session.get("/qualityz") as r:
                out["qualityz"] = await r.json()
            async with session.post("/labelz", json={"id": "x", "label": 1}) as r:
                out["labelz_status"] = r.status
            async with session.post("/qualityz/snapshot") as r:
                out["snapshot_status"] = r.status
            async with session.get("/monitoring?section=quality") as r:
                out["section"] = await r.json()
            return out

        out = _run_rest(impl, drive)
        assert out["qualityz"] == {"enabled": False}
        assert out["labelz_status"] == 500  # FAILED_PRECONDITION classification
        assert out["snapshot_status"] == 500
        assert out["section"] == {"quality": None}
    finally:
        batcher.stop()


def test_client_label_keys_meet_server_join(servable):
    """End-to-end key symmetry: the digests a CLIENT computes over the
    arrays it sends are the digests the server's completer stored — a
    label keyed client-side joins with no id plumbed through Predict."""
    from distributed_tf_serving_tpu.client import label_keys

    monitor = make_monitor()
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0, quality=monitor).start()
    try:
        arrays = make_arrays(6, seed=21)
        client_keys = label_keys(arrays)
        batcher.submit(servable, arrays).result(timeout=30)
        out = monitor.ingest_labels(
            [{"id": k, "label": 1} for k in client_keys]
        )
        assert out == {"joined": 6, "orphaned": 0}
    finally:
        batcher.stop()
