"""Bench-harness guards: what bench.py may and may not report (it is
exercised end-to-end only on hardware)."""

import importlib.util
import json
import pathlib

import pytest


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", pathlib.Path(__file__).parent.parent / "bench.py"
    )
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


def test_fail_is_rc1_with_no_number(capsys):
    """A failed run prints value 0.0 and exits 1: it has no way to print a
    number it did not measure."""
    bench = _load_bench()
    with pytest.raises(SystemExit) as exc:
        bench.fail("device_decomposition", "boom")
    assert exc.value.code == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["value"] == 0.0 and line["vs_baseline"] == 0.0
    assert line["stage"] == "device_decomposition" and "boom" in line["error"]
    assert set(line) == {"metric", "value", "unit", "vs_baseline", "error", "stage"}


def test_peak_flops_unknown_device_raises():
    bench = _load_bench()
    assert bench.peak_flops_for("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="no peak FLOP/s on record"):
        bench.peak_flops_for("TPU v9 imaginary")


def test_device_ab_block_refuses_too_few_chips(monkeypatch):
    """The multi-device A/B blocks run on real chips or fail; they never
    swap in an emulated CPU mesh beside a throughput number."""
    bench = _load_bench()
    monkeypatch.setenv("MESH_AB_DEVICES", "4")
    with pytest.raises(RuntimeError, match="needs 4 accelerator chips"):
        bench.mesh_ab_block("cpu:0")


def test_colocated_latency_estimate():
    """The north-star estimate is assembled from measured phases + the
    headline bucket's device step; a missing bucket falls back to linear
    scaling from the largest measured one."""
    bench = _load_bench()

    class Stats:
        mean_requests_per_batch = 13.0

    phases = {"predict.decode": 150.0, "predict.encode": 110.0,
              "batch.pad": 1200.0, "batch.dispatch": 4700.0,
              "batch.jitcall": 2600.0}
    device_block = {"device_step_us": {"8192": 190.0, "16384": 388.0}}
    est = bench.colocated_latency_estimate(phases, device_block, Stats(), 16384)
    want_us = 150.0 + 110.0 + 1200.0 + 4700.0 + 388.0 + 50.0
    assert abs(est["est_ms"] - want_us / 1e3) < 1e-6
    assert abs(est["floor_ms"] - (want_us - 2600.0) / 1e3) < 1e-6
    # 32768 missing from the map -> scaled 2x from the 16384 reading.
    est2 = bench.colocated_latency_estimate(phases, device_block, Stats(), 32768)
    assert abs(est2["components_us"]["device_step"] - 776.0) < 1e-6
    # No bucket measured -> no estimate rather than a garbage one.
    empty = {"device_step_us": {"8192": None}}
    assert bench.colocated_latency_estimate(phases, empty, Stats(), 8192) is None


def test_scale_window_caps_clamped_by_ladder(monkeypatch):
    bench = _load_bench()
    monkeypatch.setenv("DTS_BENCH_TOP_BUCKET", "8192")
    scale = bench.Scale("tpu")
    assert scale.buckets[-1] == 8192  # ladder respects the env override
    # Window caps above the ladder top are clamped at use (bench clamps via
    # min(cap, buckets[-1]); here we just pin that the config carries caps
    # the clamp must handle).
    assert max(cap for cap, _conc in scale.windows) > 8192
