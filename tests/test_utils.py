"""Aux-subsystem tests: histogram percentiles, server metrics, phase traces,
TOML config loading (SURVEY.md §5 obligations)."""

import dataclasses

import numpy as np
import pytest

from distributed_tf_serving_tpu.utils import (
    ClientConfig,
    LatencyHistogram,
    PhaseTrace,
    ServerConfig,
    ServerMetrics,
    load_config,
)


def test_histogram_percentiles_track_numpy():
    rng = np.random.RandomState(0)
    samples = rng.lognormal(mean=np.log(5e-3), sigma=0.5, size=20_000)  # seconds
    h = LatencyHistogram()
    for s in samples:
        h.record(s)
    for q in (50, 90, 99):
        want = np.percentile(samples, q) * 1e3
        got = h.percentile_ms(q)
        assert got == pytest.approx(want, rel=0.15), (q, got, want)
    assert h.mean_ms() == pytest.approx(samples.mean() * 1e3, rel=1e-6)
    assert h.count == 20_000


def test_histogram_empty_and_single():
    h = LatencyHistogram()
    assert h.percentile_ms(50) == 0.0
    h.record(0.002)
    assert h.percentile_ms(50) == pytest.approx(2.0, rel=0.15)


def test_server_metrics_snapshot():
    m = ServerMetrics()
    for _ in range(8):
        m.observe("Predict", 0.004, ok=True)
    m.observe("Predict", 0.1, ok=False)
    m.observe("Classify", 0.01, ok=True)
    snap = m.snapshot()
    assert snap["rpcs"]["Predict"]["ok"] == 8
    assert snap["rpcs"]["Predict"]["errors"] == 1
    assert snap["rpcs"]["Predict"]["count"] == 9
    assert snap["rpcs"]["Classify"]["ok"] == 1
    assert snap["qps"] > 0


def test_phase_trace():
    t = PhaseTrace()
    with t.span("decode"):
        pass
    with t.span("decode"):
        pass
    with t.span("execute"):
        pass
    snap = t.snapshot()
    assert snap["decode"]["count"] == 2
    assert snap["execute"]["count"] == 1
    t.reset()
    assert t.snapshot() == {}


def test_config_defaults_match_reference_constants():
    c = ClientConfig()
    # The DCNClient.java:25-42 knob set.
    assert c.num_fields == 43
    assert c.candidate_num == 1500
    assert c.request_num == 1000
    assert c.concurrent_num == 6
    assert c.model_name == "DCN"
    assert c.signature_name == "serving_default"
    assert c.output_key == "prediction_node"
    assert ServerConfig().port == 9999


def test_toml_roundtrip(tmp_path):
    p = tmp_path / "cfg.toml"
    p.write_text(
        """
[server]
port = 8500
buckets = [64, 256]
model_kind = "dlrm"

version_labels = {stable = 2, canary = 3}

[client]
hosts = ["a:1", "b:2", "c:3"]
candidate_num = 500
"""
    )
    cfg = load_config(p)
    assert cfg["server"].port == 8500
    assert cfg["server"].buckets == (64, 256)
    assert cfg["server"].model_kind == "dlrm"
    # Inline table -> sorted hashable pairs (the registry/watcher contract).
    assert cfg["server"].version_labels == (("canary", 3), ("stable", 2))
    hash(cfg["server"])  # frozen config must stay hashable with labels set
    assert cfg["client"].hosts == ("a:1", "b:2", "c:3")
    assert cfg["client"].candidate_num == 500
    assert cfg["client"].num_fields == 43  # untouched default


def test_batching_parameters_file(tmp_path):
    """A tensorflow_model_server batching_parameters_file maps onto the
    batcher knobs (text-format BatchingParameters, upstream field set)."""
    from distributed_tf_serving_tpu.utils.config import apply_batching_parameters

    p = tmp_path / "batching.pbtxt"
    p.write_text(
        "max_batch_size { value: 2048 }\n"
        "batch_timeout_micros { value: 5000 }\n"
        "max_enqueued_batches { value: 8 }\n"
        "num_batch_threads { value: 6 }\n"
        "allowed_batch_sizes: 256\n"
        "allowed_batch_sizes: 1024\n"
        "allowed_batch_sizes: 2048\n"
        "pad_variable_length_inputs { value: true }\n"
    )
    cfg = apply_batching_parameters(ServerConfig(), p)
    assert cfg.buckets == (256, 1024, 2048)
    assert cfg.max_wait_us == 5000
    assert cfg.queue_capacity_candidates == 8 * 2048
    assert cfg.completion_workers == 6

    # Upstream rule: largest allowed size must equal max_batch_size.
    bad = tmp_path / "bad.pbtxt"
    bad.write_text(
        "max_batch_size { value: 4096 }\nallowed_batch_sizes: 2048\n"
    )
    with pytest.raises(ValueError, match="must equal max_batch_size"):
        apply_batching_parameters(ServerConfig(), bad)

    # max_batch_size alone: default ladder truncated and capped at it.
    only_max = tmp_path / "max.pbtxt"
    only_max.write_text("max_batch_size { value: 1000 }\n")
    cfg = apply_batching_parameters(ServerConfig(), only_max)
    assert cfg.buckets[-1] == 1000
    assert all(b < 1000 for b in cfg.buckets[:-1])

    # Degenerate max_batch_size: clear error, not a 0-bucket ladder.
    zero = tmp_path / "zero.pbtxt"
    zero.write_text("max_batch_size { value: 0 }\n")
    with pytest.raises(ValueError, match="must be positive"):
        apply_batching_parameters(ServerConfig(), zero)


def test_toml_unknown_key_rejected(tmp_path):
    p = tmp_path / "bad.toml"
    p.write_text("[server]\nprot = 1\n")
    with pytest.raises(ValueError, match="unknown ServerConfig keys"):
        load_config(p)
    p.write_text("[srever]\n")
    with pytest.raises(ValueError, match="unknown config sections"):
        load_config(p)


@pytest.mark.parametrize("key", ["donate_buffers", "async_readback"])
def test_toml_removed_server_key_refused_by_name(tmp_path, key):
    """A [server] switch that was taken out (it had one value in use, or
    never took effect) fails a stale operator file loudly, naming the key,
    instead of being read and ignored."""
    p = tmp_path / "stale.toml"
    p.write_text(f"[server]\nport = 9999\n{key} = true\n")
    with pytest.raises(ValueError, match=f"unknown ServerConfig keys.*'{key}'"):
        load_config(p)
    assert key not in {f.name for f in dataclasses.fields(ServerConfig)}


def test_model_section_in_toml(tmp_path):
    """[model] section maps onto ModelConfig; absent section stays absent so
    callers can distinguish explicit architecture from defaults."""
    from distributed_tf_serving_tpu.utils.config import load_config

    p = tmp_path / "cfg.toml"
    p.write_text(
        '[server]\nport = 9000\n\n'
        '[model]\nnum_fields = 6\nvocab_size = 997\nembed_dim = 4\n'
        'mlp_dims = [16]\ncompute_dtype = "float32"\n'
    )
    out = load_config(p)
    assert out["server"].port == 9000
    assert out["model"].num_fields == 6
    assert out["model"].mlp_dims == (16,)
    p2 = tmp_path / "bare.toml"
    p2.write_text("[server]\nport = 9001\n")
    assert "model" not in load_config(p2)
