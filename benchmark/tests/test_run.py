import json
import os
import tomllib

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def run_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_run", os.path.join(ROOT, "benchmark", "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


RUN = run_module()


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_the_generated_toml_is_what_the_program_loads(entry, tmp_path):
    from distributed_tf_serving_tpu.utils.config import load_config

    from benchmark.common import toml_text

    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    text = toml_text(config)
    assert tomllib.loads(text) == config["toml"]
    assert set(config["toml"]) == {"server", "model"}
    assert set(config["toml"]["server"]) == {"model_kind", "num_fields", "buckets"}
    path = tmp_path / "server.toml"
    path.write_text(text)
    cfgs = load_config(str(path))
    model = config["toml"]["model"]
    assert cfgs["server"].model_kind == config["toml"]["server"]["model_kind"]
    assert cfgs["model"].vocab_size == model["vocab_size"]
    assert cfgs["model"].embed_dim == model["embed_dim"]
    assert list(cfgs["model"].mlp_dims) == model["mlp_dims"]
    assert set(entry["reduced"]) == set(config["reduced"]) <= set(model)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader_that_returns_nothing_on_nothing(metric):
    reader = RUN.load_reader(metric)
    assert reader is not None
    empty = {"phases": {}, "batcher": {}, "runtime": {}, "gen": {}, "trace": {},
             "model": {}, "notes": {}, "cost": None, "least_seconds": None, "device_kind": "x"}
    assert reader(empty) is None


def test_deltas_are_window_deltas():
    before = {"predict.decode": {"count": 10, "total_ms": 5.0, "mean_us": 500.0}}
    after = {"predict.decode": {"count": 30, "total_ms": 9.0, "mean_us": 300.0},
             "batch.pad": {"count": 4, "total_ms": 2.0, "mean_us": 500.0}}
    delta = RUN.delta_phases(before, after)
    assert delta["predict.decode"] == {"count": 20, "total_ms": 4.0}
    assert delta["batch.pad"] == {"count": 4, "total_ms": 2.0}
    block = {"batches": 3, "requests": 9, "mean_occupancy": 0.5, "readback_overlap_fraction": 0.1}
    later = {"batches": 10, "requests": 30, "mean_occupancy": 0.8, "readback_overlap_fraction": 0.3,
             "max_queue_depth": 4}
    assert RUN.batcher_block(block, later) == {
        "batches": 7, "requests": 21, "mean_occupancy": 0.8, "readback_overlap_fraction": 0.3}


def test_readers_on_a_made_up_window():
    from benchmark import peaks

    ctx = {
        "phases": {"predict.decode": {"count": 100, "total_ms": 20.0},
                   "predict.encode": {"count": 100, "total_ms": 10.0},
                   "predict.execute": {"count": 100, "total_ms": 300.0},
                   "batch.pad": {"count": 50, "total_ms": 5.0},
                   "batch.dispatch": {"count": 50, "total_ms": 45.0},
                   "batch.cache": {"count": 50, "total_ms": 25.0}},
        "batcher": {"batches": 50, "requests": 100, "mean_occupancy": 0.781,
                    "readback_overlap_fraction": 0.75},
        "gen": {"p50_ms": 5.0, "p95_ms": 12.5, "late_p95_ms": 0.1, "mean_from_send_ms": 5.0, "rows_answered": 80000,
                "warm_stall_ms": 1200.0},
        "runtime": {"warmup_s": 2.5, "compile_cache": {"misses": 0}},
        "trace": {"busy_s": 0.5, "window_s": 2.0, "batches": 20, "rows": 327680},
        "notes": {}, "device_kind": "TPU v5 lite", "least_seconds": peaks.least_seconds,
    }
    config_path = os.path.join(ROOT, "benchmark/configs/dcn_v2_ref43/config.json")
    ctx["model"] = json.load(open(config_path))["toml"]["model"]
    ctx["cost"] = RUN.load_step_cost(config_path)
    want = {"codec_us": 300.0, "execute_ms": 3.0, "rpc_overhead_ms": 1.7, "batch_host_us": 1000.0,
            "rows_per_batch": 1600.0, "pad_share_pct": 21.9, "h2d_us": 500.0,
            "step_dev_us": 25000.0, "readback_blocked_pct": 25.0, "device_idle_pct": 75.0,
            "warmup_s": 2.5, "compile_misses": 0, "gen_late_p95_ms": 0.1, "warm_stall_ms": 1200.0,
            "tail_p95_ms": 12.5}
    for name, value in want.items():
        assert RUN.load_reader(name)(ctx) == pytest.approx(value), name
    share = RUN.load_reader("step_roofline")(ctx)
    # 327,680 rows at 3.28 MFLOP over 197 TFLOP/s is 5.46 ms of 500 ms
    assert share == pytest.approx(1.09, rel=0.02) and ctx["notes"]["step_roofline_bound"] == "compute"
    with pytest.raises(KeyError):
        peaks.least_seconds(1.0, 1.0, "TPU v9")


def test_generators_are_merged_and_the_warm_up_s_longest_silence_found(tmp_path):
    from benchmark.common import write_json

    def part(measured, due, sent, finished, rows):
        return {"attempted": len(measured), "failed": 0, "faults": [], "drained": True,
                "measured": measured, "due": due, "sent": sent, "finished": finished, "rows": rows}

    write_json(str(tmp_path / "gen0.json"), part([True, True], [0.0, 1.0], [0.001, 1.0], [0.011, 1.03], [100, 300]))
    write_json(str(tmp_path / "gen1.json"), part([True, False], [0.5, 9.0], [0.5, 9.0], [0.52, 9.5], [200, 999]))
    gen = RUN.merge_generators(str(tmp_path), 2)
    assert gen["attempted"] == 4 and gen["answers"].shape == (4, 5)
    assert sorted(gen["rows"].tolist()) == [100, 200, 300]  # the unmeasured answer is left out
    assert sorted(gen["latency_ms"].tolist()) == pytest.approx([11.0, 20.0, 30.0])
    assert sorted(gen["from_send_ms"].tolist()) == pytest.approx([10.0, 20.0, 30.0])
    assert max(gen["late_ms"]) == pytest.approx(1.0)

    write_json(str(tmp_path / "gen0.ready"), {"t": 9.0, "warm_finished": [5.0, 5.1, 7.0]})
    write_json(str(tmp_path / "gen1.ready"), {"t": 9.0, "warm_finished": [5.05, 5.6, 7.01]})
    assert RUN.warm_stall_ms(str(tmp_path), 2) == pytest.approx(1400.0)


def test_a_port_is_free_and_below_the_range_of_outgoing_connections():
    import socket

    with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
        lowest_outgoing = int(f.read().split()[0])
    port = RUN.free_port()
    assert 1024 < port < lowest_outgoing
    with socket.socket() as s:
        s.bind(("127.0.0.1", port))
