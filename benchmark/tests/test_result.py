import json
import math
import os

import pytest

from benchmark import result

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
RANK, BULK = "dcn_v2_ref43-rank", "dlrm_mlperf-bulk"
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 10_200_000_000}


def good(cell, traced):
    values = {name: 1.5 for name in result.cell_metrics(BENCH, cell, traced)}
    device = dict(DEVICE, **({"busy_s": 0.7, "window_s": 3.0} if traced else {}))
    breakdown = {"device_ops": [["fusion.1", 0.4]], "idle_gaps": [["unattributed", 0.01]]}
    return result.build(BENCH, cell, traced, correct=True, attempted=4000, failed=0,
                        values=values, device=device, breakdown=breakdown if traced else None)


@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
@pytest.mark.parametrize("traced", [False, True])
def test_a_good_object_passes(cell, traced):
    obj = good(cell, traced)
    result.validate(obj, BENCH, cell, traced)
    assert "setup_s" in obj["metrics"] or traced
    assert ("breakdown" in obj) == traced


def refuse(obj, cell, traced, fragment):
    with pytest.raises(result.Malformed) as err:
        result.validate(obj, BENCH, cell, traced)
    assert fragment in str(err.value)


def test_cause_4_busy_beyond_the_window_or_zero():
    obj = good(BULK, True)
    obj["device"]["busy_s"] = 3.2
    refuse(obj, BULK, True, "busy_s")
    obj["device"]["busy_s"] = 0.0
    refuse(obj, BULK, True, "busy_s")
    del obj["device"]["busy_s"]
    refuse(obj, BULK, True, "busy_s")


@pytest.mark.parametrize("bad", [None, math.nan, math.inf, "1.5", True])
def test_cause_5_a_value_that_is_not_a_finite_number(bad):
    obj = good(RANK, False)
    obj["metrics"]["p50_ms"]["value"] = bad
    refuse(obj, RANK, False, "p50_ms")


def test_cause_5_a_metric_of_the_cell_is_missing_or_strays():
    obj = good(RANK, False)
    del obj["metrics"]["p50_ms"]
    refuse(obj, RANK, False, "p50_ms")
    obj = good(RANK, False)
    obj["metrics"]["cand_per_s"] = {"value": 1.0, "unit": "cand/s"}
    refuse(obj, RANK, False, "cand_per_s")
    obj = good(RANK, False)
    obj["metrics"]["p50_ms"]["unit"] = "s"
    refuse(obj, RANK, False, "unit")


@pytest.mark.parametrize("peak", [None, 0, -1, math.nan])
def test_cause_6_no_true_peak_memory(peak):
    obj = good(RANK, False)
    obj["device"]["memory_peak_bytes"] = peak
    refuse(obj, RANK, False, "memory_peak_bytes")
    del obj["device"]["memory_peak_bytes"]
    refuse(obj, RANK, False, "memory_peak_bytes")


def test_keys_outside_the_contract_and_a_roofline_above_its_peak():
    obj = good(RANK, False)
    obj["samples"] = 12
    refuse(obj, RANK, False, "outside the contract")
    obj = good(RANK, False)
    obj["breakdown"] = {"device_ops": [], "idle_gaps": []}
    refuse(obj, RANK, False, "outside the contract")
    obj = good(BULK, True)
    obj["metrics"]["step_roofline"]["value"] = 140.0
    refuse(obj, BULK, True, "step_roofline")
    obj = good(BULK, True)
    obj["breakdown"]["device_ops"] = [["x", 0.1]] * 11
    refuse(obj, BULK, True, "breakdown")


def test_a_layer_metric_without_source_is_left_out_but_not_all_of_them():
    values = {name: 1.0 for name in result.cell_metrics(BENCH, RANK, True)}
    values["rows_per_batch.rank"] = None
    device = dict(DEVICE, busy_s=1.0, window_s=2.0)
    obj = result.build(BENCH, RANK, True, correct=True, attempted=1, failed=0, values=values,
                       device=device)
    result.validate(obj, BENCH, RANK, True)
    assert "rows_per_batch.rank" not in obj["metrics"]
    obj = result.build(BENCH, RANK, True, correct=True, attempted=1, failed=0,
                       values=dict.fromkeys(values), device=device)
    refuse(obj, RANK, True, "no per-layer metric")


def test_every_cell_reports_setup_another_end_to_end_metric_and_a_layer_metric():
    for cell in BENCH["workloads"]:
        e2e = result.cell_metrics(BENCH, cell["name"], False)
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = result.cell_metrics(BENCH, cell["name"], True)
        assert layers
        moved = {m["name"]: m["moves"] for m in BENCH["per_layer"]}
        assert all(moved[name] in e2e for name in layers)
