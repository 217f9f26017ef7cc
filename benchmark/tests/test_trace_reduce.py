import json
import os

import pytest

from benchmark import trace_reduce

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "fixtures", "v5e_dcn_rank_extract.json")


def test_union_merges_overlaps_and_clips_to_the_window():
    events = [(0.0, 2.0, "a"), (1.0, 3.0, "b"), (5.0, 6.0, "c"), (9.5, 12.0, "d"), (-3.0, -1.0, "e")]
    assert trace_reduce.union(events, 0.5, 10.0) == [(0.5, 3.0), (5.0, 6.0), (9.5, 10.0)]


def test_busy_is_a_union_not_a_sum():
    extracted = {
        "window": [10.0, 14.0],
        "devices": {"/device:TPU:0": [
            [9.0, 11.0, "starts before the window"], [10.5, 11.5, "overlaps it"],
            [12.0, 12.5, "inside"], [13.5, 15.0, "ends after the window"],
        ]},
        "host": [[11.5, 12.0, "PjitFunction(step)"]],
    }
    reduced = trace_reduce.reduce(extracted)
    assert reduced["window_s"] == 4.0
    assert reduced["busy_s"] == pytest.approx(1.5 + 0.5 + 0.5)  # a sum would be 4.0
    assert 0.0 < reduced["busy_s"] <= reduced["window_s"]
    gaps = dict((round(s, 6), n) for n, s in reduced["breakdown"]["idle_gaps"])
    assert gaps[0.5] == "PjitFunction(step)" and gaps[1.0] == "unattributed"
    ops = dict(reduced["breakdown"]["device_ops"])
    assert ops["starts before the window"] == pytest.approx(1.0)


def test_no_window_or_no_operation_gives_nothing():
    assert trace_reduce.reduce({"window": None, "devices": {"d": [[0, 1, "x"]]}, "host": []}) is None
    assert trace_reduce.reduce({"window": [0, 1], "devices": {}, "host": []}) is None
    assert trace_reduce.reduce({"window": [0, 1], "devices": {"d": [[2, 3, "x"]]}, "host": []}) is None


def test_busy_is_averaged_over_the_chips():
    extracted = {"window": [0.0, 2.0], "host": [], "devices": {
        "/device:TPU:0": [[0.0, 1.0, "x"]], "/device:TPU:1": [[0.0, 2.0, "x"]]}}
    assert trace_reduce.reduce(extracted)["busy_s"] == pytest.approx(1.5)


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace yet")
def test_the_recorded_v5e_trace():
    with open(FIXTURE) as f:
        recorded = json.load(f)
    reduced = trace_reduce.reduce(recorded["extracted"])
    assert 0.0 < reduced["busy_s"] <= reduced["window_s"]
    assert reduced["busy_s"] == pytest.approx(recorded["busy_s"], rel=1e-9)
    assert reduced["window_s"] == pytest.approx(recorded["window_s"], rel=1e-9)
    assert len(reduced["breakdown"]["device_ops"]) <= 10
    # the sum of the operations' durations is what cause 4 reported as busy
    total = sum(e[1] - e[0] for events in recorded["extracted"]["devices"].values() for e in events)
    assert total >= reduced["busy_s"]
