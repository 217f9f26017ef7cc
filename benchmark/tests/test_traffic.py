import numpy as np
import pytest

from benchmark import traffic

OPEN = {
    "loop": "open", "rate_per_s": 200,
    "rows": {"kind": "lognormal", "median": 1000, "sigma": 0.5, "min": 200, "max": 4000},
}
SHAPE = {"num_fields": 5, "num_dense_features": 3}
BIG_SEED = 2**31 + 12345  # the driver's seeds do not fit 32 signed bits


def test_percentile_is_numpys_linear_interpolation():
    values = [1.0, 2.0, 3.0, 4.0]
    assert traffic.percentile(values, 50) == 2.5
    assert traffic.percentile(values, 95) == pytest.approx(3.85)
    assert traffic.percentile([7.0], 95) == 7.0


def test_open_window_holds_exactly_rate_times_seconds_arrivals_inside_it():
    due, sizes = traffic.open_schedule(OPEN, BIG_SEED, 20.0)
    assert due.size == sizes.size == 4000
    assert np.all(np.diff(due) > 0) and due[0] > 0 and due[-1] < 20.0


def test_seeds_offer_the_same_segments_in_another_order():
    due_a, sizes_a = traffic.open_schedule(OPEN, 1, 20.0)
    due_b, sizes_b = traffic.open_schedule(OPEN, BIG_SEED, 20.0)
    assert not np.array_equal(sizes_a, sizes_b)
    assert np.array_equal(np.sort(sizes_a), np.sort(sizes_b))

    def segments(due, sizes):
        return {
            (tuple(np.round(due[(due >= s) & (due < s + 1)] - s, 9)),
             tuple(sizes[(due >= s) & (due < s + 1)]))
            for s in range(20)
        }

    assert segments(due_a, sizes_a) == segments(due_b, sizes_b)
    assert len(segments(due_a, sizes_a)) == 20  # no two segments alike
    again, _ = traffic.open_schedule(OPEN, 1, 20.0)
    assert np.array_equal(due_a, again)
    # every segment holds the mix's whole size distribution
    first = sizes_a[due_a < 1.0]
    assert first.size == 200 and first.min() < 300 and first.max() > 3000


def test_sizes_follow_the_mix():
    sizes = traffic.size_set(OPEN["rows"], 4001)
    assert sizes.min() == 200 and sizes.max() == 4000
    assert sizes[2000] == 1000  # the median quantile
    assert np.all(traffic.size_set({"kind": "fixed", "value": 2048}, 7) == 2048)


def test_onoff_keeps_the_count_and_sends_only_in_on_periods():
    mix = dict(OPEN, arrivals={"kind": "onoff", "on_s": 0.5, "off_s": 1.5})
    due, sizes = traffic.open_schedule(mix, 3, 20.0)
    assert due.size == sizes.size and 3900 <= due.size <= 4000
    assert np.all(due % 2.0 < 0.5 + 1e-9)


def test_a_latency_runs_from_the_due_time():
    # The generator's record is (measured, due, sent, finished, rows, fault):
    # a request due at 1.000 s, sent late at 1.004 s and answered at 1.010 s
    # waited 10 ms, of which 4 ms were the generator's.
    measured, due, sent, finished = True, 1.000, 1.004, 1.010
    assert (finished - due) * 1e3 == pytest.approx(10.0)
    assert (sent - due) * 1e3 == pytest.approx(4.0)


def test_payloads_are_a_function_of_seed_and_index_and_never_repeat_a_row():
    a = traffic.Payloads({"sharing": {"kind": "none"}}, SHAPE, BIG_SEED)
    b = traffic.Payloads({"sharing": {"kind": "none"}}, SHAPE, BIG_SEED)
    one = a.make(traffic.STREAM_MEASURED, 5, 64)
    assert one["feat_ids"].dtype == np.int64 and one["feat_ids"].shape == (64, 5)
    assert one["feat_wts"].dtype == np.float32 and one["dense_features"].shape == (64, 3)
    same = b.make(traffic.STREAM_MEASURED, 5, 64)
    assert all(np.array_equal(one[k], same[k]) for k in one)
    rows = np.concatenate([
        a.make(traffic.STREAM_MEASURED, k, 64)["feat_ids"] for k in range(50)
    ] + [a.make(traffic.STREAM_WARMUP, k, 64)["feat_ids"] for k in range(50)])
    assert len({r.tobytes() for r in rows}) == rows.shape[0]


def test_zipf_sharing_draws_rows_from_a_catalog():
    mix = {"sharing": {"kind": "zipf", "catalog": 32, "skew": 1.1}}
    p = traffic.Payloads(mix, SHAPE, 9)
    rows = p.make(traffic.STREAM_MEASURED, 0, 500)["feat_ids"]
    assert len({r.tobytes() for r in rows}) <= 32


def test_the_sample_is_one_median_and_one_cap_request():
    sample = traffic.sample_requests(OPEN, SHAPE, 4)
    assert sample["median"]["feat_ids"].shape[0] == 1000
    assert sample["cap"]["feat_ids"].shape[0] == 4000


def test_mix_files_load(tmp_path):
    import glob
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = glob.glob(os.path.join(here, "traffic", "*.json"))
    assert len(files) >= 4
    for path in files:
        mix = traffic.load_mix(path)
        assert mix["generators"] >= 1 and traffic.size_range(mix["rows"])[2] >= 1
