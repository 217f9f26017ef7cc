"""One general traffic generator, driven by a mix file under `traffic/`.

A mix file is a JSON object:

  loop        "open" (arrivals on a schedule, at `rate_per_s`) or "closed"
              (`callers` callers, each sending its next request when the
              last one is answered)
  warmup_requests  unmeasured requests of the mix's own sizes, sent by the
              generators before the window
  rows        rows per request: {"kind": "fixed", "value": n} or
              {"kind": "lognormal", "median", "sigma", "min", "max"}
  arrivals    open loop only: {"kind": "poisson"} (exponential gaps, scaled
              so that every second holds exactly `rate_per_s` arrivals) or
              {"kind": "onoff", "on_s", "off_s"} (bursts: the same mean rate,
              sent only in the on-periods)
  sharing     {"kind": "none"}: every row of every request is new; or
              {"kind": "zipf", "catalog": n, "skew": s}: rows drawn from a
              seeded catalog of n rows, rank-frequency exponent s
  generators  processes the load is spread over

What `--seed` changes is the order and the content, never the amount of work:
an open-loop window is a sequence of one-second segments, each a fixed
function of the mix (its arrivals' gaps one fixed exponential sample, its sizes
the quantiles of the mix's distribution in a fixed shuffle), and the seed
decides the order of the segments and draws the rows. So two seeds offer the
same requests and the same bursts at other times, and a window of whole
seconds always holds exactly rate * seconds arrivals. Given their number,
Poisson arrivals in a segment are uniform order statistics, which is what the
normalised exponential gaps below are: the burstiness of a Poisson process
inside a second is kept, its variation of the count from second to second and
from run to run is taken out. A tail read from such a window is the tail of
one fixed realisation of the traffic, met in another order by every seed.

Nothing here imports jax or the program.
"""

from __future__ import annotations

import json
import statistics

import numpy as np

ID_SPACE = 1 << 40  # the reference client's hashed-id range (client/bench.py)
SEGMENT_S = 1.0  # an open-loop window is made of segments of this length
STREAM_MEASURED, STREAM_WARMUP, STREAM_SAMPLE, STREAM_CATALOG = 0, 1, 2, 3


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed'")
    if mix["loop"] == "open" and not mix.get("rate_per_s", 0) > 0:
        raise ValueError(f"{path}: an open loop needs rate_per_s > 0")
    if mix["loop"] == "closed" and not mix.get("callers", 0) >= 1:
        raise ValueError(f"{path}: a closed loop needs callers >= 1")
    return mix


# ------------------------------------------------------------------ sizes


def size_set(rows: dict, count: int) -> np.ndarray:
    """`count` request sizes: the (i + 0.5) / count quantiles of the mix's
    distribution, in rising order. A function of the mix alone."""
    if rows["kind"] == "fixed":
        return np.full(count, int(rows["value"]), np.int64)
    if rows["kind"] == "lognormal":
        normal = statistics.NormalDist()
        z = np.array([normal.inv_cdf((i + 0.5) / count) for i in range(count)])
        sizes = float(rows["median"]) * np.exp(float(rows["sigma"]) * z)
        return np.clip(np.rint(sizes), rows["min"], rows["max"]).astype(np.int64)
    raise ValueError(f"unknown rows kind {rows['kind']!r}")


def size_range(rows: dict) -> tuple[int, int, int]:
    """(smallest, median, largest) request of the mix."""
    if rows["kind"] == "fixed":
        return (int(rows["value"]),) * 3
    return int(rows["min"]), int(rows["median"]), int(rows["max"])


# ---------------------------------------------------------------- arrivals


def _warp_onoff(times: np.ndarray, on_s: float, off_s: float) -> np.ndarray:
    """Map times of a steady process onto one that sends only in on-periods,
    at the same mean rate: time t of `on` seconds lands in period t // on."""
    share = on_s / (on_s + off_s)
    active = times * share  # seconds of on-time used up by t
    return (active // on_s) * (on_s + off_s) + active % on_s


def open_schedule(mix: dict, seed: int, seconds: float) -> tuple[np.ndarray, np.ndarray]:
    """(due times in [0, seconds), rows per request) of an open-loop window.

    The window is made of segments of SEGMENT_S seconds. Segment j is a fixed
    function of the mix and of j: round(rate * SEGMENT_S) arrivals
    whose gaps are one sample of exponentials scaled to fill the segment, with
    the sizes of `size_set` in a fixed shuffle. `seed` only decides in which
    order the segments come. Queues form over tens of milliseconds, so two
    seeds meet the same bursts at other times of the window."""
    per_segment = max(int(round(float(mix["rate_per_s"]) * SEGMENT_S)), 1)
    segments = int(np.ceil(seconds / SEGMENT_S - 1e-9))
    arrivals = mix.get("arrivals", {"kind": "poisson"})
    if arrivals["kind"] not in ("poisson", "onoff"):
        raise ValueError(f"unknown arrivals kind {arrivals['kind']!r}")
    sizes_sorted = size_set(mix["rows"], per_segment)
    order = np.random.default_rng([seed, STREAM_MEASURED, 0]).permutation(segments)
    due, sizes = [], []
    for slot, j in enumerate(order):
        fixed = np.random.default_rng([0, per_segment, int(j)])
        gaps = fixed.exponential(size=per_segment + 1)
        offsets = np.cumsum(gaps[:-1]) * (SEGMENT_S / gaps.sum())
        due.append(slot * SEGMENT_S + offsets)
        sizes.append(fixed.permutation(sizes_sorted))
    due, sizes = np.concatenate(due), np.concatenate(sizes)
    if arrivals["kind"] == "onoff":
        due = _warp_onoff(due, float(arrivals["on_s"]), float(arrivals["off_s"]))
    keep = due < seconds
    return due[keep], sizes[keep]


def closed_sizes(mix: dict, seed: int, count: int = 512) -> np.ndarray:
    """Sizes a closed loop cycles through: request k has sizes[k % count]. A
    window goes round the cycle several times, so every seed offers the same
    sizes, in its own order."""
    order = np.random.default_rng([seed, STREAM_MEASURED, 0])
    return order.permutation(size_set(mix["rows"], count))


# ---------------------------------------------------------------- payloads


def _catalog(shape: dict, sharing: dict, seed: int) -> dict:
    rng = np.random.default_rng([seed, STREAM_CATALOG])
    arrays = fresh_rows(rng, int(sharing["catalog"]), shape)
    p = np.arange(1, int(sharing["catalog"]) + 1, dtype=np.float64) ** -float(sharing["skew"])
    arrays["_p"] = p / p.sum()
    return arrays


def fresh_rows(rng: np.random.Generator, rows: int, shape: dict) -> dict:
    """`rows` new candidate rows in the program's wire shape: hashed ids
    uniform over the id space, weights and dense features uniform in [0, 1)."""
    fields = int(shape["num_fields"])
    arrays = {
        "feat_ids": rng.integers(0, ID_SPACE, size=(rows, fields), dtype=np.int64),
        "feat_wts": rng.random((rows, fields), dtype=np.float32),
    }
    dense = int(shape.get("num_dense_features", 0))
    if dense:
        arrays["dense_features"] = rng.random((rows, dense), dtype=np.float32)
    return arrays


class Payloads:
    """Request `index` of `stream` under `seed`, the same in whichever
    process builds it."""

    def __init__(self, mix: dict, shape: dict, seed: int):
        self.shape = shape
        self.seed = seed
        self.sharing = mix.get("sharing", {"kind": "none"})
        if self.sharing["kind"] not in ("none", "zipf"):
            raise ValueError(f"unknown sharing kind {self.sharing['kind']!r}")
        self.catalog = (
            _catalog(shape, self.sharing, seed) if self.sharing["kind"] == "zipf" else None
        )

    def make(self, stream: int, index: int, rows: int) -> dict:
        rng = np.random.default_rng([self.seed, stream, 1, index])
        if self.catalog is None:
            return fresh_rows(rng, rows, self.shape)
        pick = rng.choice(self.catalog["_p"].size, size=rows, p=self.catalog["_p"])
        return {
            key: np.ascontiguousarray(value[pick])
            for key, value in self.catalog.items() if key != "_p"
        }


def sample_requests(mix: dict, shape: dict, seed: int) -> dict[str, dict]:
    """The correctness sample: one request at the mix's median size and one
    at its cap, always of new rows."""
    _, median, cap = size_range(mix["rows"])
    plain = Payloads({"sharing": {"kind": "none"}}, shape, seed)
    return {
        "median": plain.make(STREAM_SAMPLE, 0, median),
        "cap": plain.make(STREAM_SAMPLE, 1, cap),
    }


# -------------------------------------------------------------- arithmetic


def percentile(values, q: float) -> float:
    """numpy's linear-interpolated percentile, as client/bench.py reports it."""
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))
