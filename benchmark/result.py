"""The result line: one builder, one validator, one printer.

`validate` holds an object to the contract the driver reads (the five keys,
the cell's metrics as finite values with their units, the device block, and
in a traced run `0 < busy_s <= window_s`). Every end-to-end metric has to be
there; a per-layer metric whose reader found nothing to read is left out, as
the contract has it, but one at least has to be there. `emit` is the only function of
the benchmark that prints a last line: it validates first, prints the object
with `allow_nan=False`, flushes and leaves through `os._exit`, so that no
`atexit` hook and no library can print after it. A run that cannot produce a
valid object goes through `fail`: the reason on a line of its own, a non-zero
exit code and no object at all.

Nothing here imports jax.
"""

from __future__ import annotations

import json
import math
import os
import sys

RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
BREAKDOWN_KEYS = ("device_ops", "idle_gaps")
BREAKDOWN_ROWS = 10


class Malformed(ValueError):
    """The object is not one the driver would accept."""


def _is_number(x) -> bool:
    return (
        isinstance(x, (int, float))
        and not isinstance(x, bool)
        and math.isfinite(x)
    )


def cell_metrics(benchmark: dict, cell: str, traced: bool) -> dict[str, str]:
    """name -> unit of the metrics `benchmark` gives `cell`: its end-to-end
    metrics in an untraced run, its per-layer metrics in a traced one. A
    metric without a `workloads` key belongs to every cell."""
    group = benchmark["per_layer" if traced else "end_to_end"]
    return {
        m["name"]: m["unit"]
        for m in group
        if "workloads" not in m or cell in m["workloads"]
    }


def validate(obj: dict, benchmark: dict, cell: str, traced: bool) -> None:
    """Raise Malformed with the first fault found; return None on a good one."""
    if not isinstance(obj, dict):
        raise Malformed("the result is not an object")
    for key in RESULT_KEYS:
        if key not in obj:
            raise Malformed(f"key {key!r} is missing")
    extra = set(obj) - set(RESULT_KEYS) - ({"breakdown"} if traced else set())
    if extra:
        raise Malformed(f"keys outside the contract: {sorted(extra)}")
    if not isinstance(obj["correct"], bool):
        raise Malformed("correct is not a boolean")
    for key in ("attempted", "failed"):
        if not isinstance(obj[key], int) or isinstance(obj[key], bool) or obj[key] < 0:
            raise Malformed(f"{key} is not a count: {obj[key]!r}")
    if obj["attempted"] < 1:
        raise Malformed("nothing was attempted")
    if obj["failed"] > obj["attempted"]:
        raise Malformed("more failed than attempted")

    want = cell_metrics(benchmark, cell, traced)
    if not want:
        raise Malformed(f"BENCHMARK.json gives cell {cell!r} no metric")
    got = obj["metrics"]
    if not isinstance(got, dict):
        raise Malformed("metrics is not an object")
    if traced and not set(got) & set(want):
        raise Malformed("no per-layer metric of the cell is there")
    for name, unit in want.items():
        entry = got.get(name)
        if traced and entry is None:
            continue
        if not isinstance(entry, dict):
            raise Malformed(f"metric {name!r} is missing")
        if set(entry) != {"value", "unit"}:
            raise Malformed(f"metric {name!r} has keys {sorted(entry)}")
        if not _is_number(entry["value"]):
            raise Malformed(f"metric {name!r} is not a finite number: {entry['value']!r}")
        if entry["unit"] != unit:
            raise Malformed(f"metric {name!r} has unit {entry['unit']!r}, want {unit!r}")
        if ("roofline" in name or "mfu" in name) and entry["value"] > 105.0:
            raise Malformed(f"{name!r} reads {entry['value']} % of a peak")
    stray = set(got) - set(want)
    if stray:
        raise Malformed(f"metrics the cell does not have: {sorted(stray)}")

    device = obj["device"]
    if not isinstance(device, dict):
        raise Malformed("device is not an object")
    for key in DEVICE_KEYS:
        if key not in device:
            raise Malformed(f"device.{key} is missing")
    for key in ("platform", "kind"):
        if not isinstance(device[key], str) or not device[key]:
            raise Malformed(f"device.{key} is not a name")
    if not isinstance(device["count"], int) or device["count"] < 1:
        raise Malformed(f"device.count is {device['count']!r}")
    peak = device["memory_peak_bytes"]
    if not _is_number(peak) or peak <= 0:
        raise Malformed(f"device.memory_peak_bytes is {peak!r}")
    allowed = set(DEVICE_KEYS) | ({"busy_s", "window_s"} if traced else set())
    if set(device) - allowed:
        raise Malformed(f"device keys outside the contract: {sorted(set(device) - allowed)}")
    if traced:
        busy, window = device.get("busy_s"), device.get("window_s")
        if not _is_number(busy) or not _is_number(window):
            raise Malformed(f"busy_s {busy!r} / window_s {window!r} are not numbers")
        if not 0.0 < busy <= window:
            raise Malformed(f"busy_s {busy} is not in (0, window_s {window}]")
        if "breakdown" in obj:
            breakdown = obj["breakdown"]
            if not isinstance(breakdown, dict) or set(breakdown) != set(BREAKDOWN_KEYS):
                raise Malformed("breakdown does not hold device_ops and idle_gaps")
            for key in BREAKDOWN_KEYS:
                rows = breakdown[key]
                if not isinstance(rows, list) or len(rows) > BREAKDOWN_ROWS:
                    raise Malformed(f"breakdown.{key} is not a list of at most {BREAKDOWN_ROWS}")
                for row in rows:
                    if (
                        not isinstance(row, list) or len(row) != 2
                        or not isinstance(row[0], str) or not _is_number(row[1])
                    ):
                        raise Malformed(f"breakdown.{key} row {row!r} is not [name, seconds]")
    # What json.dumps would refuse or write as a bare word is caught here,
    # before anything is printed.
    try:
        json.dumps(obj, allow_nan=False)
    except (TypeError, ValueError) as exc:
        raise Malformed(f"not JSON: {exc}") from exc


def build(
    benchmark: dict, cell: str, traced: bool, *, correct: bool, attempted: int,
    failed: int, values: dict[str, float], device: dict, breakdown: dict | None = None,
) -> dict:
    """The object for `cell` from measured `values` (metric name -> number).
    Values of metrics the cell does not have are dropped; a missing one is
    left missing, for `validate` to judge."""
    want = cell_metrics(benchmark, cell, traced)
    obj = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {
            name: {"value": values[name], "unit": unit}
            for name, unit in want.items()
            if values.get(name) is not None
        },
        "device": device,
    }
    if traced and breakdown is not None:
        obj["breakdown"] = breakdown
    return obj


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def fail(reason: str, code: int = 1) -> None:
    """Leave with no result object: the reason, then a non-zero exit."""
    say(f"FAILED: {reason}")
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


def emit(obj: dict, benchmark: dict, cell: str, traced: bool) -> None:
    """Print `obj` as the last line and leave, or `fail` if it is malformed."""
    try:
        validate(obj, benchmark, cell, traced)
        line = json.dumps(obj, allow_nan=False)
    except Malformed as exc:
        fail(f"result object refused by validate: {exc}", code=4)
    sys.stderr.flush()
    sys.stdout.write(line + "\n")
    sys.stdout.flush()
    os._exit(0)
