"""From a profiler trace (`.xplane.pb`) to busy time, the top device
operations and the longest idle gaps.

Two steps, so that the arithmetic can be checked on a small recorded trace
(`fixtures/`) without the profiler: `extract` reads the planes into plain
lists of intervals, `reduce` does the arithmetic.

What counts as a device operation: an event on the line "XLA Ops" of a plane
named "/device:TPU:<n>" (one plane per chip; the planes' other lines, "XLA
Modules", "Steps" and the like, cover the same time again and would count it
twice). On the CPU backend, which a rehearsal runs on, there is no device
plane; there an event of the host plane that carries an `hlo_op` stat is an
operation.

The window is the `bench_window` annotation that chip_child.py holds open
from just after the profiler started until just before it stops: an interval
on the profiler's own clock, inside the capture and inside the load. `busy_s`
is the length of the UNION of the operations' intervals clipped to that
window, averaged over the device planes, so it cannot pass `window_s`.
"""

from __future__ import annotations

import glob
import os

WINDOW_EVENT = "bench_window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ROWS = 10


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def extract(path: str) -> dict:
    """{"window": [start, end] | None, "devices": {plane: [[start, end, name]]},
    "host": [[start, end, name]]}, times in seconds on the profiler's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window = None
    devices: dict[str, list] = {}
    host: list = []
    cpu_ops: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices.setdefault(plane.name, []).extend(
                    [e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name]
                    for e in line.events
                )
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_EVENT:
                        window = [e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9]
                    elif e.duration_ns <= 0 or e.name.startswith("$"):
                        continue  # markers, and the python tracer's frames
                    elif any(k == "hlo_op" for k, _ in e.stats):
                        cpu_ops.append([e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name])
                    else:
                        host.append([e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name])
    if not devices and cpu_ops:
        devices["/host:CPU (rehearsal)"] = cpu_ops
    return {"window": window, "devices": devices, "host": host}


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The merged intervals of `intervals` ((start, end, ...) each), clipped
    to [lo, hi], in rising order."""
    merged: list[list[float]] = []
    for start, end in sorted((max(i[0], lo), min(i[1], hi)) for i in intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def _label(gap: tuple[float, float], host: list) -> str:
    """The host event that covers most of `gap`, else `unattributed`."""
    best, best_overlap = "unattributed", 0.0
    for start, end, name in host:
        overlap = min(end, gap[1]) - max(start, gap[0])
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best if best_overlap >= 0.5 * (gap[1] - gap[0]) else "unattributed"


def reduce(extracted: dict) -> dict | None:
    """window_s, busy_s, and the breakdown; None when the trace holds no
    window or no device operation inside it."""
    window, devices = extracted["window"], extracted["devices"]
    if window is None or not devices:
        return None
    lo, hi = window
    busy_per_device = []
    op_seconds: dict[str, float] = {}
    gaps: list[tuple[float, float]] = []
    for events in devices.values():
        merged = union(events, lo, hi)
        busy_per_device.append(sum(b - a for a, b in merged))
        for start, end, name in events:
            inside = min(end, hi) - max(start, lo)
            if inside > 0:
                op_seconds[name] = op_seconds.get(name, 0.0) + inside
        edges = [lo] + [t for pair in merged for t in pair] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    busy = sum(busy_per_device) / len(busy_per_device)
    if busy <= 0:
        return None
    scale = 1.0 / len(devices)
    top_ops = sorted(op_seconds.items(), key=lambda kv: -kv[1])[:ROWS]
    long_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:ROWS]
    return {
        "window_s": hi - lo,
        "busy_s": busy,
        "breakdown": {
            "device_ops": [[name, seconds * scale] for name, seconds in top_ops],
            "idle_gaps": [[_label(g, extracted["host"]), g[1] - g[0]] for g in long_gaps],
        },
    }
