"""Device-utilization attribution plane: occupancy ledger, gap waterfall,
on-demand deep capture.

A bench's `achieved_fraction_of_device_limit` exists only as an offline
artifact, and the aggregate `phases_us` sums cannot say *when* the device
sat idle or *why*. Closing the gap between the device's limit and the
served path needs a live, continuously-served decomposition of wall time;
"Scaling TensorFlow to 300 million predictions per second" (PAPERS.md)
finds its batching and transport amortization wins by attributing exactly
this idle time.

Three layers, all off by default and armed by the `[utilization]` config
section (one attribute read per batcher hot-path hook when off — the
tracing/cache/overload precedent):

- **OccupancyLedger**: per-device busy/idle timeline fed by the batcher's
  EXISTING dispatch/jitcall/readback phase sites — ONE interval append
  per completed batch (`note_batch`), ring-bounded, injectable clock.
  Each batch contributes a (stage-start, readback-issued, readback-done)
  triple, so the busy union splits into host-dispatch/H2D, device
  compute, and D2H wait. The idle time BETWEEN busy intervals is
  attributed to its blocking cause from cheap wait-interval records the
  batcher leaves while it idles: `queue_empty` (no work arrived — the
  transport/client-bound share), `host_pack` (the host was
  assembling/coalescing while the device starved), `readback_wait`
  (pipeline saturated behind in-flight readbacks), `admission_shed`
  (traffic existed but admission refused it). An in-flight
  pipeline-depth gauge (`in_flight`/`max_in_flight`) rides the same
  hooks.
- **Gap waterfall**: a windowed decomposition of wall time into
  device / h2d_dispatch / d2h / idle-by-cause / other components whose
  sum equals the window's wall time BY CONSTRUCTION (the residual is
  reported as `other`, never hidden), plus a live
  `achieved_fraction_of_device_limit` estimate — calibrated against the
  bench's `device_step_us` table when one is provided (per-bucket pure
  device step x batches served), busy-fraction otherwise (labeled).
  Served as `GET /utilz`, a `utilization` block in `/monitoring`,
  `dts_tpu_utilization_*` Prometheus series, and a per-device counter
  track in the `/tracez?format=chrome` Perfetto export.
- **On-demand deep capture**: `POST /profilez/start?seconds=N` runs a
  `jax.profiler.trace` capture (CPU-safe; artifact dir returned;
  concurrent captures refused with 409) and simultaneously samples every
  host thread's Python stack (HostStackSampler) so one call captures the
  device and host sides of the same window together.

The ledger is jax-free; only ProfilerCapture imports jax, lazily, when a
capture actually starts.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import deque

# Idle-gap blocking causes, in reporting order.
GAP_CAUSES = ("queue_empty", "host_pack", "readback_wait", "admission_shed")

# Gap-length histogram edges (milliseconds, cumulative-le semantics).
_GAP_LE_MS = (1.0, 10.0, 100.0, 1000.0)


def _clamp(t0: float, t1: float, w0: float, w1: float) -> float:
    """Length of (t0, t1) ∩ (w0, w1)."""
    return max(0.0, min(t1, w1) - max(t0, w0))


def _merge_intervals(spans: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted union of possibly-overlapping (t0, t1) spans."""
    if not spans:
        return []
    spans = sorted(spans)
    out = [spans[0]]
    for t0, t1 in spans[1:]:
        if t0 <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t1))
        else:
            out.append((t0, t1))
    return out


def _overlap_with_union(union: list[tuple[float, float]], t0: float, t1: float) -> float:
    """Seconds of (t0, t1) covered by a sorted disjoint union."""
    total = 0.0
    for u0, u1 in union:
        if u0 >= t1:
            break
        total += _clamp(u0, u1, t0, t1)
    return total


def _normalize_step_table(table: dict | None) -> dict[int, float]:
    """ONE normalization of a per-bucket device-step table: accepts
    {bucket: us} or the range form {bucket: [lo, hi]} (midpoint); skips
    non-positive entries (a 0.0 step can only divide-by-zero downstream).
    Shared by load_calibration and set_calibration so the two install
    paths can never disagree on the same artifact."""
    out: dict[int, float] = {}
    for bucket, val in (table or {}).items():
        if isinstance(val, (list, tuple)) and len(val) == 2:
            val = (float(val[0]) + float(val[1])) / 2.0
        if val and float(val) > 0:
            out[int(bucket)] = float(val)
    return out


def load_calibration(path: str) -> dict[int, float]:
    """Per-bucket pure device step (us) from a JSON table: either a range
    form (`device_step_us: {bucket: [lo, hi]}` — midpoint used) or a
    measured table (`{bucket: us}`). Empty dict on
    any trouble — calibration is an enrichment, never a dependency."""
    try:
        with open(path) as f:
            doc = json.load(f)
        table = doc.get("device_step_us", doc) if isinstance(doc, dict) else {}
        return _normalize_step_table(table)
    except Exception:  # noqa: BLE001 — absent/corrupt table = no calibration
        return {}


def _split_span(
    waits, open_waits, sheds, g0: float, g1: float,
    residual_to_host_pack: bool = True,
) -> dict[str, float]:
    """Per-cause seconds for the idle span (g0, g1): overlap with the
    recorded wait intervals (open waits count their elapsed part),
    residual to host_pack (optional — startup/in-flight tails leave their
    residual unattributed), queue_empty share reassigned to
    admission_shed when sheds fired inside the span. Pure function over
    the passed collections, so callers can use live rings (under the
    ledger lock) or snapshots (outside it) identically."""
    split = {c: 0.0 for c in GAP_CAUSES}
    # Closed waits are append-ordered by end time: scan from the right
    # and stop once waits end before the gap starts.
    for cause, w0, w1 in reversed(waits):
        if w1 <= g0:
            break
        split[cause] += _clamp(w0, w1, g0, g1)
    for cause, w0 in open_waits:
        split[cause] += _clamp(w0, g1, g0, g1)
    gap = g1 - g0
    explained = sum(split.values())
    if explained > gap > 0:
        # Concurrent waits (coalesce fill + free-ride) can overlap;
        # scale so attribution never exceeds the gap itself.
        scale = gap / explained
        split = {c: s * scale for c, s in split.items()}
        explained = gap
    if residual_to_host_pack:
        split["host_pack"] += max(0.0, gap - explained)
    if split["queue_empty"] > 0 and any(g0 <= t <= g1 for t in sheds):
        split["admission_shed"] += split["queue_empty"]
        split["queue_empty"] = 0.0
    return split


class OccupancyLedger:
    """Busy/idle timeline + idle-gap attribution for one device.

    Hot-path feeders (the batcher, armed only):
    - ``wait_begin(cause)`` / ``wait_end(token)`` around the batcher's
      idle waits (queue-empty block, coalesce fill, pipeline free-ride) —
      two clock reads per wait, paid only while the device is idle
      anyway;
    - ``note_shed()`` at every admission refusal (point event);
    - ``depth_inc()`` / ``depth_dec()`` around each batch's
      dispatch->readback life (the pipeline-depth gauge);
    - ``note_batch(stage_t0, issue_t0, done_t, bucket, candidates,
      d2h_wait_s)`` ONCE per completed batch, from the completer — the
      single interval append the plane is built on.

    Idle-gap attribution: when a batch's busy interval opens a gap after
    the previous busy union, the gap's seconds are split across causes by
    overlap with the recorded wait intervals; the unexplained residual is
    ``host_pack`` (the host was doing per-batch work — pad/pack/digest —
    whenever it was neither waiting nor dispatching). A gap containing
    admission-shed events moves its queue_empty share to
    ``admission_shed``: the queue was empty because traffic was refused,
    not absent. Each gap lands in a per-cause histogram under its
    dominant (largest-share) cause.

    Everything is ring-bounded (``ring`` batches/gaps/waits) and clocked
    by an injectable ``clock`` so tests drive it deterministically.
    """

    def __init__(
        self,
        device: str | None = None,
        ring: int = 4096,
        clock=time.perf_counter,
        calibration: dict[int, float] | None = None,
        window_s: float = 60.0,
    ):
        self.device = device or "device:0"
        # Mesh serving mode (ISSUE 13): the per-chip device list when
        # the ledger attributes a MESH's occupancy. SPMD batches occupy
        # every chip simultaneously, so each listed device carries the
        # same busy timeline — snapshot() adds a per_device block and
        # the Perfetto export emits one counter track per chip. None =
        # single-device (the historical surface, unchanged).
        self.devices: list[str] | None = None
        self.window_s = float(window_s)
        self._clock = clock
        self._lock = threading.Lock()
        self._started_t = clock()
        # (stage_t0, issue_t0, done_t, bucket, candidates, d2h_wait_s)
        self._ring: deque[tuple] = deque(maxlen=ring)
        # (g0, g1, dominant_cause, per-cause seconds tuple aligned with
        # GAP_CAUSES)
        self._gaps: deque[tuple] = deque(maxlen=ring)
        # (cause, w0, w1) closed wait intervals, append-ordered by w1.
        self._waits: deque[tuple] = deque(maxlen=ring)
        self._open_waits: dict[int, tuple[str, float]] = {}
        self._wait_seq = 0
        self._sheds: deque[float] = deque(maxlen=ring)
        self._busy_until: float | None = None
        # Lifetime counters (ring-independent).
        self.batches = 0
        self.candidates = 0
        self.busy_s = 0.0
        self.gap_s = {c: 0.0 for c in GAP_CAUSES}
        self.gap_counts = {c: 0 for c in GAP_CAUSES}
        self._gap_hist = {c: [0] * (len(_GAP_LE_MS) + 1) for c in GAP_CAUSES}
        self.in_flight = 0
        self.max_in_flight = 0
        self.sheds = 0
        self._calibration = dict(calibration or {})

    # ------------------------------------------------------------- feeders

    def wait_begin(self, cause: str) -> int:
        now = self._clock()
        with self._lock:
            self._wait_seq += 1
            token = self._wait_seq
            self._open_waits[token] = (cause, now)
        return token

    def wait_end(self, token: int) -> None:
        now = self._clock()
        with self._lock:
            entry = self._open_waits.pop(token, None)
            if entry is not None:
                self._waits.append((entry[0], entry[1], now))

    def note_shed(self) -> None:
        now = self._clock()
        with self._lock:
            self.sheds += 1
            self._sheds.append(now)

    def depth_inc(self) -> None:
        with self._lock:
            self.in_flight += 1
            self.max_in_flight = max(self.max_in_flight, self.in_flight)

    def depth_dec(self) -> None:
        with self._lock:
            self.in_flight = max(0, self.in_flight - 1)

    def set_calibration(self, table: dict) -> None:
        """Install/refresh the per-bucket device-step table (us). Accepts
        {bucket: us} or the range {bucket: [lo, hi]} form;
        non-positive values are skipped (same normalizer as
        load_calibration)."""
        clean = _normalize_step_table(table)
        with self._lock:
            self._calibration = clean

    def note_batch(
        self,
        stage_t0: float,
        issue_t0: float,
        done_t: float,
        bucket: int = 0,
        candidates: int = 0,
        d2h_wait_s: float = 0.0,
    ) -> None:
        """ONE interval append per completed batch (from the completer):
        closes the idle gap since the previous busy union, extends the
        union, and records the batch for the windowed waterfall."""
        with self._lock:
            self.batches += 1
            self.candidates += int(candidates)
            first = self._busy_until is None
            prev_end = self._busy_until if not first else self._started_t
            if stage_t0 > prev_end:
                # The span before the FIRST batch is startup, not an
                # attributable idle gap: only its wait-explained share is
                # recorded (the waterfall's `other` residual carries the
                # rest); between-batch gaps charge their residual to
                # host_pack (the host was doing per-batch work whenever
                # it was neither waiting nor dispatching).
                self._close_gap_locked(
                    prev_end, stage_t0, residual_to_host_pack=not first
                )
            self.busy_s += max(0.0, done_t - max(stage_t0, prev_end))
            self._busy_until = max(prev_end, done_t)
            self._ring.append(
                (stage_t0, issue_t0, done_t, int(bucket), int(candidates),
                 max(0.0, float(d2h_wait_s)))
            )

    # ----------------------------------------------------- gap attribution

    def _close_gap_locked(
        self, g0: float, g1: float, residual_to_host_pack: bool = True
    ) -> None:
        split = _split_span(
            self._waits, self._open_waits.values(), self._sheds,
            g0, g1, residual_to_host_pack,
        )
        attributed = sum(split.values())
        if attributed <= 0:
            return  # fully-unattributed startup span: waterfall `other`
        dominant = max(GAP_CAUSES, key=lambda c: split[c])
        self.gap_s[dominant] += attributed
        self.gap_counts[dominant] += 1
        hist = self._gap_hist[dominant]
        gap_ms = attributed * 1e3
        for i, le in enumerate(_GAP_LE_MS):
            if gap_ms <= le:
                hist[i] += 1
                break
        else:
            hist[-1] += 1
        self._gaps.append(
            (g0, g1, dominant, tuple(split[c] for c in GAP_CAUSES))
        )

    # ------------------------------------------------------------- readers

    def waterfall(self, window_s: float | None = None) -> dict:
        """Windowed wall-time decomposition. Components sum to the
        window's wall time by construction: wall = busy (split into
        h2d_dispatch / device / d2h) + per-cause idle + `other` (idle the
        ring no longer covers, e.g. pre-first-batch time) — the residual
        is REPORTED, never folded into a real component."""
        now = self._clock()
        # Snapshot under the lock, compute OUTSIDE it: the same lock
        # serializes the batcher/completer hot-path hooks, and a
        # Prometheus scrape must not stall serving for an
        # O(ring log ring) merge (the chrome_counter_events pattern).
        with self._lock:
            window = float(window_s if window_s is not None else self.window_s)
            ring = list(self._ring)
            gaps = list(self._gaps)
            waits = list(self._waits)
            open_waits = list(self._open_waits.values())
            sheds = list(self._sheds)
            busy_until = self._busy_until
            started_t = self._started_t
            calibration = self._calibration
            in_flight = self.in_flight
        w0 = max(now - window, started_t)
        wall = max(now - w0, 1e-9)
        batches = [b for b in ring if b[2] > w0]
        busy_union = _merge_intervals(
            [(max(b[0], w0), min(b[2], now)) for b in batches
             if min(b[2], now) > max(b[0], w0)]
        )
        busy = sum(t1 - t0 for t0, t1 in busy_union)
        # Busy sub-split: host-dispatch/H2D (stage start -> readback
        # issued) and D2H wait (the completer's measured blocked
        # fetch); device compute is the remainder of the busy union.
        dispatch_raw = sum(
            _clamp(b[0], min(b[1], b[2]), w0, now) for b in batches
        )
        d2h_raw = sum(
            min(b[5], _clamp(b[0], b[2], w0, now)) for b in batches
        )
        sub = dispatch_raw + d2h_raw
        if sub > busy > 0:
            # Pipelined batches overlap, so per-batch sub-spans can
            # exceed the union: scale into it.
            dispatch_raw *= busy / sub
            d2h_raw *= busy / sub
        device = max(0.0, busy - dispatch_raw - d2h_raw)
        idle = {c: 0.0 for c in GAP_CAUSES}
        for g0, g1, _dom, split in gaps:
            full = g1 - g0
            if g1 <= w0 or full <= 0:
                continue
            vis = _clamp(g0, g1, w0, now)
            # Out-of-order completions can retroactively cover a
            # recorded gap: only the still-idle part counts.
            vis -= _overlap_with_union(busy_union, max(g0, w0), min(g1, now))
            if vis <= 0:
                continue
            frac = vis / full
            for c, s in zip(GAP_CAUSES, split):
                idle[c] += s * frac
        # Live tail since the last completed batch: residual idle goes to
        # host_pack only when that is what it means — after at least one
        # batch completed (pre-first-batch time is startup, matching
        # note_batch's exemption) and with nothing in flight (an
        # executing batch's span is busy-in-waiting, not host work; it
        # stays `other` until its completion records it as busy).
        tail0 = max(busy_until if busy_until is not None else started_t, w0)
        if now > tail0:
            tail_split = _split_span(
                waits, open_waits, sheds, tail0, now,
                residual_to_host_pack=(
                    busy_until is not None and in_flight == 0
                ),
            )
            for c, s in tail_split.items():
                idle[c] += s
        other = max(0.0, wall - busy - sum(idle.values()))
        components = {
            "device": device,
            "h2d_dispatch": dispatch_raw,
            "d2h": d2h_raw,
            **{f"idle_{c}": idle[c] for c in GAP_CAUSES},
            "other": other,
        }
        total = sum(components.values())
        # Calibrated device-limit fraction: pure per-bucket device
        # step x batches served in the window, over wall — the live
        # counterpart of the bench's achieved_fraction_of_device_limit.
        calibrated = None
        if calibration:
            est = sum(calibration.get(b[3], 0.0) for b in batches) / 1e6
            calibrated = est / wall
        busy_fraction = busy / wall
        return {
            "window_s": round(window, 3),
            "wall_s": round(wall, 6),
            "components_s": {k: round(v, 6) for k, v in components.items()},
            "sum_s": round(total, 6),
            "sum_over_wall": round(total / wall, 6),
            "busy_fraction": round(busy_fraction, 6),
            "batches": len(batches),
            "achieved_fraction_of_device_limit": round(
                calibrated if calibrated is not None else busy_fraction, 6
            ),
            "calibration": (
                "device_step_table" if calibrated is not None
                else "busy_fraction"
            ),
        }

    def snapshot(self, window_s: float | None = None) -> dict:
        wf = self.waterfall(window_s)
        per_device = (
            {
                d: {"busy_fraction": wf["busy_fraction"]}
                for d in self.devices
            }
            if self.devices else None
        )
        with self._lock:
            gaps = {
                c: {
                    "count": self.gap_counts[c],
                    "total_s": round(self.gap_s[c], 6),
                    "le_ms": dict(
                        zip([str(le) for le in _GAP_LE_MS] + ["+Inf"],
                            self._gap_hist[c])
                    ),
                }
                for c in GAP_CAUSES
            }
            out = {
                "enabled": True,
                "device": self.device,
                "in_flight": self.in_flight,
                "max_in_flight": self.max_in_flight,
                "batches": self.batches,
                "candidates": self.candidates,
                "busy_s": round(self.busy_s, 6),
                "sheds": self.sheds,
                "calibrated": bool(self._calibration),
                "idle_gaps": gaps,
                "waterfall": wf,
            }
        if per_device is not None:
            out["devices"] = list(self.devices)
            out["per_device"] = per_device
            out["occupancy_attribution"] = "spmd_uniform"
        return out

    def chrome_counter_events(self, t_base: float, pid: int) -> list[dict]:
        """Per-device counter track for the Perfetto export: an
        `occupancy` counter stepping with the number of batches in the
        device pipeline, reconstructed from the interval ring. Events are
        emitted in non-decreasing ts order on one named per-device
        track."""
        with self._lock:
            batches = list(self._ring)
        edges: list[tuple[float, int]] = []
        for b in batches:
            edges.append((b[0], +1))
            edges.append((b[2], -1))
        edges.sort()
        # Mesh mode: one counter track per chip (SPMD batches occupy all
        # of them, so every track carries the same edge stream, named
        # after its device); single-device mode keeps the one track.
        tracks = list(self.devices) if self.devices else [self.device]
        events: list[dict] = [
            {"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
             "args": {"name": "device-utilization"}},
        ]
        for tid, name in enumerate(tracks):
            events.append(
                {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
                 "args": {"name": name}}
            )
        depth = 0
        last_ts = 0
        for t, step in edges:
            depth += step
            ts = max(last_ts, max(0, int((t - t_base) * 1e6)))
            last_ts = ts
            for tid in range(len(tracks)):
                events.append({
                    "ph": "C", "name": "occupancy", "pid": pid, "tid": tid,
                    "ts": ts, "args": {"in_flight": depth},
                })
        return events


# --------------------------------------------------------------------------
# On-demand deep capture: jax.profiler device trace + host stack sampling.


class HostStackSampler:
    """Periodic Python-stack sampler over every live thread, for on-demand
    capture. Aggregates collapsed stacks (``func (file:line);...``) per
    thread name; the report is a plain dict the REST surface serializes.
    Pure stdlib; sampling cost is bounded by interval_s and stack depth."""

    def __init__(self, interval_s: float = 0.02, max_depth: int = 12):
        self.interval_s = max(float(interval_s), 0.001)
        self.max_depth = int(max_depth)
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._counts: dict[tuple[str, str], int] = {}
        self.samples = 0

    def _collapse(self, frame) -> str:
        parts = []
        depth = 0
        while frame is not None and depth < self.max_depth:
            code = frame.f_code
            parts.append(
                f"{code.co_name} ({os.path.basename(code.co_filename)}:{frame.f_lineno})"
            )
            frame = frame.f_back
            depth += 1
        return ";".join(parts)

    def _loop(self) -> None:
        me = threading.get_ident()
        while not self._stop.is_set():
            names = {t.ident: t.name for t in threading.enumerate()}
            for ident, frame in sys._current_frames().items():
                if ident == me:
                    continue
                key = (names.get(ident, f"thread-{ident}"), self._collapse(frame))
                self._counts[key] = self._counts.get(key, 0) + 1
            self.samples += 1
            self._stop.wait(self.interval_s)

    def start(self) -> "HostStackSampler":
        self._thread = threading.Thread(
            target=self._loop, name="host-stack-sampler", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> dict:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        threads: dict[str, list] = {}
        for (name, stack), count in sorted(
            self._counts.items(), key=lambda kv: -kv[1]
        ):
            threads.setdefault(name, []).append(
                {"stack": stack, "count": count}
            )
        return {
            "samples": self.samples,
            "interval_s": self.interval_s,
            "threads": threads,
        }


class CaptureInProgressError(RuntimeError):
    """A deep capture is already running; concurrent jax.profiler traces
    are refused (the profiler is process-global)."""


class ProfilerCapture:
    """One-at-a-time deep capture: a `jax.profiler.trace` of the device
    side plus a HostStackSampler of the host side, over the same window.
    `start(seconds)` returns immediately with the artifact paths; a
    daemon timer stops both and writes `host_stacks.json` into the
    artifact dir. CPU-safe: a jax profiler that cannot start (headless
    CPU builds, missing plugin) is recorded as `device_trace_error` and
    the host side still captures. Injectable device start/stop hooks keep
    tests deterministic and jax-free."""

    MAX_SECONDS = 120.0

    def __init__(self, base_dir: str | None = None,
                 device_start=None, device_stop=None):
        self.base_dir = base_dir
        self._device_start = device_start
        self._device_stop = device_stop
        self._lock = threading.Lock()
        self._active: dict | None = None

    def _jax_start(self, log_dir: str) -> None:
        import jax

        jax.profiler.start_trace(log_dir)

    def _jax_stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def status(self) -> dict:
        with self._lock:
            if self._active is None:
                return {"active": False}
            return {"active": True, **self._active}

    def start(self, seconds: float, host_interval_s: float = 0.02) -> dict:
        import tempfile

        seconds = min(max(float(seconds), 0.05), self.MAX_SECONDS)
        with self._lock:
            if self._active is not None:
                raise CaptureInProgressError(
                    "a profiler capture is already running "
                    f"({self._active.get('artifact_dir')})"
                )
            base = self.base_dir or os.path.join(
                tempfile.gettempdir(), "dts_tpu_profiles"
            )
            os.makedirs(base, exist_ok=True)
            stamp = time.strftime("%Y%m%d-%H%M%S")
            artifact_dir = tempfile.mkdtemp(
                prefix=f"capture-{stamp}-", dir=base
            )
            info: dict = {
                "artifact_dir": artifact_dir,
                "seconds": seconds,
                "host_stacks": os.path.join(artifact_dir, "host_stacks.json"),
            }
            try:
                (self._device_start or self._jax_start)(artifact_dir)
                info["device_trace"] = True
            except Exception as exc:  # noqa: BLE001 — host side still captures
                info["device_trace"] = False
                info["device_trace_error"] = f"{type(exc).__name__}: {exc}"[:300]
            sampler = HostStackSampler(interval_s=host_interval_s).start()
            self._active = dict(info)

        def finish():
            time.sleep(seconds)
            report = sampler.stop()
            if info.get("device_trace"):
                try:
                    (self._device_stop or self._jax_stop)()
                except Exception as exc:  # noqa: BLE001 — record, release slot
                    info["device_trace_error"] = (
                        f"{type(exc).__name__}: {exc}"[:300]
                    )
            try:
                with open(info["host_stacks"], "w") as f:
                    json.dump(report, f, indent=1)
            except OSError:
                pass
            with self._lock:
                self._active = None

        threading.Thread(target=finish, name="profilez", daemon=True).start()
        return info


# Process-global capture slot (the jax profiler itself is process-global,
# so two REST gateways in one process must share the refusal).
_CAPTURE = ProfilerCapture()


def profiler_capture() -> ProfilerCapture:
    return _CAPTURE
