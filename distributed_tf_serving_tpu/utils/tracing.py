"""Request-path tracing: per-request span trees + aggregate phase timers.

The reference's only tracing is System.nanoTime() around whole requests
(DCNClient.java:141,198-199; SURVEY.md §5). PhaseTrace (below) improved
that in AGGREGATE — mean wall time per named phase across all requests —
but an aggregate cannot explain ONE slow request: which shard hedged, how
long it sat in the batcher queue, whether the D2H wait or a failover retry
ate the budget. This module adds the per-request plane:

- **Span / start_span / start_root**: an explicit span-tree recorder.
  The client opens a root span per logical Predict and injects a W3C
  ``traceparent`` into gRPC metadata; the servers extract it, so the
  server-side span tree shares the client's trace id and parents onto the
  exact shard attempt that carried it. Cross-thread producers (the
  batcher's dispatch/completer threads) attach child spans to an explicit
  handle instead of the contextvar.
- **TraceRecorder**: bounded in-memory retention with TAIL sampling —
  errors and degraded/fault-annotated traces are always kept, the
  slowest-N are always kept, everything else is sampled. `/tracez`
  (serving/rest.py) serves its contents as JSON; `chrome_trace()` exports
  Chrome-trace-event JSON that Perfetto / chrome://tracing load directly
  (`/tracez?format=chrome` and tools/soak.py's SOAK_TRACE_OUT fetch it over
  HTTP and write the file themselves).
- **collect_phases**: a thread-local sink that lets the batcher's existing
  PhaseTrace call sites double as per-request span producers — one pair of
  clock reads feeds both the aggregate and the span tree.
- **annotate()**: attaches an annotation to the current span (or the
  active phase sink) — faults.py marks injection sites with it so a chaos
  run's trace shows exactly where the delay/error/wedge landed.

Tracing is OFF by default and gated on one module bool: every hot-path
hook is a single global read when disabled (the bench gate is <=1%
overhead with tracing off).

PhaseTrace keeps its original role (aggregate phase means, always on,
served by /monitoring?section=phases). It also speaks on the profiler's
clock: once a server has bound jax's TraceAnnotation (`bind_annotation`,
called by serving.server.build_stack; this module imports no jax, the
jax-free client imports it), the phases of the batcher's own threads
(`wait.*`, `batch.*`, `readback.*`, `cache.*`: all synchronous, none spans
an `await`) and the transport's two protobuf passes (`rpc.parse` on a
listener's poller thread, `rpc.serialize` on the handler's pool thread;
proto/service_grpc.py) are also written into an open jax.profiler capture
under their own names, so a device idle gap can be read against what the
host was doing. `predict.*`, `cascade.*`, `req.*` and the other `rpc.*`
phases stay on perf_counter alone: the first two wrap an `await` in the
coroutine servers, where an annotation would mis-nest on the event-loop
thread, and `req.*`, `rpc.pool_wait`, `rpc.request_wait`, `rpc.reply`,
`rpc.server` and `rpc.listener<i>` are differences of stamps, most taken on
two threads, that reach the trace through `add_many`, which annotates
nothing.

Who is on the CPU (ISSUE 56): a wall span under ONE interpreter lock cannot
say whether its thread computed, waited for the lock or was denied a core.
`ThreadSampler` reads what the kernel keeps a thread (its CPU-time clock;
where there is a `schedstat`, also the ns it was runnable and waiting for a
core), on a scrape and at no other time, and publishes it by thread ROLE as
the phases `cpu.*` and `sched.*` of `request_trace`; inside an open capture
the spans that only compute (`_CPU_SPLIT`) also read their thread's CPU
clock and add `offcpu.<phase>`, wall less CPU: lock wait plus preemption.
"""

from __future__ import annotations

import contextlib
import contextvars
import heapq
import itertools
import os
import random
import threading
import time
import weakref
from collections import Counter, defaultdict, deque

# --------------------------------------------------------------------------
# Aggregate phase timing (the original plane).

_ENABLED = False  # per-request tracing; flipped by enable()/disable()

# jax.profiler.TraceAnnotation once a server has bound it, else None: one
# global read per span when unbound, and when bound with no capture open
# one call of its native `is_enabled()`. The annotation object itself is
# made only inside a capture: made for every span, capture or not, it
# cost dcn_v2_ref43-rank 1.8% of its p50 (PERF.md section 6, PR 24).
_ANNOTATION = None
_ON_PROFILER = ("wait.", "batch.", "readback.", "cache.", "rpc.")
# The spans that open and close on one thread and wait for nothing by
# design: inside a capture they also read that thread's CPU clock, and what
# their wall time holds beyond it (the interpreter lock taken by another
# thread, a core given to another process) is the phase `offcpu.<phase>`.
# `predict.execute` is not one: its handler blocks on the batcher on purpose.
_CPU_SPLIT = frozenset((
    "predict.decode", "predict.encode", "batch.dispatch", "batch.cache",
    "batch.jitcall", "batch.deliver",
))
# What a span asks the gate about: the two of `_CPU_SPLIT` that are not on
# the profiler's clock beside those that are. Every other phase stays one
# `startswith` short of it.
_GATED = _ON_PROFILER + ("predict.decode", "predict.encode")


def bind_annotation(annotation_cls) -> None:
    """Let the phases of `_ON_PROFILER` open `annotation_cls(name)` (jax's
    TraceAnnotation) around themselves while `annotation_cls.is_enabled()`
    says a capture is open. Process-wide, like the capture it writes into;
    None unbinds."""
    global _ANNOTATION
    _ANNOTATION = annotation_cls


def capture_open() -> bool:
    """The gate itself: a bound annotation class says a capture is open.
    For a stamp that rides no span (the transport's `_RpcStamps`)."""
    cls = _ANNOTATION
    return cls is not None and cls.is_enabled()


def annotation(phase: str):
    """Context manager that puts `phase` on the profiler's clock and
    nothing else: for the callers that time the interval themselves and
    `add` it (the batcher's waits, its readback fetch, its delivery)."""
    cls = _ANNOTATION
    if cls is None or not phase.startswith(_ON_PROFILER) or not cls.is_enabled():
        return _NOOP
    return cls(phase)


class _PhaseSpan:
    """PhaseTrace.span's context manager: one pair of clock reads, the
    annotation outside them so that its own cost is not the phase's."""

    __slots__ = ("_trace", "_phase", "_annotation", "_t0")

    def __init__(self, trace: "PhaseTrace", phase: str, annotation):
        self._trace = trace
        self._phase = phase
        self._annotation = annotation

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return None

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        self._trace.add(self._phase, seconds)
        return False


class _SplitPhaseSpan(_PhaseSpan):
    """The span of a `_CPU_SPLIT` phase while a capture is open: the thread's
    CPU clock beside each wall read, INSIDE the wall reads (so that a span
    that only computes reads no negative rest), and `offcpu.<phase>` beside
    the phase."""

    __slots__ = ("_cpu0",)

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        self._cpu0 = time.thread_time()
        return None

    def __exit__(self, *exc):
        on_cpu = time.thread_time() - self._cpu0
        seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        # Not cut off at zero a span: where the CPU clock ticks coarsely
        # (10 ms under gVisor) only the SUM of many spans means anything.
        self._trace.add(self._phase, seconds, offcpu=seconds - on_cpu)
        return False


class PhaseTrace:
    """Accumulates wall time per named phase, aggregated across requests."""

    def __init__(self):
        self._totals: dict[str, float] = defaultdict(float)
        self._counts: dict[str, int] = defaultdict(int)
        self._lock = threading.Lock()
        self._sources: list = []

    def add_source(self, source) -> None:
        """Merge `source.phases()` (cumulative blocks a phase, as snapshot's
        own) into every snapshot; `reset()` calls `source.rebase()`. For
        counters read on a scrape and at no other time (`ThreadSampler`)."""
        self._sources.append(source)

    def span(self, phase: str) -> _PhaseSpan:
        cls = _ANNOTATION
        if cls is None or not phase.startswith(_GATED) or not cls.is_enabled():
            return _PhaseSpan(self, phase, _NOOP)
        note = cls(phase) if phase.startswith(_ON_PROFILER) else _NOOP
        kind = _SplitPhaseSpan if phase in _CPU_SPLIT else _PhaseSpan
        return kind(self, phase, note)

    def add(self, phase: str, seconds: float, offcpu: float | None = None) -> None:
        """Record an externally timed duration under `phase`. For callers
        that already hold the wall time for their own accounting (the
        batcher's readback-overlap bookkeeping times the fetch once and
        feeds both this trace and the overlap counters) — a nested span
        would pay a second pair of clock reads for the same interval.
        `offcpu`, from a span that split itself, lands under
        `offcpu.<phase>` in the same take of the lock."""
        with self._lock:
            self._totals[phase] += seconds
            self._counts[phase] += 1
            if offcpu is not None:
                self._totals["offcpu." + phase] += offcpu
                self._counts["offcpu." + phase] += 1
        if _ENABLED:
            # Per-request plane: the same interval becomes a child span of
            # whatever request context is active on this thread — the
            # batcher's phase sink when one is installed, else the
            # contextvar span (the service/REST handler threads). One
            # global read when tracing is off.
            end = time.perf_counter()
            sink = getattr(_SINK, "phases", None)
            if sink is not None:
                sink.append((phase, end - seconds, end))
            else:
                cur = _CURRENT.get()
                if cur is not None:
                    cur.add_interval(phase, end - seconds, end)

    def add_many(self, entries) -> None:
        """Record (phase, total seconds, count) triples under one lock:
        sums a caller has already formed over several requests (the
        batcher's `req.*` timeline of one batch). Aggregate only."""
        with self._lock:
            for phase, seconds, count in entries:
                self._totals[phase] += seconds
                self._counts[phase] += count

    def snapshot(self) -> dict[str, dict]:
        with self._lock:
            out = {
                phase: _phase_block(self._totals[phase], self._counts[phase])
                for phase in self._totals
            }
        for source in self._sources:
            out.update(source.phases())
        return dict(sorted(out.items()))

    def reset(self) -> None:
        with self._lock:
            self._totals.clear()
            self._counts.clear()
        for source in self._sources:
            source.rebase()


def _phase_block(seconds: float, count: int) -> dict:
    return {
        "total_ms": round(seconds * 1e3, 3),
        "count": count,
        # A phase by count may stand at 0 (add_many).
        "mean_us": round(seconds / max(count, 1) * 1e6, 1),
    }


# --------------------------------------------------------------------------
# Who is on the CPU: the kernel's per-thread clocks, by the thread's role.

# A Python thread's role, from the name its owner gave it: a listener's
# `_serve` thread (grpc names none, so `threading` calls it
# `Thread-N (_serve)`), `create_server`'s pool, the batcher's three kinds
# and the REST gateway's loop, which is also where a scrape runs: its cost
# shows there, outside the request path's roles.
_ROLE_OF_PREFIX = (
    ("rpc_", "handler"), ("batch-dispatch", "dispatch"),
    ("batch-complete", "completer"),
)
_ROLE_OF_NAME = {"batcher": "collector", "rest": "rest"}
_NATIVE_NAMES_KEPT = 8


def thread_role(name: str) -> str:
    """The role of a thread Python knows, from `Thread.name`."""
    if name.endswith("(_serve)"):
        return "poller"
    role = _ROLE_OF_NAME.get(name)
    if role is not None:
        return role
    for prefix, role in _ROLE_OF_PREFIX:
        if name.startswith(prefix):
            return role
    return "python_other"


class ThreadSampler:
    """CPU time and run-queue wait of this process's threads, by role.

    One pass over the threads a call of `phases()` or `threads()`, and
    nothing between calls: no thread, no stamp on the request path. The
    threads are `/proc/self/task`'s; a thread's CPU is its CPU-time clock,
    which the kernel names by the thread's id (the id glibc's
    `pthread_getcpuclockid` builds), read without a file and without giving
    the interpreter lock away; where the kernel keeps `schedstat` (ns on a
    core, ns runnable and waiting for one, timeslices: Linux with
    SCHED_INFO; gVisor has none) that file is read instead and the wait is
    kept too. A thread Python knows (`threading.enumerate()`'s `native_id`)
    takes its role from its name; any other is `native.<comm, trailing
    digits cut>`: the TPU runtime's and grpc core's own. A comm's place is
    fixed when it is first seen (those first seen in one pass ranked by
    CPU): one of `_NATIVE_NAMES_KEPT` names of its own, or `native.other`.
    Each pass adds a thread's growth since the pass before to its role's
    total, so a thread that exits leaves what it had and no total ever
    falls. Where there is no `/proc/self/task` every reading is absent."""

    def __init__(self, task_dir: str = "/proc/self/task"):
        self._task_dir = task_dir
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self._schedstat: bool | None = None  # asked of the kernel at the first pass
        # tid -> (run ns, wait ns, role, comm) at the pass before
        self._last: dict[int, tuple[int, int, str, str]] = {}
        self._run_ns: dict[str, int] = defaultdict(int)  # by role, cumulative
        self._wait_ns: dict[str, int] = defaultdict(int)
        self._native_role: dict[str, str] = {}  # comm -> native.<name>
        self._base: dict[str, float] = {}  # phase -> seconds at reset()
        self._scraped: tuple | None = None  # threads()'s last (t, run, wait)
        self._pass_s, self._passes = 0.0, 0  # what the passes themselves took

    def _clocks(self, tid: int) -> tuple[int, int, int | None] | None:
        """(ns on a core, ns waiting for one, timeslices) of one thread;
        without `schedstat` the wait is 0 and the timeslices None. None for
        a thread that ended since the listing."""
        try:
            if self._schedstat:
                with open(f"{self._task_dir}/{tid}/schedstat") as f:
                    run, wait, slices = (int(x) for x in f.read().split())
                return run, wait, slices
            # MAKE_THREAD_CPUCLOCK(tid, CPUCLOCK_SCHED) of the kernel's ABI.
            return time.clock_gettime_ns((~tid << 3) | 6), 0, None
        except (OSError, ValueError):
            return None

    def _comm(self, tid: int) -> str:
        try:
            with open(f"{self._task_dir}/{tid}/comm") as f:
                return f.read().strip()
        except OSError:
            return ""

    def _pass(self) -> list[dict] | None:
        """Read every thread once and add its growth to its role; the live
        threads' rows, or None where the kernel shows none. Lock held."""
        t_pass = time.perf_counter()
        try:
            tids = [int(tid) for tid in os.listdir(self._task_dir)]
        except OSError:
            return None
        if self._schedstat is None:
            self._schedstat = bool(tids) and os.path.exists(
                f"{self._task_dir}/{tids[0]}/schedstat")
        names = {t.native_id: t.name for t in threading.enumerate()}
        rows, grown, fresh = [], [], defaultdict(int)
        for tid in tids:
            clocks = self._clocks(tid)
            if clocks is None:
                continue
            run, wait, slices = clocks
            run0, wait0, role, comm = self._last.get(tid, (0, 0, None, None))
            if run < run0:  # the kernel gave a dead thread's id to a new one
                run0, wait0, role, comm = 0, 0, None, None
            comm = comm or self._comm(tid)  # once a thread: a file, unlike the clock
            name, group = names.get(tid), None
            if name is not None:
                role = thread_role(name)
            elif role is None or role.startswith("native."):
                # Not a Python thread on its way out (which keeps its role
                # when `threading` has forgotten it): native, by its comm.
                group = comm.rstrip("0123456789") or comm or "unnamed"
                role = self._native_role.get(group)
                if role is None:
                    fresh[group] += run - run0
            rows.append({
                "role": role, "name": name, "comm": comm, "native_id": tid,
                "cpu_s": run / 1e9,
                "runq_wait_s": wait / 1e9 if self._schedstat else None,
                "timeslices": slices,
            })
            grown.append((run, wait, run - run0, max(wait - wait0, 0), group))
        # Native comms not seen before take their places, largest first.
        for group in sorted(fresh, key=fresh.get, reverse=True):
            kept = sum(r != "native.other" for r in self._native_role.values())
            self._native_role[group] = (
                f"native.{group}" if kept < _NATIVE_NAMES_KEPT else "native.other")
        self._last = {}
        for row, (run, wait, run_grew, wait_grew, group) in zip(rows, grown):
            role = row["role"] = row["role"] or self._native_role[group]
            self._run_ns[role] += run_grew
            self._wait_ns[role] += wait_grew
            self._last[row["native_id"]] = (run, wait, role, row["comm"])
        self._pass_s += time.perf_counter() - t_pass
        self._passes += 1
        return rows

    def _cumulative(self, rows: list[dict]) -> dict[str, tuple[float, int]]:
        """{phase: (seconds, count)}: `cpu.<role>` and, where the kernel
        keeps the wait, `sched.<role>`, with the role's live threads as the
        count; `cpu.process`, `cpu.wall`, and `cpu.scrape`: the WALL time of
        the passes so far, this one too, by their number (what a scrape
        costs, on the thread that asked)."""
        live = Counter(row["role"] for row in rows)
        out = {}
        for role, run_ns in self._run_ns.items():
            out["cpu." + role] = (run_ns / 1e9, live[role])
            if self._schedstat:
                out["sched." + role] = (self._wait_ns[role] / 1e9, live[role])
        out["cpu.process"] = (time.process_time(), len(rows))
        out["cpu.wall"] = (time.perf_counter() - self._t0, 1)
        out["cpu.scrape"] = (self._pass_s, self._passes)
        return out

    def phases(self) -> dict[str, dict]:
        """The blocks `PhaseTrace.snapshot` merges: cumulative since the
        process started (since `rebase()`, after one), never falling. A
        window's delta of a `count` is 0: absent is told by the key."""
        with self._lock:
            rows = self._pass()
            if rows is None:
                return {}
            return {
                phase: _phase_block(seconds - self._base.get(phase, 0.0), count)
                for phase, (seconds, count) in self._cumulative(rows).items()
            }

    def rebase(self) -> None:
        """Count from now (`PhaseTrace.reset`)."""
        with self._lock:
            rows = self._pass()
            self._base = {} if rows is None else {
                phase: seconds for phase, (seconds, _n) in self._cumulative(rows).items()}

    def threads(self) -> dict | None:
        """`/monitoring?section=threads`: a row a live thread, and a row a
        role with its share of ONE core and of the run queue since the last
        call of this method (since the process started, at the first);
        `runq_*` and `timeslices` are null where the kernel keeps none."""
        with self._lock:
            rows = self._pass()
            if rows is None:
                return None
            now = time.perf_counter()
            t0, run0, wait0 = self._scraped or (self._t0, {}, {})
            self._scraped = (now, dict(self._run_ns), dict(self._wait_ns))
            span_ns = max(now - t0, 1e-9) * 1e9
            live = Counter(row["role"] for row in rows)

            def pct(total, before, role):
                return round(100.0 * (total[role] - before.get(role, 0)) / span_ns, 2)

            return {
                "interval_s": round(now - t0, 6),
                "roles": [
                    {
                        "role": role, "threads": live[role], "cpu_s": run_ns / 1e9,
                        "runq_wait_s": self._wait_ns[role] / 1e9 if self._schedstat else None,
                        "cpu_pct_of_core": pct(self._run_ns, run0, role),
                        "runq_pct_of_core": (
                            pct(self._wait_ns, wait0, role) if self._schedstat else None),
                    }
                    for role, run_ns in sorted(self._run_ns.items())
                ],
                "threads": sorted(rows, key=lambda r: (r["role"], r["native_id"])),
            }


# Process-wide default trace used by the serving path, and the sampler whose
# `cpu.*` and `sched.*` phases every snapshot of it holds.
request_trace = PhaseTrace()
thread_cpu = ThreadSampler()
request_trace.add_source(thread_cpu)


# --------------------------------------------------------------------------
# W3C trace context (the `traceparent` header, version 00).

_TRACEPARENT_VERSION = "00"


def make_traceparent(trace_id: str, span_id: str, sampled: bool = True) -> str:
    return f"{_TRACEPARENT_VERSION}-{trace_id}-{span_id}-{'01' if sampled else '00'}"


def parse_traceparent(header: str | None) -> tuple[str, str] | None:
    """(trace_id, parent_span_id) from a W3C traceparent, or None when the
    header is absent/malformed — a bad header must degrade to a fresh
    trace, never fail the request."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) != 4:
        return None
    _version, trace_id, span_id, _flags = parts
    if len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        if int(trace_id, 16) == 0 or int(span_id, 16) == 0:
            return None
    except ValueError:
        return None
    return trace_id.lower(), span_id.lower()


def _new_trace_id() -> str:
    return os.urandom(16).hex()


def _new_span_id() -> str:
    return os.urandom(8).hex()


def clock_anchor() -> dict:
    """Pair this process's span clock (perf_counter) with the wall clock,
    plus the pid, so an exported span tree can be placed on a shared
    fleet timeline: unix_us(span) = start_us - perf_us + unix_us. The two
    reads are not atomic; the fleet stitcher refines residual error from
    RPC send/recv pairs, so sub-millisecond anchor noise is acceptable."""
    return {
        "perf_us": int(time.perf_counter() * 1e6),
        "unix_us": time.time_ns() // 1000,
        "pid": os.getpid(),
    }


# --------------------------------------------------------------------------
# Spans.


class Span:
    """One timed operation in a request's tree.

    Timestamps are time.perf_counter() — monotonic, so exported Chrome
    events never go backwards even across NTP steps. Child mutation is
    list-append under the GIL plus an explicit lock for cross-thread
    attachment (the batcher's dispatch/completer threads attach to a span
    owned by an RPC handler)."""

    __slots__ = (
        "name", "trace_id", "span_id", "parent_id", "remote_parent",
        "start", "end", "status", "attrs", "annotations", "children",
        "_lock",
    )

    def __init__(
        self,
        name: str,
        trace_id: str | None = None,
        parent_id: str | None = None,
        remote_parent: bool = False,
        attrs: dict | None = None,
    ):
        self.name = name
        self.trace_id = trace_id or _new_trace_id()
        self.span_id = _new_span_id()
        self.parent_id = parent_id
        self.remote_parent = remote_parent
        self.start = time.perf_counter()
        self.end: float | None = None
        self.status = "OK"
        self.attrs = dict(attrs) if attrs else {}
        self.annotations: list[dict] = []
        self.children: list[Span] = []
        self._lock = threading.Lock()

    # ------------------------------------------------------------- building

    def child(self, name: str, attrs: dict | None = None) -> "Span":
        """Open (started-now) child span; the caller ends it."""
        sp = Span(
            name, trace_id=self.trace_id, parent_id=self.span_id, attrs=attrs
        )
        with self._lock:
            self.children.append(sp)
        return sp

    def add_interval(
        self, name: str, start: float, end: float, attrs: dict | None = None
    ) -> "Span":
        """Attach an already-timed child interval (the batcher's phase
        sink replay; safe from any thread)."""
        sp = Span(
            name, trace_id=self.trace_id, parent_id=self.span_id, attrs=attrs
        )
        sp.start = start
        sp.end = end
        with self._lock:
            self.children.append(sp)
        return sp

    def annotate(self, message: str, **attrs) -> None:
        with self._lock:
            self.annotations.append(
                {"t": time.perf_counter(), "message": message, **attrs}
            )

    def set_error(self, exc: BaseException | None = None) -> None:
        self.status = "ERROR"
        if exc is not None:
            self.attrs.setdefault("error", f"{type(exc).__name__}: {exc}")

    def finish(self) -> None:
        if self.end is None:
            self.end = time.perf_counter()

    # -------------------------------------------------------------- reading

    @property
    def duration_s(self) -> float:
        return ((self.end if self.end is not None else time.perf_counter())
                - self.start)

    def has_error(self) -> bool:
        return self.status == "ERROR" or any(
            c.has_error() for c in self.children
        )

    def has_annotations(self) -> bool:
        return bool(self.annotations) or any(
            c.has_annotations() for c in self.children
        )

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start_us": int(self.start * 1e6),
            "duration_us": int(self.duration_s * 1e6),
            "status": self.status,
            "attrs": self.attrs,
            "annotations": [
                {**a, "t": int(a["t"] * 1e6)} for a in self.annotations
            ],
            "children": [c.to_dict() for c in self.children],
        }

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()


# Contextvar current span: propagates through asyncio tasks (context is
# captured at task creation) and stays per-thread in threaded servers.
_CURRENT: contextvars.ContextVar[Span | None] = contextvars.ContextVar(
    "dts_tpu_current_span", default=None
)

# Thread-local phase sink for producers that run OUTSIDE the request's
# context (the batcher's dispatch/completer threads): a list of
# (phase, t0, t1) tuples plus annotation dicts, replayed onto every
# co-batched request's span by the batcher.
_SINK = threading.local()


def current_span() -> Span | None:
    return _CURRENT.get()


def enabled() -> bool:
    return _ENABLED


class _NoopSpanCtx:
    """Returned by start_span/start_root when tracing is disabled, and by
    annotation() for a phase that is not on the profiler's clock: one
    shared instance, no allocation on the disabled hot path."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpanCtx()


class _SpanCtx:
    __slots__ = ("span", "_token", "_record")

    def __init__(self, span: Span, record: bool):
        self.span = span
        self._token = None
        self._record = record

    def __enter__(self) -> Span:
        self._token = _CURRENT.set(self.span)
        return self.span

    def __exit__(self, exc_type, exc, tb):
        _CURRENT.reset(self._token)
        if exc is not None:
            if isinstance(exc, Exception):
                self.span.set_error(exc)
            else:
                # BaseException-only exits (asyncio.CancelledError — the
                # hedge loser's DESIGNED fate — GeneratorExit, shutdown):
                # not failures. Marking them ERROR would roll up to the
                # root, defeat tail sampling, and report every healthy
                # hedged request as an error in /tracez.
                self.span.status = "CANCELLED"
        self.span.finish()
        if self._record:
            _RECORDER.record(self.span)
        return False


def start_span(name: str, attrs: dict | None = None):
    """Child span of the current context span (a fresh local root when no
    context is set). Context manager yielding the Span; no-op when tracing
    is disabled."""
    if not _ENABLED:
        return _NOOP
    parent = _CURRENT.get()
    if parent is not None:
        sp = parent.child(name, attrs=attrs)
        return _SpanCtx(sp, record=False)
    return _SpanCtx(Span(name, attrs=attrs), record=True)


def start_root(name: str, traceparent: str | None = None, attrs: dict | None = None):
    """LOCAL-ROOT span: a fresh trace, or — when a valid W3C traceparent
    arrives — a remote-parented span in the caller's trace (the server
    side of a propagated request). Recorded into the global recorder on
    exit regardless of any ambient context."""
    if not _ENABLED:
        return _NOOP
    ctx = parse_traceparent(traceparent)
    if ctx is not None:
        sp = Span(
            name, trace_id=ctx[0], parent_id=ctx[1],
            remote_parent=True, attrs=attrs,
        )
    else:
        sp = Span(name, attrs=attrs)
    return _SpanCtx(sp, record=True)


def annotate(message: str, **attrs) -> None:
    """Attach an annotation to whatever request context is active: the
    thread's phase sink when installed (batcher threads — the batcher
    replays it onto every co-batched request), else the contextvar span.
    One global read when tracing is off."""
    if not _ENABLED:
        return
    sink = getattr(_SINK, "phases", None)
    if sink is not None:
        sink.append(
            {"t": time.perf_counter(), "message": message, **attrs}
        )
        return
    cur = _CURRENT.get()
    if cur is not None:
        cur.annotate(message, **attrs)


@contextlib.contextmanager
def collect_phases(sink: list):
    """Install `sink` as this thread's phase sink: request_trace phase
    timings (and annotate() calls) land in it as (phase, t0, t1) tuples /
    annotation dicts until the block exits. The batcher uses one sink per
    batch and replays it onto every member request's span."""
    prev = getattr(_SINK, "phases", None)
    _SINK.phases = sink
    try:
        yield sink
    finally:
        _SINK.phases = prev


def replay_phases(span: Span, phases: list) -> None:
    """Attach a collect_phases sink's contents to `span`: tuples become
    child intervals, annotation dicts become annotations."""
    for entry in phases:
        if isinstance(entry, dict):
            span.annotations.append(dict(entry))
        else:
            name, t0, t1 = entry
            span.add_interval(name, t0, t1)


# --------------------------------------------------------------------------
# Counter-track sources for the Chrome export (the utilization plane's
# per-device occupancy track, ISSUE 6). Registered objects expose
# `chrome_counter_events(t_base, pid) -> list[dict]`; a WeakSet so a
# retired ledger (bench teardown, tests) drops out of every later export
# without an unregister call.

_COUNTER_SOURCES: "weakref.WeakSet" = weakref.WeakSet()


def register_counter_source(source) -> None:
    """Add a counter-track provider to every future chrome_trace()
    export. Weakly held: dropping the object deregisters it."""
    _COUNTER_SOURCES.add(source)


# --------------------------------------------------------------------------
# Recorder: bounded retention + tail sampling + exporters.


class TraceRecorder:
    """Bounded in-memory store of finished local-root spans.

    Tail sampling (decided at span END, when the outcome is known):

    - error spans (own or any descendant) and annotated spans (fault
      injections, degraded merges) are ALWAYS kept, in a dedicated ring;
    - the slowest `slowest_n` spans are ALWAYS kept (min-heap on
      duration), independent of the sample draw;
    - everything else enters the recent ring with probability
      `sample_rate` (1.0 and 0.0 never consult the RNG — deterministic
      for tests and for the keep-nothing-but-tails production setting).

    Rings are deques: retention is bounded regardless of traffic, and an
    idle server holds exactly what it last saw."""

    def __init__(
        self,
        buffer_size: int = 256,
        sample_rate: float = 1.0,
        slowest_n: int = 32,
        seed: int | None = None,
    ):
        self.buffer_size = max(1, int(buffer_size))
        self.sample_rate = float(sample_rate)
        self.slowest_n = max(0, int(slowest_n))
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._recent: deque[Span] = deque(maxlen=self.buffer_size)
        self._errors: deque[Span] = deque(maxlen=self.buffer_size)
        self._slow: list[tuple[float, int, Span]] = []  # min-heap
        self._seq = itertools.count()
        self.recorded = 0
        self.dropped = 0
        # Export ring (the fleet trace-export surface): every KEPT span
        # gets a monotonically increasing export sequence number, so a
        # remote collector can pull incrementally with a `since` cursor.
        # Bounded like the retention rings — a collector that falls more
        # than a ring behind misses spans, by design.
        self._export: deque[tuple[int, Span]] = deque(maxlen=self.buffer_size)
        self._export_seq = 0

    # ------------------------------------------------------------ ingestion

    def record(self, span: Span) -> None:
        keep_tail = span.has_error() or span.has_annotations()
        dur = span.duration_s
        with self._lock:
            self.recorded += 1
            kept = False
            if keep_tail:
                self._errors.append(span)
                kept = True
            evicted: Span | None = None
            if self.slowest_n:
                if len(self._slow) < self.slowest_n:
                    heapq.heappush(self._slow, (dur, next(self._seq), span))
                    kept = True
                elif dur > self._slow[0][0]:
                    evicted = heapq.heapreplace(
                        self._slow, (dur, next(self._seq), span)
                    )[2]
                    kept = True
            if self.sample_rate >= 1.0 or (
                0.0 < self.sample_rate and self._rng.random() < self.sample_rate
            ):
                self._recent.append(span)
                kept = True
            if kept:
                self._export_seq += 1
                self._export.append((self._export_seq, span))
            # dropped is APPROXIMATE: spans retained nowhere at record
            # time, plus heap evictions that had no tail claim when the
            # sampler was keeping less than everything. (An exact count
            # would need an O(buffer) ring-membership scan under this
            # lock on every heap replacement — a per-request critical
            # section not worth a diagnostics counter.)
            if not kept:
                self.dropped += 1
            if (
                evicted is not None
                and self.sample_rate < 1.0
                and not (evicted.has_error() or evicted.has_annotations())
            ):
                self.dropped += 1

    def clear(self) -> None:
        with self._lock:
            self._recent.clear()
            self._errors.clear()
            self._slow.clear()
            self._export.clear()
            self.recorded = 0
            self.dropped = 0

    # -------------------------------------------------------------- queries

    def _all_spans_locked(self) -> list[Span]:
        """Distinct retained roots, newest-first-stable (a span can sit in
        several rings; report it once)."""
        seen: set[int] = set()
        out: list[Span] = []
        for sp in itertools.chain(
            self._recent, self._errors, (s for _, _, s in self._slow)
        ):
            if id(sp) not in seen:
                seen.add(id(sp))
                out.append(sp)
        return out

    def spans(self) -> list[Span]:
        with self._lock:
            return self._all_spans_locked()

    def slowest(self, n: int | None = None) -> list[Span]:
        with self._lock:
            ordered = sorted(self._slow, key=lambda e: -e[0])
        return [s for _, _, s in ordered[: n or self.slowest_n]]

    def traces(self) -> list[dict]:
        """Retained local roots grouped by trace id — one entry per
        distributed trace, with every local root (client predict, each
        server RPC) as a tree under it."""
        return self._traces_from(self.spans())

    @staticmethod
    def _traces_from(roots: list[Span]) -> list[dict]:
        groups: dict[str, list[Span]] = {}
        for sp in roots:
            groups.setdefault(sp.trace_id, []).append(sp)
        out = []
        for trace_id, roots in groups.items():
            roots.sort(key=lambda s: s.start)
            out.append({
                "trace_id": trace_id,
                "duration_us": int(
                    (max(s.end or s.start for s in roots)
                     - min(s.start for s in roots)) * 1e6
                ),
                "status": (
                    "ERROR" if any(s.has_error() for s in roots) else "OK"
                ),
                "spans": [s.to_dict() for s in roots],
            })
        out.sort(key=lambda t: -t["duration_us"])
        return out

    def tracez(self, limit: int = 50) -> dict:
        """The /tracez JSON body: recorder config + counters, the
        slowest-N trees, and the most recent traces. ONE lock acquisition
        snapshots everything, so the counters and the serialized trace
        list cannot disagree within a response."""
        with self._lock:
            roots = self._all_spans_locked()
            slow_sorted = [
                s for _, _, s in sorted(self._slow, key=lambda e: -e[0])
            ]
            recorded, dropped = self.recorded, self.dropped
        return {
            "config": {
                "buffer_size": self.buffer_size,
                "sample_rate": self.sample_rate,
                "slowest_n": self.slowest_n,
            },
            "recorded": recorded,
            "dropped": dropped,
            "num_retained": len(roots),
            "slowest": [s.to_dict() for s in slow_sorted],
            "traces": self._traces_from(roots)[: max(1, int(limit))],
        }

    def export_since(self, since: int = 0, limit: int = 64) -> dict:
        """Incremental span-tree export for a remote TraceCollector
        (`GET /tracez/export?since=CURSOR`): every kept local root after
        `since`, as `Span.to_dict` trees, with this process's clock
        anchor so the collector can map perf_counter timestamps onto the
        shared wall-clock timeline. The returned `cursor` feeds the next
        call. A cursor AHEAD of the ring (this process restarted and the
        sequence reset) replays from the start instead of going silent."""
        since = max(0, int(since))
        with self._lock:
            if since > self._export_seq:
                since = 0
            pending = [(seq, sp) for seq, sp in self._export if seq > since]
        pending = pending[: max(1, int(limit))]
        return {
            "enabled": True,
            "clock": clock_anchor(),
            "cursor": pending[-1][0] if pending else since,
            "spans": [sp.to_dict() for _, sp in pending],
        }

    # ------------------------------------------------------------ exporters

    def chrome_trace(self) -> dict:
        """Chrome trace-event JSON (Perfetto / chrome://tracing loadable):
        one complete ("X") event per span with microsecond ts/dur, one
        instant ("i") event per annotation, grouped into one pid per trace
        with the span tree flattened onto tids by root. Monotonic by
        construction — ts derives from perf_counter."""
        events: list[dict] = []
        trace_pids: dict[str, int] = {}
        tid_counters: dict[int, int] = {}
        with self._lock:
            roots = self._all_spans_locked()
        # Stable base so every ts is a small non-negative number.
        t_base = min((s.start for s in roots), default=0.0)
        for root in sorted(roots, key=lambda s: s.start):
            pid = trace_pids.setdefault(root.trace_id, len(trace_pids))
            # One tid per local root inside its trace's pid (sibling RPC
            # attempts render as parallel tracks); O(1) per root — a full
            # export can hold hundreds of roots and runs on the event loop.
            tid = tid_counters.get(pid, 0)
            tid_counters[pid] = tid + 1
            events.append({
                "ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
                "args": {"name": root.name},
            })
            for sp in root.walk():
                events.append({
                    "ph": "X",
                    "name": sp.name,
                    "cat": "span" if sp is root else "phase",
                    "pid": pid,
                    "tid": tid,
                    "ts": max(0, int((sp.start - t_base) * 1e6)),
                    "dur": max(0, int(sp.duration_s * 1e6)),
                    "args": {
                        "trace_id": sp.trace_id,
                        "span_id": sp.span_id,
                        "parent_id": sp.parent_id,
                        "status": sp.status,
                        **sp.attrs,
                    },
                })
                for a in sp.annotations:
                    events.append({
                        "ph": "i",
                        "name": a.get("message", "annotation"),
                        "cat": "annotation",
                        "pid": pid,
                        "tid": tid,
                        "ts": max(0, int((a["t"] - t_base) * 1e6)),
                        "s": "t",
                        "args": {
                            k: v for k, v in a.items()
                            if k not in ("t", "message")
                        },
                    })
        # Counter tracks (per-device occupancy from the utilization
        # ledger): appended on their own pids AFTER the span pids, sharing
        # t_base so the tracks align with the spans on the timeline.
        pid_next = len(trace_pids)
        for source in list(_COUNTER_SOURCES):
            try:
                events.extend(source.chrome_counter_events(t_base, pid_next))
                pid_next += 1
            except Exception:  # noqa: BLE001 — a sick source must not
                pass           # poison the whole export
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "recorded": self.recorded,
                "producer": "distributed_tf_serving_tpu",
            },
        }


# Process-global recorder (the /tracez surface); enable() swaps config.
_RECORDER = TraceRecorder()


def recorder() -> TraceRecorder:
    return _RECORDER


def enable(
    buffer_size: int = 256,
    sample_rate: float = 1.0,
    slowest_n: int = 32,
    seed: int | None = None,
) -> TraceRecorder:
    """Turn the per-request plane on with a fresh recorder; returns it."""
    global _ENABLED, _RECORDER
    _RECORDER = TraceRecorder(
        buffer_size=buffer_size, sample_rate=sample_rate,
        slowest_n=slowest_n, seed=seed,
    )
    _ENABLED = True
    return _RECORDER


def disable() -> None:
    global _ENABLED
    _ENABLED = False
