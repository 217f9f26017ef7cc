"""The request timeline (`req.*`), the thread-state spans (`wait.*`) and the
start-up stamps (ISSUE 24): six stamps tile a request's way through a batch,
every wait of the batcher's threads lands in a named phase (and still in an
armed utilization ledger), the same phases appear in a jax.profiler capture,
and the module that carries them stays free of jax for the client."""

import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from distributed_tf_serving_tpu.models import (
    ModelConfig,
    Servable,
    ServableRegistry,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.serving import DynamicBatcher, PredictionServiceImpl
from distributed_tf_serving_tpu.serving.batcher import SERVED_KERNELS
from distributed_tf_serving_tpu.serving.utilization import OccupancyLedger
from distributed_tf_serving_tpu.utils import tracing
from distributed_tf_serving_tpu.utils.tracing import request_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F = 6
VOCAB = 997
REQ = ("req.queue", "req.assemble", "req.dispatch", "req.device",
       "req.deliver", "req.resume")


@pytest.fixture(scope="module")
def servable():
    cfg = ModelConfig(
        name="DCN", num_fields=F, vocab_size=VOCAB, embed_dim=4,
        mlp_dims=(8,), num_cross_layers=1, cross_full_matrix=True,
    )
    model = build_model("dcn_v2", cfg)
    return Servable(
        name="DCN", version=1, model=model,
        params=jax.jit(model.init)(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(F),
    )


@pytest.fixture(autouse=True)
def _unbound_annotation():
    """Each test binds what it needs; none leaves the class bound."""
    yield
    tracing.bind_annotation(None)


def _payload(n=8, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "feat_ids": rng.randint(0, VOCAB, size=(n, F)).astype(np.int64),
        "feat_wts": rng.rand(n, F).astype(np.float32),
    }


def _slow_run(seconds):
    def run_fn(sv, arrays):
        time.sleep(seconds)
        return {"prediction_node": np.zeros(next(iter(arrays.values())).shape[0], np.float32)}
    return run_fn


def _impl(servable, **batcher_kwargs):
    registry = ServableRegistry()
    registry.load(servable)
    batcher = DynamicBatcher(buckets=(16, 64), **batcher_kwargs).start()
    return PredictionServiceImpl(registry, batcher), batcher


def _delta(before, phase, field):
    return request_trace.snapshot().get(phase, {}).get(field, 0) - before.get(phase, {}).get(field, 0)


@pytest.mark.parametrize("mode", ["threads", "coroutines"])
def test_req_phases_tile_the_handler_interval(servable, mode):
    """Equal counts, and the six totals sum to what the handlers saw from
    just before submit to just after they ran again: no gap, no overlap.
    What is left over is the prelude of submit() before `enqueue_t`."""
    impl, batcher = _impl(servable, max_wait_us=2000, run_fn=_slow_run(0.004))
    callers, each = 4, 6
    requests = callers * each
    payloads = [_payload(seed=i) for i in range(requests)]
    seen = []
    try:
        before = request_trace.snapshot()
        if mode == "threads":
            def caller(c):
                for i in range(each):
                    t0 = time.perf_counter()
                    impl._run(servable, payloads[c * each + i])
                    seen.append(time.perf_counter() - t0)

            threads = [threading.Thread(target=caller, args=(c,)) for c in range(callers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
        else:
            async def caller(c):
                for i in range(each):
                    t0 = time.perf_counter()
                    await impl._run_async(servable, payloads[c * each + i])
                    seen.append(time.perf_counter() - t0)

            async def drive():
                await asyncio.gather(*(caller(c) for c in range(callers)))

            asyncio.run(drive())
    finally:
        batcher.stop()
    assert len(seen) == requests
    assert [_delta(before, p, "count") for p in REQ] == [requests] * 6
    tiled_ms = sum(_delta(before, p, "total_ms") for p in REQ)
    handler_ms = sum(seen) * 1e3
    # Neighbouring segments share their stamps, so the only slack is the
    # handler's own code outside them: submit's validation and admission
    # before `enqueue_t` and a few clock reads, against intervals of 4 ms
    # and more. 3% is the share at which ISSUE 24 would give the prelude a
    # phase of its own.
    assert tiled_ms <= handler_ms
    assert handler_ms - tiled_ms < 0.03 * handler_ms


@pytest.mark.parametrize("stage", ["slow custom run_fn", "the jitted entry"])
def test_req_phases_tile_a_direct_crossing(servable, stage):
    """A request that crosses the batcher on its own handler thread (ISSUE
    42) carries the same six stamps: equal counts, and the totals tile the
    handler's interval, with `req.queue` and `req.assemble` now too short
    for a thread to have been woken inside them."""
    kwargs = {"run_fn": _slow_run(0.004)} if stage == "slow custom run_fn" else {}
    impl, batcher = _impl(servable, max_wait_us=2000, **kwargs)
    requests = 8
    payloads = [_payload(seed=i) for i in range(requests)]
    seen = []
    try:
        impl._run(servable, _payload(seed=99))  # compiles, and parks the collector again
        deadline = time.monotonic() + 10
        while not batcher._collector_parked and time.monotonic() < deadline:
            time.sleep(0.002)
        before = request_trace.snapshot()
        for i in range(requests):
            with batcher._cv:  # a trickle: arrivals far slower than crossings
                batcher._arrival_gap_s, batcher._traversal_s = 1.0, 0.001
                batcher._last_arrival_t = None
            t0 = time.perf_counter()
            impl._run(servable, payloads[i])
            seen.append(time.perf_counter() - t0)
        assert batcher.stats.direct_batches == requests
    finally:
        batcher.stop()
    assert [_delta(before, p, "count") for p in REQ] == [requests] * 6
    assert _delta(before, "batch.direct", "count") == requests
    tiled_ms = sum(_delta(before, p, "total_ms") for p in REQ)
    handler_ms = sum(seen) * 1e3
    assert tiled_ms <= handler_ms
    if stage == "slow custom run_fn":  # intervals of 4 ms and more, as above
        assert handler_ms - tiled_ms < 0.03 * handler_ms
    # No coalesce window (2 ms here) and no hand-over lie in the first two.
    assert _delta(before, "req.queue", "total_ms") / requests < 1.0
    assert _delta(before, "req.assemble", "total_ms") / requests < 1.0


def test_warmup_items_and_cache_hits_add_no_req_phase(servable):
    from distributed_tf_serving_tpu.cache import ScoreCache

    impl, batcher = _impl(
        servable, max_wait_us=0, score_cache=ScoreCache(max_entries=8)
    )
    try:
        before = request_trace.snapshot()
        batcher.warmup_via_queue(servable, buckets=(16,))
        assert [_delta(before, p, "count") for p in REQ] == [0] * 6
        impl._run(servable, _payload(seed=1))  # through a batch
        impl._run(servable, _payload(seed=1))  # a score-cache hit: no batch
        assert [_delta(before, p, "count") for p in REQ] == [1] * 6
    finally:
        batcher.stop()


class _LazyReadback:
    """Stands in for a device array whose fetch blocks until released."""

    def __init__(self, n, release):
        self.n, self.release = n, release

    def __array__(self, dtype=None, copy=None):
        self.release.wait(timeout=30)
        return np.zeros(self.n, np.float32)


@pytest.mark.parametrize("cause, ledger_cause", [
    ("queue_empty", "queue_empty"),
    ("coalesce", "host_pack"),
    ("pipeline", "readback_wait"),
    ("window", None),
])
def test_every_wait_lands_in_a_wait_phase_and_the_ledger(servable, cause, ledger_cause):
    """Each wait site of the collector and dispatch threads adds to
    `wait.<cause>`, and to an armed ledger under the name it had before."""
    ledger = OccupancyLedger(device="cpu:0")
    release = threading.Event()

    def parked_run(sv, arrays):
        return {"prediction_node": _LazyReadback(next(iter(arrays.values())).shape[0], release)}

    kwargs = {
        "queue_empty": dict(max_wait_us=0),
        "coalesce": dict(max_wait_us=20000),
        # One batch parked in its readback fills a pipeline of depth 1, so
        # the next group's coalescing free-rides it.
        "pipeline": dict(max_wait_us=0, run_fn=parked_run, pipeline_depth=1),
        # The same parked batch fills an in-flight window of 1, so the
        # dispatch thread waits for it before issuing the next.
        "window": dict(max_wait_us=0, run_fn=parked_run, inflight_window=1),
    }[cause]
    impl, batcher = _impl(servable, utilization=ledger, **kwargs)
    try:
        before = request_trace.snapshot()
        time.sleep(0.05)  # the collector sits in _take's wait
        futures = [batcher.submit(servable, _payload(seed=0))]
        if cause in ("pipeline", "window"):
            deadline = time.monotonic() + 30
            while not batcher._inflight and time.monotonic() < deadline:
                time.sleep(0.002)
            futures.append(batcher.submit(servable, _payload(seed=1)))
            time.sleep(0.05)
            release.set()
        for f in futures:
            f.result(timeout=60)
    finally:
        release.set()
        batcher.stop()
    assert _delta(before, "wait." + cause, "count") >= 1
    assert _delta(before, "wait." + cause, "total_ms") > 0
    recorded = {c for c, _t0, _t1 in ledger._waits}
    if ledger_cause is not None:
        assert ledger_cause in recorded
    assert recorded <= {"queue_empty", "host_pack", "readback_wait"}


def test_readback_window_closes_readback_wait(servable):
    impl, batcher = _impl(servable, max_wait_us=0)
    try:
        before = request_trace.snapshot()
        for i in range(3):
            impl._run(servable, _payload(seed=i))
    finally:
        batcher.stop()
    assert _delta(before, "readback.window", "count") == 3
    assert _delta(before, "readback.wait", "count") == 3
    assert _delta(before, "batch.deliver", "count") == 3
    assert (_delta(before, "readback.wait", "total_ms")
            <= _delta(before, "readback.window", "total_ms") + 1e-3)
    stats = batcher.stats
    assert stats.readback_blocked_s <= stats.readback_window_s


def test_tracing_and_the_client_import_no_jax():
    code = (
        "import sys\n"
        "import distributed_tf_serving_tpu.utils.tracing as t\n"
        "from distributed_tf_serving_tpu.client import ShardedPredictClient\n"
        "assert t._ANNOTATION is None\n"
        "with t.request_trace.span('batch.dispatch'): pass\n"
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]\n"
        "assert not bad, bad\n"
    )
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True, timeout=120
    )
    assert done.returncode == 0, done.stderr[-2000:]


def test_span_annotates_only_the_batchers_phases_and_only_in_a_capture():
    opened = []
    capturing = [False]

    class Fake:
        def __init__(self, name):
            opened.append(name)

        @staticmethod
        def is_enabled():
            return capturing[0]

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    tracing.bind_annotation(Fake)
    trace = tracing.PhaseTrace()
    with trace.span("batch.pad"):  # no capture open: no annotation is made
        pass
    assert opened == []
    capturing[0] = True
    for phase in ("batch.pad", "wait.queue_empty", "readback.wait", "cache.row_fill",
                  "predict.execute", "cascade.stage1", "req.queue"):
        with trace.span(phase):
            pass
    assert opened == ["batch.pad", "wait.queue_empty", "readback.wait", "cache.row_fill"]
    assert set(trace.snapshot()) >= {"predict.execute", "batch.pad"}
    tracing.bind_annotation(None)
    with trace.span("batch.pad"):
        pass
    assert len(opened) == 4


def test_profiler_capture_holds_the_programs_spans(tmp_path):
    """On the CPU backend: a capture around a small served load holds host
    events under the phases' own names, on the profiler's clock."""
    from jax.profiler import ProfileData

    from distributed_tf_serving_tpu.serving.server import build_stack
    from distributed_tf_serving_tpu.utils.config import ServerConfig

    cfg = ServerConfig(
        model_kind="dcn_v2", model_name="DCN", num_fields=F, buckets=(16, 32),
        warmup=True,
    )
    registry, batcher, impl, sv, _mesh, _watcher = build_stack(
        cfg, model_config=ModelConfig(
            name="DCN", num_fields=F, vocab_size=VOCAB, embed_dim=4, mlp_dims=(8,),
            num_cross_layers=1,
        ),
    )
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    try:
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            for i in range(6):
                if i % 2:
                    impl._run(sv, _payload(seed=i))
                else:
                    # Through the queue: a handler thread's direct crossing
                    # lets the collector sleep on, and a `wait.queue_empty`
                    # that never ends inside the capture is not in it.
                    batcher.submit(sv, _payload(seed=i)).result(timeout=60)
                time.sleep(0.01)
        finally:
            jax.profiler.stop_trace()
    finally:
        batcher.stop()
    (path,) = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    names = {
        event.name
        for plane in ProfileData.from_file(str(path)).planes
        for line in plane.lines
        for event in line.events
    }
    assert {"batch.dispatch", "batch.jitcall", "wait.queue_empty", "readback.wait",
            "batch.deliver"} <= names
    # The jitted entry says model and variant, not `run`.
    assert any("DCN_score" in n for n in names), sorted(n for n in names if "jit" in n.lower())
    assert "predict.execute" not in names
    # build_stack measured the start-up it owns.
    startup = impl.runtime_stats()["startup"]
    assert startup["params_init_s"] >= 0 and startup["warmup_s"] == impl.warmup_s


class _Capture:
    """Stands in for jax's TraceAnnotation, bound: `open` is what its
    `is_enabled()` says, `asked` how often it was asked."""

    open = False
    asked = 0

    def __init__(self, name):
        pass

    @classmethod
    def is_enabled(cls):
        cls.asked += 1
        return cls.open

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def capture():
    _Capture.open, _Capture.asked = False, 0
    tracing.bind_annotation(_Capture)
    yield _Capture
    _Capture.open = False


SPLIT = ("predict.decode", "predict.encode", "batch.dispatch", "batch.cache",
         "batch.jitcall", "batch.deliver")


@pytest.mark.parametrize("phase", SPLIT)
def test_offcpu_rides_the_span_only_inside_a_capture(capture, phase):
    """wall less the thread's CPU, one entry a span so split, never above
    the span's own wall time; nothing of it with the gate closed."""
    trace = tracing.PhaseTrace()
    for _ in range(3):
        with trace.span(phase):
            pass
    assert set(trace.snapshot()) == {phase}
    capture.open = True
    for _ in range(4):
        with trace.span(phase):
            time.sleep(0.002)  # off the core: all of it is `offcpu`
    with trace.span(phase):
        t0 = time.thread_time()
        while time.thread_time() - t0 < 0.002:  # on it: none of it is
            pass
    capture.open = False
    with trace.span(phase):
        pass
    snap = trace.snapshot()
    assert snap[phase]["count"] == 9 and snap["offcpu." + phase]["count"] == 5
    assert 4 * 2.0 <= snap["offcpu." + phase]["total_ms"] <= snap[phase]["total_ms"]
    assert snap[phase]["total_ms"] - snap["offcpu." + phase]["total_ms"] >= 1.9


@pytest.mark.parametrize("phase", ["predict.execute", "wait.queue_empty", "batch.pad", "cascade.stage1"])
def test_a_span_that_waits_or_nests_is_never_split(capture, phase):
    capture.open = True
    trace = tracing.PhaseTrace()
    with trace.span(phase):
        pass
    assert set(trace.snapshot()) == {phase}


class _Clocks:
    """`time`, with the reads of each clock counted."""

    def __init__(self):
        self.reads = {"perf_counter": 0, "thread_time": 0}

    def __getattr__(self, name):
        if name in self.reads:
            self.reads[name] += 1
        return getattr(time, name)


@pytest.mark.parametrize("bound", [False, True])
@pytest.mark.parametrize("phase", ["predict.decode", "batch.dispatch", "predict.execute"])
def test_a_span_outside_a_capture_reads_two_clocks_and_asks_the_gate_once(
        capture, monkeypatch, phase, bound):
    if not bound:
        tracing.bind_annotation(None)
    clocks = _Clocks()
    monkeypatch.setattr(tracing, "time", clocks)
    trace = tracing.PhaseTrace()
    with trace.span(phase):
        pass
    assert clocks.reads == {"perf_counter": 2, "thread_time": 0}
    assert capture.asked == (1 if bound and phase != "predict.execute" else 0)
    capture.open = True
    with trace.span(phase):
        pass
    split = bound and phase != "predict.execute"
    assert clocks.reads == {"perf_counter": 4, "thread_time": 2 if split else 0}


def _grpc_predict(port, n=1):
    import grpc

    from distributed_tf_serving_tpu.client import build_predict_request
    from distributed_tf_serving_tpu.proto import PredictionServiceStub

    with grpc.insecure_channel(f"127.0.0.1:{port}") as channel:
        for i in range(n):
            PredictionServiceStub(channel).Predict(
                build_predict_request(_payload(seed=i), "DCN"), timeout=60)


def test_handler_cpu_is_stamped_only_inside_a_capture(servable, capture):
    """`cpu.rpc_handler`: the pool thread's CPU from `t_taken` to `t_return`,
    one entry an RPC stamped while the gate was open, asked once an RPC; it
    cannot pass the wall time from `t_taken` to the RPC's end, which holds
    both reads of the thread's clock."""
    from distributed_tf_serving_tpu.serving.server import create_server

    impl, batcher = _impl(servable, max_wait_us=0)
    impl.warmup_complete = True
    server, port = create_server(impl, "127.0.0.1:0")
    server.start()

    def count(snap, phase):
        return snap.get(phase, {}).get("count", 0)

    def settled(since, n):
        """The snapshot once `n` Predicts after `since` are stamped whole.
        Two threads stamp after the client has its answer: the poller's
        `done` (`rpc.server` and `cpu.rpc_handler`, one add_many) and the
        completer, which closes `batch.deliver` after the last set_result.
        One request is one batch here (sequential, no window)."""
        deadline = time.monotonic() + 10
        while True:
            snap = request_trace.snapshot()
            if time.monotonic() > deadline or all(
                    count(snap, p) >= count(since, p) + n for p in ("rpc.server", "batch.deliver")):
                return snap
            time.sleep(0.005)

    try:
        start = request_trace.snapshot()
        _grpc_predict(port)  # compiles
        base = settled(start, 1)
        _grpc_predict(port, 3)
        closed = settled(base, 3)
        capture.open = True
        _grpc_predict(port, 5)
        opened = settled(closed, 5)
        capture.open = False
    finally:
        server.stop(0).wait()
        batcher.stop()

    def rose(after, before, phase, field):
        return after.get(phase, {}).get(field, 0) - before.get(phase, {}).get(field, 0)

    assert rose(closed, base, "rpc.server", "count") == 3
    assert rose(closed, base, "cpu.rpc_handler", "count") == 0
    assert not any(rose(closed, base, "offcpu." + p, "count") for p in SPLIT)
    assert rose(opened, closed, "rpc.server", "count") == 5
    assert rose(opened, closed, "cpu.rpc_handler", "count") == 5
    # The same counts as their spans: one decode and one encode a request,
    # one dispatch and one delivery a batch.
    for phase in ("predict.decode", "predict.encode", "batch.dispatch", "batch.deliver"):
        assert rose(opened, closed, "offcpu." + phase, "count") == \
            rose(opened, closed, phase, "count") >= 5, phase
        assert rose(opened, closed, "offcpu." + phase, "total_ms") <= \
            rose(opened, closed, phase, "total_ms") + 1e-3
    # `cpu_return` is read after `t_return` (the handler's metrics in
    # between are CPU too) and before `done`, so the wall time that holds
    # both reads runs to the RPC's end: `rpc.reply` beside the other two.
    cpu_ms = rose(opened, closed, "cpu.rpc_handler", "total_ms")
    wall_ms = sum(rose(opened, closed, name, "total_ms") for name in opened
                  if name in ("rpc.request_wait", "rpc.reply") or name.startswith("rpc.listener"))
    assert 0.0 < cpu_ms <= wall_ms + 1e-2


def test_the_kernels_counts_of_a_batch_are_one_add_many(servable, monkeypatch):
    """ISSUE 56: a batch's phases by count (`batch.<x>_kernel`, `batch.direct`)
    reach the trace in ONE `add_many`, every name and counter as before."""
    impl, batcher = _impl(servable, max_wait_us=0)
    calls = []
    real = request_trace.add_many

    def add_many(entries):
        entries = tuple(entries)
        calls.append([name for name, _s, _c in entries])
        real(entries)

    try:
        impl._run(servable, _payload(seed=0))  # compiles
        batcher._kernel_kinds[servable] = SERVED_KERNELS
        before = request_trace.snapshot()
        stats0 = {k: getattr(batcher.stats, k) for k in (
            "gather_kernel_batches", "attention_kernel_batches", "grouped_kernel_batches",
            "delta_kernel_batches", "ssd_kernel_batches", "conv_kernel_batches", "direct_batches")}
        monkeypatch.setattr(request_trace, "add_many", add_many)
        with batcher._cv:  # a trickle, so the batch crosses direct
            batcher._arrival_gap_s, batcher._traversal_s = 1.0, 0.001
            batcher._last_arrival_t = None
        impl._run(servable, _payload(seed=1))
    finally:
        monkeypatch.undo()
        batcher.stop()
    counted = ["batch.gather_kernel", "batch.attention_kernel", "batch.grouped_kernel",
               "batch.delta_kernel", "batch.ssd_kernel", "batch.conv_kernel", "batch.direct"]
    assert [c for c in calls if any(n in counted for n in c)] == [counted]
    assert [_delta(before, p, "count") for p in counted] == [1] * 7
    assert all(getattr(batcher.stats, k) - v == 1 for k, v in stats0.items())


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _monitoring(port, section):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/monitoring?section={section}", timeout=5
    ) as r:
        return json.load(r)[section]


def test_cli_server_reports_startup_stamps_and_raw_counters(tmp_path):
    """The CLI server's /monitoring: `runtime.startup` holds the five
    stamps serve() and build_stack take (and each servable's lookups a row,
    bags and upload format, and the listeners on its port), `metrics.batcher`
    the raw terms of the two ratios."""
    grpc = pytest.importorskip("grpc")
    from distributed_tf_serving_tpu.client import build_predict_request
    from distributed_tf_serving_tpu.proto import PredictionServiceStub

    port, rest_port = _free_port(), _free_port()
    (tmp_path / "server.toml").write_text(
        f'[server]\nmodel_kind = "dcn_v2"\nnum_fields = {F}\nbuckets = [16]\n'
        f"[model]\nnum_fields = {F}\nvocab_size = {VOCAB}\nembed_dim = 4\n"
        "mlp_dims = [8]\nnum_cross_layers = 1\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    with open(tmp_path / "server.log", "w") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "distributed_tf_serving_tpu.serving.server",
             "--config", str(tmp_path / "server.toml"), "--host", "127.0.0.1",
             "--port", str(port), "--rest-port", str(rest_port)],
            cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT,
        )
    try:
        deadline = time.monotonic() + 150
        runtime = None
        while runtime is None and time.monotonic() < deadline:
            assert proc.poll() is None, (tmp_path / "server.log").read_text()[-3000:]
            try:
                runtime = _monitoring(rest_port, "runtime")
            except OSError:
                time.sleep(0.5)
        assert runtime is not None, "the server never came up"
        startup = dict(runtime["startup"])
        # Beside the stamps: what each loaded servable looks up a candidate row.
        assert startup.pop("lookups_per_row") == {"DCN:1": F}
        assert startup.pop("bags") == {"DCN:1": F}
        # A CTR family has no layer plan; its tree's bytes are stamped (PR 32).
        assert startup.pop("layer_plan") == {"DCN:1": None}
        # Nor an expert plan: that is a routed family's share (PR 35).
        assert startup.pop("expert_plan") == {"DCN:1": None}
        # Nor an attention plan: that is a family's whose attention differs by layer (PR 43).
        assert startup.pop("attention_plan") == {"DCN:1": None}
        assert startup.pop("params_bytes")["DCN:1"] > 0
        # And how its batches cross to the device: the ladder's warm-up
        # traced the one-buffer entry (ops/transfer.py describe_layout).
        assert startup.pop("upload_format") == {
            "DCN:1": "uint32 words, row planes: feat_ids int32/24b x4, feat_wts bfloat16/16b x2"
        }
        # And what builds them: one native pass from the requests' arrays.
        assert startup.pop("assembler") == {"DCN:1": "native"}
        # And what gathers its embedding rows (PR 39): XLA's, on a CPU
        # backend and over this table's 16-byte rows.
        assert startup.pop("gather") == {"DCN:1": {
            "kernel": "xla", "row_bytes": 16, "in_flight": 0, "picked_in_kernel": False}}
        # And no attention stamp (PR 48): a CTR step attends to nothing.
        assert startup.pop("attention") == {}
        # Nor a grouped stamp (PR 51): it routes to no expert.
        assert startup.pop("grouped") == {}
        # Nor a delta-rule stamp (PR 52): it carries no matrix state.
        assert startup.pop("delta_rule") == {}
        # Nor an SSD stamp (PR 54): no layer of it holds a Mamba-2 mixer.
        assert startup.pop("ssd") == {}
        # Nor a convolution's stamp (PR 63), for the same reason.
        assert startup.pop("conv") == {}
        # Nor a products stamp (PR 57): its step makes no product of an activation in pieces.
        assert startup.pop("products") == {}
        # And how many gRPC listeners share its port, from how many cores (PR 34).
        from distributed_tf_serving_tpu.serving.server import listener_count

        k, cores = listener_count()
        assert startup.pop("listeners") == {"k": k, "cores": cores}
        assert set(startup) == {
            "backend_init_s", "params_init_s", "native_build_s", "warmup_s", "to_serving_s",
        }
        assert all(isinstance(v, float) and v >= 0 for v in startup.values())
        assert startup["warmup_s"] == runtime["warmup_s"]
        assert startup["to_serving_s"] >= (
            startup["backend_init_s"] + startup["params_init_s"] + startup["warmup_s"]
        )
        with grpc.insecure_channel(f"127.0.0.1:{port}") as channel:
            PredictionServiceStub(channel).Predict(
                build_predict_request(_payload(n=5), "DCN"), timeout=60
            )
        block = _monitoring(rest_port, "metrics")["batcher"]
        assert block["candidates"] == 5 and block["padded_candidates"] == 16
        assert block["fused_batches"] == block["batches"] == 1
        assert block["gather_kernel_batches"] == 0
        assert 0 < block["readback_blocked_s"] <= block["readback_window_s"]
        phases = _monitoring(rest_port, "phases")
        assert {"req.queue", "req.resume", "wait.queue_empty", "readback.window"} <= set(phases)
        # The one Predict is counted under the listener whose connection carried it.
        counted = {name: p["count"] for name, p in phases.items() if name.startswith("rpc.listener")}
        assert sum(counted.values()) == 1 and set(counted) <= {f"rpc.listener{i}" for i in range(k)}
        # And once in each phase of the transport around its handler (PR 40);
        # the four of the call's termination come from the poller thread
        # after the client has its answer.
        transport = ("rpc.pool_wait", "rpc.request_wait", "rpc.parse",
                     "rpc.serialize", "rpc.reply", "rpc.server")
        deadline = time.monotonic() + 10
        while "rpc.server" not in phases and time.monotonic() < deadline:
            time.sleep(0.05)
            phases = _monitoring(rest_port, "phases")
        assert {name: phases[name]["count"] for name in transport} == dict.fromkeys(transport, 1)
        assert phases["batch.fusedpack_native"]["count"] == phases["batch.fusedpack"]["count"] == 1
        assert phases["batch.fusedpack_native"]["total_ms"] <= phases["batch.fusedpack"]["total_ms"]
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)
    assert proc.returncode == 0, (tmp_path / "server.log").read_text()[-3000:]
