"""Who is on the CPU (ISSUE 56): `utils/tracing.py`'s `ThreadSampler` reads
the kernel's per-thread clocks on a scrape (each thread's CPU-time clock;
`/proc/self/task/<tid>/schedstat` where the kernel keeps one, for the
run-queue wait too), gives each thread a role from its name, and publishes
cumulative `cpu.<role>` / `sched.<role>` phases, `cpu.process` and `cpu.wall`
through `request_trace.snapshot()`; `/monitoring?section=threads` is the
operator's view; ten readers of `benchmark/layers/` turn window deltas into
metrics."""

import importlib.util
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from distributed_tf_serving_tpu.utils.tracing import PhaseTrace, ThreadSampler, thread_role

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HAS_PROC = os.path.isdir("/proc/self/task")
needs_proc = pytest.mark.skipif(not HAS_PROC, reason="no /proc/self/task here")
ROLE_COLUMNS = {"role", "threads", "cpu_s", "runq_wait_s", "cpu_pct_of_core", "runq_pct_of_core"}
THREAD_COLUMNS = {"role", "name", "comm", "native_id", "cpu_s", "runq_wait_s", "timeslices"}


@pytest.mark.parametrize("name, role", [
    ("Thread-7 (_serve)", "poller"),
    ("rpc_0", "handler"),
    ("rpc_15", "handler"),
    ("batcher", "collector"),
    ("batch-dispatch_0", "dispatch"),
    ("batch-complete_1", "completer"),
    ("rest", "rest"),
    ("MainThread", "python_other"),
    ("restore-watcher", "python_other"),
    ("rpc-sampler", "python_other"),
])
def test_role_from_a_threads_name(name, role):
    assert thread_role(name) == role


class _Worker:
    """A named thread that spins (or sleeps) until told to stop. A spinner
    keeps publishing its own CPU clock, the clock the kernel's `schedstat`
    counts too: what the sampler read lies between two reads of it,
    whatever share of this machine the test was given."""

    def __init__(self, name, spin):
        self.stop = threading.Event()
        self.cpu_ms = 0.0
        self.thread = threading.Thread(target=self._run, args=(spin,), name=name, daemon=True)
        self.thread.start()
        deadline = time.monotonic() + 30
        while spin and self.cpu_ms < 50.0 and time.monotonic() < deadline:
            time.sleep(0.01)

    def _run(self, spin):
        while spin and not self.stop.is_set():
            self.cpu_ms = time.thread_time() * 1e3
        self.stop.wait(30)

    def end(self):
        self.stop.set()
        self.thread.join(timeout=30)
        assert not self.thread.is_alive()


def _between(spinner, read):
    """(the spinner's clock before, read(), its clock after), in ms."""
    lo = spinner.cpu_ms
    got = read()
    time.sleep(0.002)  # the spinner publishes once more
    return lo, got, spinner.cpu_ms


@needs_proc
def test_a_spinning_and_a_sleeping_thread_land_in_their_roles():
    sampler = ThreadSampler()
    spinner, sleeper = _Worker("rpc_0", spin=True), _Worker("batcher", spin=False)
    try:
        lo, phases, hi = _between(spinner, sampler.phases)
    finally:
        spinner.end()
        sleeper.end()
    assert phases["cpu.handler"]["count"] == 1 and phases["cpu.collector"]["count"] == 1
    assert 49.0 <= lo - 1.0 <= phases["cpu.handler"]["total_ms"] <= hi + 1.0
    assert phases["cpu.collector"]["total_ms"] < 20.0
    assert phases["sched.handler"]["total_ms"] >= 0.0 and "sched.collector" in phases
    # This thread is Python's too, and the whole is not under its parts.
    assert phases["cpu.python_other"]["count"] >= 1
    assert phases["cpu.process"]["total_ms"] >= phases["cpu.handler"]["total_ms"] - 1.0
    assert phases["cpu.wall"]["total_ms"] >= phases["cpu.handler"]["total_ms"] - 1.0
    assert phases["cpu.wall"]["count"] == 1


@needs_proc
def test_a_thread_that_exits_never_lowers_a_total():
    sampler = ThreadSampler()
    spinner = _Worker("batch-dispatch_0", spin=True)
    alive = sampler.phases()
    spinner.end()
    deadline = time.monotonic() + 10  # joined, and a moment later out of /proc
    while (gone := sampler.phases())["cpu.dispatch"]["count"] and time.monotonic() < deadline:
        time.sleep(0.01)
    assert alive["cpu.dispatch"]["count"] == 1 and gone["cpu.dispatch"]["count"] == 0
    assert gone["cpu.dispatch"]["total_ms"] >= alive["cpu.dispatch"]["total_ms"] >= 49.0
    assert gone["sched.dispatch"]["total_ms"] >= alive["sched.dispatch"]["total_ms"]
    again = _Worker("batch-dispatch_0", spin=True)  # a second thread of the role adds on
    try:
        assert sampler.phases()["cpu.dispatch"]["total_ms"] >= gone["cpu.dispatch"]["total_ms"] + 49.0
    finally:
        again.end()


@needs_proc
def test_reset_rebases_the_cumulative_phases():
    trace = PhaseTrace()
    trace.add_source(ThreadSampler())
    spinner = _Worker("rpc_3", spin=True)
    try:
        before = trace.snapshot()
        trace.add("predict.decode", 0.001)
        wall0, process0 = time.perf_counter(), time.process_time()
        lo, _, _ = _between(spinner, trace.reset)
        _, after, hi = _between(spinner, trace.snapshot)
        wall_ms, process_ms = ((time.perf_counter() - wall0) * 1e3,
                               (time.process_time() - process0) * 1e3)
    finally:
        spinner.end()
    assert before["cpu.handler"]["total_ms"] >= 49.0
    assert "predict.decode" not in after
    # Every cumulative phase counts from the reset: no more than has passed since.
    assert after["cpu.handler"]["total_ms"] <= hi - lo + 1.0
    assert 0.0 <= after["cpu.wall"]["total_ms"] <= wall_ms
    assert 0.0 <= after["cpu.process"]["total_ms"] <= process_ms + 1.0
    assert before["cpu.process"]["total_ms"] > process_ms  # the imports alone


T = 9_000_000  # above any id the kernel gives out (pid_max is at most 2**22)


def _fake_task_dir(root, threads):
    """{tid: (comm, run_ns, wait_ns)} as /proc/self/task would show them."""
    for tid, (comm, run, wait) in threads.items():
        os.makedirs(root / str(tid), exist_ok=True)
        (root / str(tid) / "schedstat").write_text(f"{run} {wait} 7\n")
        (root / str(tid) / "comm").write_text(comm + "\n")


def test_native_threads_fold_to_eight_names_and_no_total_falls(tmp_path):
    """Threads Python does not know, by `comm` with trailing digits cut: the
    eight largest by CPU keep their name and the rest are `native.other`; a
    comm's place never changes, so every total only grows."""
    threads = {T + i: (f"pool{chr(97 + i)}_{i}", (i + 1) * 1_000_000, 1000) for i in range(11)}
    threads[T + 500] = ("poolk_1", 50_000_000, 0)  # same group as T + 10's `poolk_10`
    _fake_task_dir(tmp_path, threads)
    sampler = ThreadSampler(task_dir=str(tmp_path))
    first = sampler.phases()
    native = {k: v for k, v in first.items() if k.startswith("cpu.native.")}
    assert len(native) == 9 and "cpu.native.other" in native
    # Groups poola_..poolk_ hold 1..11 ms, poolk_ 50 more: the three least fold.
    assert first["cpu.native.other"]["total_ms"] == pytest.approx(1 + 2 + 3)
    assert first["cpu.native.other"]["count"] == 3
    assert first["cpu.native.poolk_"] == {"total_ms": 61.0, "count": 2, "mean_us": 30500.0}
    assert first["sched.native.poolk_"]["total_ms"] == pytest.approx(0.001)
    assert "cpu.native.poola_" not in first and "cpu.native.poold_" in first
    # A folded group grows past a kept one, a new comm appears, a thread ends
    # and its id comes back under a smaller clock: places stay, totals grow.
    threads[T] = ("poola_0", 900_000_000, 1000)
    threads[T + 900] = ("late", 5_000_000, 0)
    del threads[T + 5]
    os.remove(tmp_path / str(T + 5) / "schedstat")
    os.remove(tmp_path / str(T + 5) / "comm")
    os.rmdir(tmp_path / str(T + 5))
    threads[T + 4] = ("poole_4", 1_000_000, 0)  # was 5 ms: a new thread under an old id
    _fake_task_dir(tmp_path, threads)
    second = sampler.phases()
    assert set(k for k in second if k.startswith("cpu.native.")) == set(native)
    assert second["cpu.native.other"]["total_ms"] == pytest.approx(6 + 899 + 5)
    assert second["cpu.native.poolf_"] == first["cpu.native.poolf_"] | {"count": 0, "mean_us": 6000.0}
    assert second["cpu.native.poole_"]["total_ms"] == pytest.approx(5 + 1)
    for name, block in first.items():
        if name.startswith(("cpu.native.", "sched.native.")):
            assert second[name]["total_ms"] >= block["total_ms"]
    view = sampler.threads()
    assert {row["role"] for row in view["threads"]} <= {r["role"] for r in view["roles"]}
    assert all(row["name"] is None for row in view["threads"])


def test_a_kernel_without_schedstat_gives_cpu_by_the_threads_clocks_and_no_wait(tmp_path):
    """gVisor, where the benchmark's chips are: `/proc/self/task` lists the
    threads and has no `schedstat`. CPU comes from each thread's CPU-time
    clock, named by its id; `sched.*` is absent and the section says null."""
    spinner = _Worker("rpc_2", spin=True)
    try:
        for tid, comm in ((threading.get_native_id(), "python3"), (spinner.thread.native_id, "python3"),
                          (T + 1, "gone")):  # listed, and ended before its clock was read
            os.makedirs(tmp_path / str(tid))
            (tmp_path / str(tid) / "comm").write_text(comm + "\n")
        sampler = ThreadSampler(task_dir=str(tmp_path))
        lo, phases, hi = _between(spinner, sampler.phases)
        view = sampler.threads()
    finally:
        spinner.end()
    assert not any(name.startswith("sched.") for name in phases)
    assert {"cpu.handler", "cpu.python_other", "cpu.process", "cpu.wall", "cpu.scrape"} == set(phases)
    assert lo - 1.0 <= phases["cpu.handler"]["total_ms"] <= hi + 1.0
    assert phases["cpu.handler"]["count"] == 1 and phases["cpu.python_other"]["count"] == 1
    assert {r["native_id"] for r in view["threads"]} == {spinner.thread.native_id, threading.get_native_id()}
    assert all(r["runq_wait_s"] is None and r["timeslices"] is None for r in view["threads"])
    assert all(r["runq_wait_s"] is None and r["runq_pct_of_core"] is None for r in view["roles"])
    assert all(r["cpu_pct_of_core"] >= 0.0 for r in view["roles"])


def test_without_proc_every_reading_is_absent_and_nothing_fails(tmp_path):
    sampler = ThreadSampler(task_dir=str(tmp_path / "not-there"))
    trace = PhaseTrace()
    trace.add_source(sampler)
    trace.add("predict.decode", 0.002)
    assert set(trace.snapshot()) == {"predict.decode"}
    assert sampler.threads() is None
    trace.reset()
    assert trace.snapshot() == {}


@needs_proc
def test_section_shares_are_since_the_last_scrape():
    sampler = ThreadSampler()
    sampler.threads()
    spinner = _Worker("rpc_1", spin=True)
    lo, busy, hi = _between(spinner, sampler.threads)
    spinner.end()
    time.sleep(0.1)  # joined, and by now out of /proc
    idle = sampler.threads()
    assert set(busy) == {"interval_s", "roles", "threads"}
    assert all(set(row) == ROLE_COLUMNS for row in busy["roles"])
    assert all(set(row) == THREAD_COLUMNS for row in busy["threads"])
    handler = {row["role"]: row for row in busy["roles"]}["handler"]
    assert handler["threads"] == 1
    # All of the thread's CPU fell between the first scrape and this one.
    on_core_ms = handler["cpu_pct_of_core"] / 100.0 * busy["interval_s"] * 1e3
    assert lo - 1.0 <= on_core_ms <= hi + 1.0
    assert handler["cpu_s"] * 1e3 == pytest.approx(on_core_ms, abs=1.0)
    assert [r for r in busy["threads"] if r["name"] == "rpc_1"][0]["role"] == "handler"
    after = {row["role"]: row for row in idle["roles"]}["handler"]
    assert after["threads"] == 0
    assert after["cpu_s"] >= handler["cpu_s"]  # cumulative: what it had stays
    # Since the LAST scrape: only what the thread still burned on its way out.
    tail_ms = after["cpu_pct_of_core"] / 100.0 * idle["interval_s"] * 1e3
    assert tail_ms <= spinner.cpu_ms - lo + 2.0 < on_core_ms


# ---------------------------------------------------------------- the readers

READERS = ("cpu_process_cores", "cpu_python_cores", "cpu_pollers_pct", "cpu_handlers_pct",
           "cpu_dispatch_pct", "cpu_native_pct", "runq_wait_pct", "handler_cpu_us",
           "codec_offcpu_us", "dispatch_offcpu_us")


def _reader(name):
    import sys

    layers = os.path.join(ROOT, "benchmark", "layers")
    if layers not in sys.path:
        sys.path.insert(0, layers)
    spec = importlib.util.spec_from_file_location("bench_layer_" + name, os.path.join(layers, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _block(total_ms, count=0):
    return {"total_ms": total_ms, "count": count}


def _recorded_ctx():
    """A window of 50 s as `run.py::delta_phases` leaves it: cumulative
    phases come with a count delta of 0, the capture's with their spans'."""
    return {"notes": {}, "phases": {
        "cpu.wall": _block(50_000.0), "cpu.process": _block(70_000.0),
        "cpu.poller": _block(30_000.0), "cpu.handler": _block(20_000.0),
        "cpu.collector": _block(500.0), "cpu.dispatch": _block(4_000.0),
        "cpu.completer": _block(1_500.0), "cpu.rest": _block(100.0),
        "cpu.python_other": _block(900.0),
        "cpu.native.tpu_worker": _block(9_000.0), "cpu.native.grpc_event": _block(3_500.0),
        "cpu.native.other": _block(500.0),
        "sched.poller": _block(2_000.0), "sched.handler": _block(2_500.0),
        "sched.collector": _block(0.0), "sched.dispatch": _block(250.0),
        "sched.completer": _block(250.0), "sched.rest": _block(9_999.0),
        "sched.native.tpu_worker": _block(9_999.0),
        "cpu.rpc_handler": _block(600.0, 2_000),
        "predict.decode": _block(1_000.0, 20_000), "predict.encode": _block(400.0, 20_000),
        "offcpu.predict.decode": _block(50.0, 2_000), "offcpu.predict.encode": _block(30.0, 2_000),
        "batch.dispatch": _block(5_000.0, 10_000), "offcpu.batch.dispatch": _block(120.0, 400),
    }}


@pytest.mark.parametrize("name, want", list(zip(READERS, (
    1.4, 1.14, 60.0, 40.0, 8.0, 26.0, 10.0, 300.0, 40.0, 300.0))))
def test_reader_on_a_recorded_window(name, want):
    ctx = _recorded_ctx()
    assert _reader(name)(ctx) == pytest.approx(want)
    if name == "cpu_native_pct":
        assert ctx["notes"]["cpu_native_pct_by_comm"] == {
            "grpc_event": 7.0, "other": 1.0, "tpu_worker": 18.0}


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_from_a_program_without_the_phases(name):
    """The parent's `/monitoring`: the older phases alone."""
    ctx = _recorded_ctx()
    ctx["phases"] = {k: v for k, v in ctx["phases"].items()
                     if not k.startswith(("cpu.", "sched.", "offcpu."))}
    assert _reader(name)(ctx) is None


def test_the_run_queue_reader_tells_a_kernel_without_the_wait_from_no_wait():
    ctx = _recorded_ctx()
    ctx["phases"] = {k: v for k, v in ctx["phases"].items() if not k.startswith("sched.")}
    assert _reader("runq_wait_pct")(ctx) is None
    assert _reader("cpu_python_cores")(ctx) == pytest.approx(1.14)  # the CPU readers go on


def test_a_ticks_worth_of_cpu_in_a_small_sample_reads_zero_not_less():
    ctx = _recorded_ctx()
    ctx["phases"]["offcpu.batch.dispatch"] = _block(-3.08, 12)  # 12 spans of 2.7 ms, one 10 ms tick
    ctx["phases"]["offcpu.predict.encode"] = _block(-200.0, 2_000)
    assert _reader("dispatch_offcpu_us")(ctx) == 0.0
    assert _reader("codec_offcpu_us")(ctx) == 0.0


def test_every_new_metric_is_declared_twice_with_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    rank = [w["name"] for w in bench["workloads"] if w["name"].endswith("-rank")]
    bulk = [w["name"] for w in bench["workloads"] if w["name"].endswith("-bulk")]
    layers = {m["layer"] for m in bench["per_layer"][:64]}
    # `runq_wait_pct` has its reader and no entry: the kernel of the machine
    # the benchmark runs on (gVisor) keeps no run-queue wait to read.
    assert not any(name.startswith("runq_wait_pct") for name in by_name)
    for base in (r for r in READERS if r != "runq_wait_pct"):
        for suffix, moves, cells in ((".rank", "p50_ms", rank), (".bulk", "cand_per_s", bulk)):
            entry = by_name[base + suffix]
            assert entry["moves"] == moves and sorted(entry["workloads"]) == sorted(cells)
            assert entry["better"] == "lower" and entry["layer"] in layers
            assert entry["source"] == ("program_span" if base.endswith("_us") else "program_counter")


@needs_proc
def test_a_started_server_shows_its_six_kinds_of_thread():
    """`create_server` + `DynamicBatcher` + the REST gateway, one Predict
    through them: every kind of thread the request path has is in its role,
    on `/monitoring?section=threads` and as phases on `section=phases`."""
    jax = pytest.importorskip("jax")
    import grpc

    from distributed_tf_serving_tpu.client import build_predict_request
    from distributed_tf_serving_tpu.models import (
        ModelConfig, Servable, ServableRegistry, build_model, ctr_signatures)
    from distributed_tf_serving_tpu.proto import PredictionServiceStub
    from distributed_tf_serving_tpu.serving import DynamicBatcher, PredictionServiceImpl
    from distributed_tf_serving_tpu.serving.server import create_server, start_rest_in_thread

    fields = 6
    model = build_model("dcn_v2", ModelConfig(
        name="DCN", num_fields=fields, vocab_size=1 << 10, embed_dim=4,
        mlp_dims=(8,), num_cross_layers=1, compute_dtype="float32"))
    registry = ServableRegistry()
    registry.load(Servable(name="DCN", version=1, model=model,
                           params=model.init(jax.random.PRNGKey(0)),
                           signatures=ctr_signatures(fields)))
    # A coalesce window keeps the request off the direct crossing, so the
    # collector and the dispatch thread both run.
    batcher = DynamicBatcher(buckets=(16,), max_wait_us=1000).start()
    impl = PredictionServiceImpl(registry, batcher)
    impl.warmup_complete = True
    server, port = create_server(impl, "127.0.0.1:0", listeners=2)
    server.start()
    try:
        rest_port = start_rest_in_thread(impl, "127.0.0.1", 0)
        rng = np.random.RandomState(0)
        arrays = {"feat_ids": rng.randint(0, 1 << 10, size=(4, fields)).astype(np.int64),
                  "feat_wts": rng.rand(4, fields).astype(np.float32)}
        with grpc.insecure_channel(f"127.0.0.1:{port}") as channel:
            for _ in range(2):
                PredictionServiceStub(channel).Predict(
                    build_predict_request(arrays, "DCN"), timeout=60)

        def get(section):
            url = f"http://127.0.0.1:{rest_port}/monitoring?section={section}"
            with urllib.request.urlopen(url, timeout=30) as r:
                return json.loads(r.read())[section]

        view = get("threads")
        live = {row["role"]: row["threads"] for row in view["roles"]}
        assert live["poller"] == 2
        for role in ("handler", "collector", "dispatch", "completer", "rest"):
            assert live[role] >= 1, (role, live)
        names = {row["role"]: row["name"] for row in view["threads"] if row["name"]}
        assert names["collector"] == "batcher" and names["rest"] == "rest"
        assert names["handler"].startswith("rpc_") and names["poller"].endswith("(_serve)")
        phases = get("phases")
        for role in ("poller", "handler", "collector", "dispatch", "completer", "rest", "python_other"):
            assert phases[f"cpu.{role}"]["total_ms"] >= 0.0 and f"sched.{role}" in phases
        assert phases["cpu.poller"]["count"] == 2
        assert phases["cpu.process"]["total_ms"] > 0 and phases["cpu.wall"]["total_ms"] > 0
        with urllib.request.urlopen(f"http://127.0.0.1:{rest_port}/monitoring", timeout=30) as r:
            assert set(json.loads(r.read())["threads"]) == {"interval_s", "roles", "threads"}
    finally:
        server.stop(0).wait()
        batcher.stop()
