"""Spans inside the sync transport (PR 40): the stamps of one Predict from the
pool's `submit` on the listener's poller thread to the call's termination,
as `rpc.pool_wait`, `rpc.request_wait`, `rpc.reply` and `rpc.server`;
protobuf's two passes as `rpc.parse` and `rpc.serialize`; the native
assembler's own clock as `batch.fusedpack_native`; and the six readers of
`benchmark/layers/` over them. A real `create_server` on localhost over a
stub impl, on the CPU."""

import os
import sys
import threading
import time
from concurrent import futures

import grpc
import numpy as np
import pytest

from distributed_tf_serving_tpu.proto import PredictionServiceStub, apis
from distributed_tf_serving_tpu.proto import health as health_proto
from distributed_tf_serving_tpu.serving import server as server_mod
from distributed_tf_serving_tpu.serving.server import LISTENER_PHASE, create_server
from distributed_tf_serving_tpu.serving.service import ServiceError
from distributed_tf_serving_tpu.utils.tracing import request_trace

STAMPED = ("rpc.pool_wait", "rpc.request_wait", "rpc.reply", "rpc.server")
PHASES = STAMPED + ("rpc.parse", "rpc.serialize")
LOCAL_POOL = [("grpc.use_local_subchannel_pool", 1)]


class _Registry:
    def models(self):
        return {"DCN": [1]}


class _StubImpl:
    """What GrpcPredictionService.Predict and the health service ask of an
    impl, and no more: `predict` sleeps `sleep_s`, raises `error` if set,
    and sets `entered` when it starts."""

    integrity = None
    warmup_complete = True
    registry = _Registry()

    def __init__(self):
        self.sleep_s = 0.0
        self.error = None
        self.entered = threading.Event()
        self.threads = set()

    def predict(self, request, **_kwargs):
        self.threads.add(threading.current_thread().name)
        self.entered.set()
        if self.sleep_s:
            time.sleep(self.sleep_s)
        if self.error is not None:
            raise self.error
        response = apis.PredictResponse()
        response.model_spec.name = request.model_spec.name
        return response


def _request(payload_bytes=64):
    request = apis.PredictRequest()
    request.model_spec.name = "DCN"
    request.inputs["x"].tensor_content = b"\1" * payload_bytes
    return request


def _counts():
    snap = request_trace.snapshot()
    return {name: snap.get(name, {"count": 0})["count"] for name in PHASES}


def _totals_ms():
    snap = request_trace.snapshot()
    out = {name: snap.get(name, {"total_ms": 0.0})["total_ms"] for name in STAMPED}
    out["handler"] = sum(
        p["total_ms"] for name, p in snap.items() if name.startswith(LISTENER_PHASE))
    return out


def _rose(before, after):
    return {name: after[name] - before[name] for name in after}


def _wait_for_ended(before, n):
    """The termination callback runs on the poller thread AFTER the client
    has its answer: wait until `rpc.server` has counted n more RPCs."""
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if _counts()["rpc.server"] - before["rpc.server"] >= n:
            return
        time.sleep(0.005)
    raise AssertionError(f"rpc.server counted {_rose(before, _counts())}, want {n}")


@pytest.fixture
def served():
    """A maker of started servers over a stub impl, (impl, target, server)
    each; all are stopped when the test ends."""
    made = []

    def make(max_workers=4, listeners=1):
        impl = _StubImpl()
        server, port = create_server(
            impl, "127.0.0.1:0", max_workers=max_workers, listeners=listeners)
        server.start()
        made.append(server)
        return impl, f"127.0.0.1:{port}", server

    yield make
    for server in made:
        server.stop(0).wait()


def _pool(server):
    return server.servers[0]._state.thread_pool


def _nothing_left_on_the_pool_threads(server, workers):
    """Run a probe on every pool thread, PAST the stamping `submit`, and read
    what the thread-local holds there."""
    barrier = threading.Barrier(workers)

    def probe():
        barrier.wait(timeout=10)
        return server_mod._TAKEN.stamps

    pool = _pool(server)
    assert isinstance(pool, server_mod._StampedPool)
    probes = [futures.ThreadPoolExecutor.submit(pool, probe) for _ in range(workers)]
    return all(p.result(timeout=10) is None for p in probes)


def test_every_predict_counts_once_in_every_phase_and_health_checks_in_none(served):
    impl, target, server = served()
    before = _counts()
    n = 7
    with grpc.insecure_channel(target, options=LOCAL_POOL) as channel:
        stub = PredictionServiceStub(channel)
        health = health_proto.HealthStub(channel)
        for _ in range(n):
            assert stub.Predict(_request(), timeout=30).model_spec.name == "DCN"
            reply = health.Check(health_proto.HealthCheckRequest(service=""), timeout=30)
            assert reply.status == health_proto.SERVING
    _wait_for_ended(before, n)
    time.sleep(0.05)  # a count too many would come late as well
    assert _rose(before, _counts()) == dict.fromkeys(PHASES, n)
    # The handler and the serializer ran on the pool's threads.
    assert impl.threads and all(name.startswith("rpc_") for name in impl.threads)


def test_the_four_phases_and_the_handler_tile_the_rpc(served):
    impl, target, server = served()
    impl.sleep_s = 0.002
    before_counts, before = _counts(), _totals_ms()
    n = 12
    with grpc.insecure_channel(target, options=LOCAL_POOL) as channel:
        stub = PredictionServiceStub(channel)
        for i in range(n):
            stub.Predict(_request(1 << (6 + i)), timeout=30)
    _wait_for_ended(before_counts, n)
    rose = _rose(before, _totals_ms())
    parts = rose["rpc.pool_wait"] + rose["rpc.request_wait"] + rose["handler"] + rose["rpc.reply"]
    # snapshot() rounds each total to a microsecond.
    assert rose["rpc.server"] == pytest.approx(parts, abs=0.01)
    assert rose["handler"] >= n * 2.0
    assert all(rose[name] > 0 for name in STAMPED)


def test_the_tiling_holds_under_many_callers_and_a_short_switch_interval(served):
    """More callers than cores and pool threads, the interpreter switching
    threads every 10 us: every RPC is still counted once in each phase and
    the totals still tile, so no record was shared or lost between the
    poller and the pool threads."""
    impl, target, server = served(max_workers=4, listeners=2)
    callers, each = 4 * (os.cpu_count() or 4), 8
    before_counts, before = _counts(), _totals_ms()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def caller():
            with grpc.insecure_channel(target, options=LOCAL_POOL) as channel:
                stub = PredictionServiceStub(channel)
                for _ in range(each):
                    stub.Predict(_request(4096), timeout=60)

        with futures.ThreadPoolExecutor(max_workers=callers) as pool:
            for call in [pool.submit(caller) for _ in range(callers)]:
                call.result(timeout=120)
        _wait_for_ended(before_counts, callers * each)
    finally:
        sys.setswitchinterval(interval)
    assert _rose(before_counts, _counts()) == dict.fromkeys(PHASES, callers * each)
    rose = _rose(before, _totals_ms())
    parts = rose["rpc.pool_wait"] + rose["rpc.request_wait"] + rose["handler"] + rose["rpc.reply"]
    assert rose["rpc.server"] == pytest.approx(parts, abs=0.01)
    assert _nothing_left_on_the_pool_threads(server, 4)


def test_a_busy_pool_shows_in_pool_wait_and_not_in_request_wait(served):
    """One pool thread, a handler that sleeps 50 ms, two calls at once: the
    second waits for the thread, about 50 ms, before it is TAKEN; once taken,
    neither waits long for its message."""
    impl, target, server = served(max_workers=1)
    impl.sleep_s = 0.05
    before_counts, before = _counts(), _totals_ms()
    with grpc.insecure_channel(target, options=LOCAL_POOL) as channel:
        stub = PredictionServiceStub(channel)
        calls = [stub.Predict.future(_request(), timeout=30) for _ in range(2)]
        for call in calls:
            call.result()
    _wait_for_ended(before_counts, 2)
    rose = _rose(before, _totals_ms())
    assert 40.0 <= rose["rpc.pool_wait"] <= 100.0
    assert rose["rpc.request_wait"] < 20.0
    assert rose["handler"] >= 100.0


@pytest.mark.parametrize("how", ["service_error", "internal_error", "client_cancel"])
def test_a_failed_or_cancelled_rpc_ends_in_one_count_and_leaves_no_record(served, how):
    impl, target, server = served(max_workers=2)
    before = _counts()
    with grpc.insecure_channel(target, options=LOCAL_POOL) as channel:
        stub = PredictionServiceStub(channel)
        if how == "client_cancel":
            impl.sleep_s = 0.2
            call = stub.Predict.future(_request(), timeout=30)
            assert impl.entered.wait(timeout=10)
            call.cancel()
            with pytest.raises(grpc.FutureCancelledError):
                call.result()
        else:
            impl.error = (
                ServiceError("INVALID_ARGUMENT", "no such input") if how == "service_error"
                else RuntimeError("a bug"))
            with pytest.raises(grpc.RpcError) as failed:
                stub.Predict(_request(), timeout=30)
            assert failed.value.code() == (
                grpc.StatusCode.INVALID_ARGUMENT if how == "service_error"
                else grpc.StatusCode.INTERNAL)
    _wait_for_ended(before, 1)
    time.sleep(0.05)
    rose = _rose(before, _counts())
    assert {name: rose[name] for name in STAMPED} == dict.fromkeys(STAMPED, 1)
    assert rose["rpc.parse"] == 1
    # Nothing is serialized for an RPC that failed; grpc still serializes the
    # answer of a handler whose client has gone, and then drops it.
    assert rose["rpc.serialize"] == (1 if how == "client_cancel" else 0)
    assert _nothing_left_on_the_pool_threads(server, 2)


def test_two_listeners_on_one_port_both_record(served):
    """Connections are spread by the kernel's hash: open new ones until both
    listeners have carried a Predict, then every RPC of either is in
    `rpc.server` once."""
    impl, target, server = served(listeners=2)
    if len(server.servers) < 2:
        pytest.skip("this host gave the port one listener")
    before = _counts()
    snap = request_trace.snapshot()
    base = [snap.get(f"{LISTENER_PHASE}{i}", {"count": 0})["count"] for i in range(2)]
    sent = 0
    for _ in range(64):
        with grpc.insecure_channel(target, options=LOCAL_POOL) as channel:
            PredictionServiceStub(channel).Predict(_request(), timeout=30)
        sent += 1
        snap = request_trace.snapshot()
        carried = [snap.get(f"{LISTENER_PHASE}{i}", {"count": 0})["count"] - base[i]
                   for i in range(2)]
        if all(carried):
            break
    assert all(carried), carried
    _wait_for_ended(before, sent)
    assert _rose(before, _counts()) == dict.fromkeys(PHASES, sent)
    assert sum(carried) == sent


def test_parse_and_serialize_are_on_the_profilers_clock_and_the_stamped_four_are_not(
        served, monkeypatch):
    """In an open capture `rpc.parse` is annotated on the poller thread and
    `rpc.serialize` on the pool thread; no other `rpc.*` name reaches the
    profiler, so no handler-long event can take an idle gap's label."""
    from distributed_tf_serving_tpu.utils import tracing

    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        @staticmethod
        def is_enabled():
            return True

        def __enter__(self):
            seen.append((self.name, threading.current_thread().name))

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracing, "_ANNOTATION", Annotation)
    impl, target, server = served()
    before = _counts()
    with grpc.insecure_channel(target, options=LOCAL_POOL) as channel:
        PredictionServiceStub(channel).Predict(_request(), timeout=30)
    _wait_for_ended(before, 1)
    rpc = [(name, thread) for name, thread in seen if name.startswith("rpc.")]
    assert [name for name, _ in rpc] == ["rpc.parse", "rpc.serialize"]
    assert rpc[0][1].endswith("(_serve)") and rpc[1][1].startswith("rpc_")


def test_a_servicer_called_off_the_pool_records_no_stamps():
    """In-process callers (tests, the REST gateway's own path) have no pool
    thread under them: `_call` gets no record and asks the context nothing."""
    impl = _StubImpl()
    servicer = server_mod.GrpcPredictionService(impl)
    before = _counts()

    class Context:
        def time_remaining(self):
            return None

        def invocation_metadata(self):
            return ()

    assert servicer.Predict(_request(), Context()).model_spec.name == "DCN"
    assert _rose(before, _counts()) == dict.fromkeys(PHASES, 0)


# ------------------------------------------------- the native pass's own clock


def test_the_native_assembler_times_itself_inside_the_callers_clock():
    pytest.importorskip("jax")
    from distributed_tf_serving_tpu import native

    if not native.ensure():
        pytest.skip("native hostops unavailable")
    rows, fields = 4096, 64
    rng = np.random.RandomState(0)
    parts = {"feat_ids": [rng.randint(0, 1 << 40, size=(rows, fields)).astype(np.int64)]}
    layout = (rows, (("feat_ids", 32, (fields,), "int32"),))
    t0 = time.perf_counter_ns()
    out, native_ns = native.assemble_batch(layout, parts, {"feat_ids": 1009})
    wall_ns = time.perf_counter_ns() - t0
    assert out.shape == (rows * fields,)
    assert isinstance(native_ns, int) and 0 < native_ns <= wall_ns


def test_a_native_batch_adds_one_native_time_beside_one_fusedpack():
    jax = pytest.importorskip("jax")
    from distributed_tf_serving_tpu import native
    from distributed_tf_serving_tpu.models import (
        ModelConfig, Servable, build_model, ctr_signatures)
    from distributed_tf_serving_tpu.serving import DynamicBatcher

    if not native.ensure():
        pytest.skip("native hostops unavailable")
    config = ModelConfig(
        num_fields=8, vocab_size=1 << 10, embed_dim=4, mlp_dims=(16,),
        num_cross_layers=1, compute_dtype="bfloat16")
    model = build_model("dcn_v2", config)
    servable = Servable(
        name="DCN", version=1, model=model, params=model.init(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(8))
    rng = np.random.RandomState(1)
    names = ("batch.fusedpack", "batch.fusedpack_native")

    def phases():
        snap = request_trace.snapshot()
        return {n: snap.get(n, {"count": 0, "total_ms": 0.0}) for n in names}

    before = phases()
    batcher = DynamicBatcher(buckets=(16,), max_wait_us=0).start()
    try:
        for i in range(3):
            batcher.submit(servable, {
                "feat_ids": rng.randint(0, 1 << 40, size=(5 + i, 8)).astype(np.int64),
                "feat_wts": rng.rand(5 + i, 8).astype(np.float32),
            }).result(timeout=60)
        assert batcher.stats.fused_batches == 3
    finally:
        batcher.stop()
    after = phases()
    rose = {n: (after[n]["count"] - before[n]["count"],
                after[n]["total_ms"] - before[n]["total_ms"]) for n in names}
    assert rose["batch.fusedpack"][0] == rose["batch.fusedpack_native"][0] == 3
    assert 0 < rose["batch.fusedpack_native"][1] <= rose["batch.fusedpack"][1] + 0.003


# ------------------------------------------------------------------ the readers

_P = {
    "rpc.pool_wait": {"count": 100, "total_ms": 150.0},
    "rpc.request_wait": {"count": 100, "total_ms": 420.0},
    "rpc.reply": {"count": 100, "total_ms": 90.0},
    "rpc.server": {"count": 100, "total_ms": 2000.0},
    "rpc.parse": {"count": 100, "total_ms": 31.0},
    "rpc.serialize": {"count": 100, "total_ms": 4.0},
    "batch.fusedpack": {"count": 50, "total_ms": 70.0},
    "batch.fusedpack_native": {"count": 50, "total_ms": 20.0},
}


def _without(*names):
    return {name: p for name, p in _P.items() if name not in names}


@pytest.mark.parametrize(
    "reader, phases, gen, want",
    [
        ("rpc_pool_wait_ms", _P, {}, 1.5),
        ("rpc_pool_wait_ms", _without("rpc.pool_wait"), {}, None),
        ("rpc_request_wait_ms", _P, {}, 4.2),
        ("rpc_request_wait_ms", _without("rpc.request_wait"), {}, None),
        ("rpc_proto_us", _P, {}, 350.0),
        ("rpc_proto_us", _without("rpc.parse"), {}, None),
        ("rpc_proto_us", _without("rpc.serialize"), {}, None),
        ("rpc_reply_ms", _P, {}, 0.9),
        ("rpc_reply_ms", _without("rpc.reply"), {}, None),
        ("rpc_outside_ms", _P, {"mean_from_send_ms": 23.5}, 3.5),
        ("rpc_outside_ms", _without("rpc.server"), {"mean_from_send_ms": 23.5}, None),
        ("rpc_outside_ms", _P, {}, None),
        ("lock_handback_us", _P, {}, 1000.0),
        ("lock_handback_us", _without("batch.fusedpack_native"), {}, None),
        ("lock_handback_us", {"rpc.server": {"count": 0, "total_ms": 0.0}}, {}, None),
    ],
)
def test_the_new_readers_on_a_made_up_window(reader, phases, gen, want):
    """benchmark/layers/<reader>.py over a window's phase deltas: its value,
    and nothing where its phase is absent (the parent commit's runs)."""
    layers = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "layers")
    sys.path.insert(0, layers)
    try:
        from benchmark.common import load_module

        read = load_module(os.path.join(layers, reader + ".py"), "bench_layer_" + reader).read
    finally:
        sys.path.remove(layers)
    got = read({"phases": phases, "gen": gen})
    assert got is None if want is None else got == pytest.approx(want)
