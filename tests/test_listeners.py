"""Several gRPC listeners on the one port (PR 34): k `grpc.server` objects
bound through SO_REUSEPORT, each with its own completion queue and poller
thread, over the one impl, ServerMetrics, handler pool and batcher. k = 1 is
the single server; serve() derives k from the host's cores."""

import logging
import socket
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import grpc

from distributed_tf_serving_tpu.client import build_predict_request
from distributed_tf_serving_tpu import codec
from distributed_tf_serving_tpu.models import (
    ModelConfig,
    Servable,
    ServableRegistry,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.proto import PredictionServiceStub
from distributed_tf_serving_tpu.proto import health as health_proto
from distributed_tf_serving_tpu.serving import DynamicBatcher, PredictionServiceImpl
from distributed_tf_serving_tpu.serving import server as server_mod
from distributed_tf_serving_tpu.serving.server import (
    LISTENER_PHASE,
    GracefulShutdown,
    create_server,
    listener_count,
)
from distributed_tf_serving_tpu.utils.metrics import ServerMetrics
from distributed_tf_serving_tpu.utils.tracing import request_trace

F = 6
CFG = ModelConfig(
    name="DCN", num_fields=F, vocab_size=1 << 12, embed_dim=8,
    mlp_dims=(16,), num_cross_layers=1, compute_dtype="float32",
)
LOCAL_POOL = [("grpc.use_local_subchannel_pool", 1)]


def _stack():
    model = build_model("dcn_v2", CFG)
    sv = Servable(
        name="DCN", version=1, model=model,
        params=model.init(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(F),
    )
    registry = ServableRegistry()
    registry.load(sv)
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0).start()
    impl = PredictionServiceImpl(registry, batcher)
    impl.warmup_complete = True
    return impl, sv, batcher


@pytest.fixture(scope="module")
def stack():
    impl, sv, batcher = _stack()
    yield impl, sv
    batcher.stop()


def _arrays(n=4, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "feat_ids": rng.randint(0, 1 << 40, size=(n, F)).astype(np.int64),
        "feat_wts": rng.rand(n, F).astype(np.float32),
    }


def _want(sv, arrays):
    return np.asarray(sv.model.apply(sv.params, {
        "feat_ids": arrays["feat_ids"] % CFG.vocab_size,
        "feat_wts": arrays["feat_wts"],
    })["prediction_node"])


def _predict(target, arrays, credentials=None):
    channel = (
        grpc.insecure_channel(target, options=LOCAL_POOL) if credentials is None
        else grpc.secure_channel(target, credentials, options=LOCAL_POOL)
    )
    with channel:
        resp = PredictionServiceStub(channel).Predict(
            build_predict_request(arrays, "DCN"), timeout=30
        )
    return codec.to_ndarray(resp.outputs["prediction_node"])


def _listener_counts(k):
    phases = request_trace.snapshot()
    return [phases.get(f"{LISTENER_PHASE}{i}", {"count": 0})["count"] for i in range(k)]


def _serve_threads():
    return {t for t in threading.enumerate() if t.name.endswith("(_serve)")}


@pytest.mark.parametrize("k", [1, 2, 4])
def test_every_channel_is_answered_and_every_rpc_counted(stack, k):
    """3k connections over k listeners on port 0: a right answer on every
    one, the per-listener counts sum to the RPCs sent, one ServerMetrics
    saw them all, and k poller threads were started."""
    impl, sv = stack
    metrics = ServerMetrics()
    before_threads = _serve_threads()
    server, port = create_server(impl, "127.0.0.1:0", metrics=metrics, listeners=k)
    assert len(server.servers) == k and port > 0
    before = _listener_counts(k)
    server.start()
    try:
        assert len(_serve_threads() - before_threads) == k
        for i in range(3 * k):
            arrays = _arrays(n=3 + i % 3, seed=i)
            got = _predict(f"127.0.0.1:{port}", arrays)
            np.testing.assert_allclose(got, _want(sv, arrays), rtol=1e-5)
        delta = [a - b for a, b in zip(_listener_counts(k), before)]
        assert sum(delta) == 3 * k and all(d >= 0 for d in delta)
        assert metrics.rpc("Predict").latency.count == 3 * k
    finally:
        server.stop(0).wait()


@pytest.mark.parametrize("k", [1, 2, 4])
def test_every_listener_serves_health_and_predict(stack, k):
    """Which listener the kernel hands a connection to cannot be chosen, so
    each listener is also given a port of its own: over it, health `Check`
    answers SERVING, a Predict is right, and it is counted under THAT
    listener."""
    impl, sv = stack
    server, _port = create_server(impl, "127.0.0.1:0", listeners=k)
    own = [s.add_insecure_port("127.0.0.1:0") for s in server.servers]
    assert all(own) and len(set(own)) == k
    server.start()
    try:
        arrays = _arrays(seed=7)
        for i, port in enumerate(own):
            with grpc.insecure_channel(f"127.0.0.1:{port}", options=LOCAL_POOL) as ch:
                check = ch.unary_unary(
                    "/grpc.health.v1.Health/Check",
                    request_serializer=health_proto.HealthCheckRequest.SerializeToString,
                    response_deserializer=health_proto.HealthCheckResponse.FromString,
                )
                reply = check(health_proto.HealthCheckRequest(service=""), timeout=10)
            assert reply.status == health_proto.SERVING
            before = _listener_counts(k)
            np.testing.assert_allclose(
                _predict(f"127.0.0.1:{port}", arrays), _want(sv, arrays), rtol=1e-5)
            delta = [a - b for a, b in zip(_listener_counts(k), before)]
            assert delta == [int(j == i) for j in range(k)]
    finally:
        server.stop(0).wait()


@pytest.mark.parametrize("k", [1, 3])
def test_graceful_shutdown_stops_every_listener(k):
    """GracefulShutdown's one path: afterwards nothing accepts on the port
    and none of the k poller threads is alive."""
    impl, _sv, batcher = _stack()
    before_threads = _serve_threads()
    server, port = create_server(impl, "127.0.0.1:0", listeners=k)
    server.start()
    pollers = _serve_threads() - before_threads
    assert len(pollers) == k
    _predict(f"127.0.0.1:{port}", _arrays())
    shutdown = GracefulShutdown(impl, batcher, grace_s=2.0)
    shutdown.server = server
    shutdown.shutdown()
    assert shutdown.drained is True
    assert not server.wait_for_termination(timeout=5.0)
    for t in pollers:
        t.join(timeout=5.0)
    assert not any(t.is_alive() for t in pollers)
    for _ in range(2 * k):
        with pytest.raises(OSError):
            socket.create_connection(("127.0.0.1", port), timeout=2.0).close()


def test_wait_for_termination_times_out_while_serving(stack):
    impl, _sv = stack
    server, _port = create_server(impl, "127.0.0.1:0", listeners=2)
    server.start()
    try:
        assert server.wait_for_termination(timeout=0.05) is True
        stopped = server.stop(None)
        assert stopped.is_set() and stopped.wait(1.0)
        assert server.wait_for_termination(timeout=5.0) is False
    finally:
        server.stop(0)


@pytest.mark.parametrize("asked, refused_from", [(2, 2), (4, 3), (4, 4)])
def test_refused_bind_falls_back_to_one_listener(stack, monkeypatch, caplog, asked, refused_from):
    """A further listener that cannot bind the port: the first serves alone,
    with one log line, and no connection lands on a listener that was let go
    (one that had bound before the refusal would otherwise be handed its
    share and never accept)."""
    impl, sv = stack
    real_bind, calls = server_mod._bind, []

    def bind(server, address, credentials):
        calls.append(address)
        return 0 if len(calls) >= refused_from else real_bind(server, address, credentials)

    monkeypatch.setattr(server_mod, "_bind", bind)
    with caplog.at_level(logging.WARNING, logger="dts_tpu.server"):
        server, port = create_server(impl, "127.0.0.1:0", listeners=asked)
    assert len(server.servers) == 1 and len(calls) == refused_from
    assert calls[1:] == [f"127.0.0.1:{port}"] * (refused_from - 1)
    lines = [r for r in caplog.records if "could not bind" in r.getMessage()]
    assert len(lines) == 1 and "the first serves alone" in lines[0].getMessage()
    server.start()
    try:
        arrays = _arrays(seed=3)
        for _ in range(3 * asked):
            np.testing.assert_allclose(
                _predict(f"127.0.0.1:{port}", arrays), _want(sv, arrays), rtol=1e-5)
    finally:
        server.stop(0).wait()


def test_no_reuseport_means_one_listener(stack, monkeypatch, caplog):
    impl, _sv = stack
    monkeypatch.delattr(socket, "SO_REUSEPORT")
    with caplog.at_level(logging.WARNING, logger="dts_tpu.server"):
        server, _port = create_server(impl, "127.0.0.1:0", listeners=4)
    assert len(server.servers) == 1
    assert len([r for r in caplog.records if "SO_REUSEPORT" in r.getMessage()]) == 1
    server.start()
    server.stop(0).wait()


def test_first_bind_failure_still_raises(stack):
    impl, _sv = stack
    with socket.socket() as taken:
        taken.bind(("127.0.0.1", 0))
        taken.listen(1)
        with pytest.raises(RuntimeError, match="could not bind"):
            create_server(impl, f"127.0.0.1:{taken.getsockname()[1]}", listeners=2)


@pytest.mark.parametrize("k", [2, 4])
def test_unix_socket_is_on_the_first_listener_only(stack, tmp_path, monkeypatch, k):
    """[transport] uds_path with k > 1: one socket file, bound by the first
    listener; TCP and the socket both answer."""
    impl, sv = stack
    uds = str(tmp_path / "dts.sock")
    real_add, bound_by = server_mod._add_uds_port, []
    monkeypatch.setattr(
        server_mod, "_add_uds_port",
        lambda server, path: (bound_by.append(server), real_add(server, path)))
    server, port = create_server(impl, "127.0.0.1:0", uds_path=uds, listeners=k)
    assert len(server.servers) == k and bound_by == [server.servers[0]]
    server.start()
    try:
        arrays = _arrays(seed=5)
        before = _listener_counts(k)
        for _ in range(3):
            np.testing.assert_allclose(_predict(f"unix:{uds}", arrays), _want(sv, arrays), rtol=1e-5)
        delta = [a - b for a, b in zip(_listener_counts(k), before)]
        assert delta == [3] + [0] * (k - 1)
        np.testing.assert_allclose(_predict(f"127.0.0.1:{port}", arrays), _want(sv, arrays), rtol=1e-5)
    finally:
        server.stop(0).wait()


@pytest.mark.parametrize("k", [1, 2])
def test_unix_socket_next_to_tls_is_refused_for_any_k(stack, tmp_path, k):
    impl, _sv = stack
    with pytest.raises(ValueError, match="plaintext"):
        create_server(
            impl, "127.0.0.1:0", credentials=object(),
            uds_path=str(tmp_path / "dts.sock"), listeners=k,
        )


@pytest.mark.parametrize(
    "cores, k",
    [(1, 1), (2, 1), (3, 1), (5, 1), (6, 2), (8, 2), (9, 3), (12, 4), (13, 4), (30, 4), (224, 4)],
)
def test_listener_count_follows_the_cores(monkeypatch, cores, k):
    """k is derived from what the process may run on: one on a small host,
    capped on a large one. No option, flag or environment variable."""
    monkeypatch.setattr(server_mod.os, "sched_getaffinity", lambda pid: set(range(cores)))
    assert listener_count() == (k, cores)


def test_listener_count_without_affinity_uses_cpu_count(monkeypatch):
    monkeypatch.delattr(server_mod.os, "sched_getaffinity")
    monkeypatch.setattr(server_mod.os, "cpu_count", lambda: 16)
    assert listener_count() == (min(server_mod.MAX_LISTENERS, 16 // server_mod.CORES_A_LISTENER), 16)
    monkeypatch.setattr(server_mod.os, "cpu_count", lambda: None)
    assert listener_count() == (1, 1)


@pytest.mark.parametrize(
    "phases, want",
    [
        ({}, None),  # a window that answered nothing
        ({"predict.execute": {"count": 40, "total_ms": 9.0}}, 100.0),  # one listener, no such phase
        ({"predict.execute": {"count": 40, "total_ms": 9.0},
          "rpc.listener0": {"count": 30, "total_ms": 9.0},
          "rpc.listener1": {"count": 10, "total_ms": 3.0}}, 75.0),
        ({"rpc.listener0": {"count": 0, "total_ms": 0.0},
          "rpc.listener3": {"count": 12, "total_ms": 3.0}}, 100.0),
        ({f"rpc.listener{i}": {"count": 5, "total_ms": 1.0} for i in range(4)}, 25.0),
    ],
)
def test_busiest_listener_reader(phases, want):
    """benchmark/layers/busiest_listener_pct.py over a window's phase deltas."""
    import os
    import sys

    layers = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "layers")
    sys.path.insert(0, layers)
    try:
        from benchmark.common import load_module

        read = load_module(os.path.join(layers, "busiest_listener_pct.py"), "busiest_listener_pct").read
    finally:
        sys.path.remove(layers)
    assert read({"phases": phases}) == want


def test_channels_per_host_are_connections_of_their_own(stack):
    """`ShardedPredictClient(channels_per_host=4)` opens four CONNECTIONS
    (grpc would share one between channels of equal target and arguments),
    so its requests reach more than one listener. The kernel's hash may put
    four connections on one listener of four once in 64 clients; three
    clients in a row is one in 262,144."""
    import asyncio

    from distributed_tf_serving_tpu.client import ShardedPredictClient

    impl, sv = stack
    server, port = create_server(impl, "127.0.0.1:0", listeners=4)
    server.start()
    try:
        async def one_client(channels):
            before = _listener_counts(4)
            async with ShardedPredictClient(
                [f"127.0.0.1:{port}"], "DCN", channels_per_host=channels
            ) as client:
                for i in range(8):
                    arrays = _arrays(seed=i)
                    np.testing.assert_allclose(
                        await client.predict(arrays), _want(sv, arrays), rtol=1e-5)
            return sum(a > b for a, b in zip(_listener_counts(4), before))

        assert asyncio.run(one_client(1)) == 1
        assert max([asyncio.run(one_client(4)) for _ in range(3)]) > 1
    finally:
        server.stop(0).wait()
