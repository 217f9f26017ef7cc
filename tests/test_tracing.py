"""End-to-end request tracing + live telemetry plane (ISSUE 3):
W3C traceparent propagation client->server, per-request span trees through
the batcher's phases, hedge/failover sibling spans, fault-injection
annotations, deterministic tail sampling, Chrome-trace export, rolling-
window metrics with per-model labels, and the /tracez + /monitoring REST
surfaces."""

import asyncio
import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")
aiohttp = pytest.importorskip("aiohttp")

from distributed_tf_serving_tpu import faults
from distributed_tf_serving_tpu.client import (
    ShardedPredictClient,
    build_predict_request,
)
from distributed_tf_serving_tpu.models import (
    ModelConfig,
    Servable,
    ServableRegistry,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.serving import (
    DynamicBatcher,
    PredictionServiceImpl,
    create_server,
)
from distributed_tf_serving_tpu.serving.rest import start_rest_gateway
from distributed_tf_serving_tpu.utils import tracing
from distributed_tf_serving_tpu.utils.metrics import (
    LatencyHistogram,
    ServerMetrics,
    WindowedLatency,
    escape_label_value,
    resilience_prometheus_text,
)

F = 8
CFG = ModelConfig(
    num_fields=F, vocab_size=1009, embed_dim=4, mlp_dims=(16,),
    num_cross_layers=1, compute_dtype="float32",
)


def _servable(seed=0):
    model = build_model("dcn_v2", CFG)
    return Servable(
        name="DCN", version=1, model=model,
        params=model.init(jax.random.PRNGKey(seed)),
        signatures=ctr_signatures(F),
    )


def _arrays(n=9, seed=3):
    rng = np.random.RandomState(seed)
    return {
        "feat_ids": rng.randint(0, 1 << 40, size=(n, F)).astype(np.int64),
        "feat_wts": rng.rand(n, F).astype(np.float32),
    }


@pytest.fixture(autouse=True)
def _clean_tracing_and_faults():
    faults.reset(seed=0)
    yield
    faults.reset(seed=0)
    tracing.disable()


@pytest.fixture(scope="module")
def two_backends():
    servers, hosts, batchers = [], [], []
    for _ in range(2):
        registry = ServableRegistry()
        registry.load(_servable())
        batcher = DynamicBatcher(buckets=(32, 128), max_wait_us=0).start()
        impl = PredictionServiceImpl(registry, batcher)
        server, port = create_server(impl, "127.0.0.1:0")
        server.start()
        servers.append(server)
        batchers.append(batcher)
        hosts.append(f"127.0.0.1:{port}")
    yield hosts
    for s in servers:
        s.stop(0)
    for b in batchers:
        b.stop()


def _names(span):
    return [s.name for s in span.walk()]


def _by_name(recorder, name):
    return [s for s in recorder.spans() if s.name == name]


# ------------------------------------------------- traceparent plumbing


def test_traceparent_roundtrip_helpers():
    tp = tracing.make_traceparent("ab" * 16, "cd" * 8)
    assert tp == f"00-{'ab' * 16}-{'cd' * 8}-01"
    assert tracing.parse_traceparent(tp) == ("ab" * 16, "cd" * 8)
    # Malformed headers degrade to None, never raise.
    for bad in (None, "", "garbage", "00-short-cdcd-01",
                f"00-{'0' * 32}-{'cd' * 8}-01",  # all-zero trace id
                f"00-{'zz' * 16}-{'cd' * 8}-01"):
        assert tracing.parse_traceparent(bad) is None


def test_traceparent_propagation_round_trip(two_backends):
    """Client root and server spans share ONE trace id, and each server
    span parents onto the exact client attempt span that carried it."""
    rec = tracing.enable(sample_rate=1.0)

    async def drive():
        async with ShardedPredictClient(two_backends, "DCN") as client:
            return await client.predict(_arrays(10), sort_scores=True)

    scores = asyncio.run(drive())
    assert scores.shape == (10,)
    roots = _by_name(rec, "client.predict")
    assert len(roots) == 1
    root = roots[0]
    servers = _by_name(rec, "server.Predict")
    assert len(servers) == 2  # one per backend shard
    rpc_ids = {s.span_id for s in root.walk() if s.name == "client.rpc"}
    for sv in servers:
        assert sv.trace_id == root.trace_id  # round-trip ids match
        assert sv.remote_parent and sv.parent_id in rpc_ids
        assert sv.attrs.get("model") == "DCN"


def test_span_tree_covers_batcher_phases(two_backends):
    """A server span tree decomposes the request: queue wait, the device
    stage (dispatch/jit), the readback, and decode/encode."""
    rec = tracing.enable(sample_rate=1.0)

    async def drive():
        async with ShardedPredictClient(two_backends[:1], "DCN") as client:
            await client.predict(_arrays(12))

    asyncio.run(drive())
    (server,) = _by_name(rec, "server.Predict")
    names = _names(server)
    for phase in ("predict.decode", "batch.queue_wait", "batch.dispatch",
                  "predict.execute", "predict.encode"):
        assert phase in names, f"{phase} missing from {names}"
    assert "readback.wait" in names
    # Phase intervals sit inside the server span's window.
    for child in server.children:
        assert child.start >= server.start - 1e-3
        assert child.end is not None and child.end <= server.end + 1e-3


def test_failover_attempts_are_sibling_spans(two_backends):
    """A rerouted shard shows BOTH attempts under one shard span: the
    failed attempt (with its status code) and the winning one."""
    rec = tracing.enable(sample_rate=1.0)
    # count=1: the first attempt on the (single) host fails, the wrap-
    # around retry on the same host succeeds — a transient blip.
    faults.get().add(
        "client.rpc", "error", code="UNAVAILABLE", key=two_backends[0],
        count=1,
    )

    async def drive():
        async with ShardedPredictClient(
            two_backends[:1], "DCN", failover_attempts=1,
            backoff_initial_s=0.0,
        ) as client:
            return await client.predict(_arrays(6))

    scores = asyncio.run(drive())
    assert scores.shape == (6,)
    (root,) = _by_name(rec, "client.predict")
    shards = [s for s in root.children if s.name == "client.shard"]
    assert len(shards) == 1
    attempts = [s for s in shards[0].children if s.name == "client.rpc"]
    assert len(attempts) == 2  # failed primary + failover hop, siblings
    assert attempts[0].status == "ERROR"
    assert attempts[0].attrs.get("code") == "UNAVAILABLE"
    assert attempts[1].status == "OK"
    assert [a.attrs.get("attempt") for a in attempts] == [0, 1]
    # Error traces are tail-kept even at sample_rate 0 — verified by the
    # recorder classifying this root as error-bearing.
    assert root.has_error()


def test_hedged_attempt_is_sibling_span(two_backends):
    """A hedge fired against a slow primary appears as a sibling attempt
    span flagged hedge=True (and the winner resolves the shard)."""
    rec = tracing.enable(sample_rate=1.0)
    faults.get().add(
        "client.rpc", "delay", delay_s=0.5, key=two_backends[0]
    )

    async def drive():
        async with ShardedPredictClient(
            two_backends, "DCN", hedge_delay_s=0.05,
        ) as client:
            # Two hosts -> two shards; shard 0's primary (host 0) stalls.
            return await client.predict(_arrays(8))

    scores = asyncio.run(drive())
    assert scores.shape == (8,)
    (root,) = _by_name(rec, "client.predict")
    rpcs = [s for s in root.walk() if s.name == "client.rpc"]
    hedges = [s for s in rpcs if s.attrs.get("hedge")]
    assert len(hedges) == 1
    assert hedges[0].attrs["host"] == two_backends[1]


def test_fault_annotations_under_env_grammar(two_backends, monkeypatch):
    """DTS_TPU_FAULTS-installed rules annotate the span they land on:
    decode chaos on the server root, batcher.dispatch chaos replayed onto
    every co-batched request's span."""
    rec = tracing.enable(sample_rate=1.0)
    monkeypatch.setenv(
        "DTS_TPU_FAULTS",
        "decode=delay,delay=0.001;batcher.dispatch=delay,delay=0.001",
    )
    assert faults.configure_from_env() == 2

    async def drive():
        async with ShardedPredictClient(two_backends[:1], "DCN") as client:
            await client.predict(_arrays(5))

    asyncio.run(drive())
    (server,) = _by_name(rec, "server.Predict")
    messages = {a["message"] for a in server.annotations}
    assert "fault.decode" in messages
    assert "fault.batcher.dispatch" in messages
    kinds = {a["message"]: a.get("kind") for a in server.annotations}
    assert kinds["fault.decode"] == "delay"
    # Annotated traces are tail-kept.
    assert server.has_annotations()


# ---------------------------------------------------------- tail sampling


def _finished_root(name, dur_s, error=False, annotated=False):
    sp = tracing.Span(name)
    sp.end = sp.start + dur_s
    if error:
        sp.status = "ERROR"
    if annotated:
        sp.annotations.append({"t": sp.start, "message": "fault.x"})
    return sp


def test_tail_sampler_keeps_errors_and_slowest_deterministically():
    rec = tracing.TraceRecorder(buffer_size=64, sample_rate=0.0, slowest_n=2)
    slow = [_finished_root(f"slow{i}", float(i)) for i in (1, 2, 3, 4, 5)]
    err = _finished_root("err", 0.001, error=True)
    ann = _finished_root("ann", 0.002, annotated=True)
    for sp in slow + [err, ann]:
        rec.record(sp)
    kept = {s.name for s in rec.spans()}
    # sample_rate 0: ONLY the tails survive — errors, annotated, slowest-2.
    assert kept == {"err", "ann", "slow4", "slow5"}
    assert [s.name for s in rec.slowest()] == ["slow5", "slow4"]
    assert rec.recorded == 7
    assert rec.dropped == 3  # slow1..slow3 (slow4/5 live in the heap)


def test_cancelled_span_is_not_an_error():
    """A hedge loser dies by asyncio.CancelledError BY DESIGN: its span
    must read CANCELLED, not ERROR — or every healthy hedged request
    would be tail-kept and reported as a failure in /tracez."""
    rec = tracing.enable(sample_rate=1.0)
    with pytest.raises(asyncio.CancelledError):
        with tracing.start_root("client.predict"):
            with tracing.start_span("client.rpc"):
                raise asyncio.CancelledError()
    (root,) = rec.spans()
    assert root.children[0].status == "CANCELLED"
    assert not root.has_error()


def test_model_label_cardinality_is_bounded():
    """Client-supplied model names must not grow series without bound:
    past the cap, overflow names aggregate under the sentinel label."""
    m = ServerMetrics()
    for i in range(ServerMetrics.MAX_MODEL_LABELS + 40):
        m.observe("Predict", 0.001, ok=True, model=f"fuzz-{i}")
    models = m.snapshot()["models"]
    assert len(models) <= ServerMetrics.MAX_MODEL_LABELS + 1
    assert models[ServerMetrics.OVERFLOW_MODEL]["Predict"]["ok"] >= 40


def test_sampler_rate_one_keeps_everything_without_rng():
    rec = tracing.TraceRecorder(buffer_size=8, sample_rate=1.0, slowest_n=0)
    for i in range(12):
        rec.record(_finished_root(f"s{i}", 0.01))
    names = [s.name for s in rec.spans()]
    assert len(names) == 8  # ring bound holds
    assert names == [f"s{i}" for i in range(4, 12)]  # newest retained


# ------------------------------------------------------------ Chrome export


def test_chrome_export_schema_and_monotonic_ts(two_backends, tmp_path):
    rec = tracing.enable(sample_rate=1.0)

    async def drive():
        async with ShardedPredictClient(two_backends[:1], "DCN") as client:
            for _ in range(3):
                await client.predict(_arrays(4))

    asyncio.run(drive())
    doc = rec.chrome_trace()
    events = doc["traceEvents"]
    assert events
    spans = [e for e in events if e["ph"] == "X"]
    assert spans
    for ev in spans:
        assert isinstance(ev["ts"], int) and ev["ts"] >= 0
        assert isinstance(ev["dur"], int) and ev["dur"] >= 0
        assert ev["args"]["trace_id"] and ev["args"]["span_id"]
    # Parent/child containment: every phase event's window sits inside
    # some root span event of the same pid/tid.
    roots = {
        (e["pid"], e["tid"]): e for e in spans if e["cat"] == "span"
    }
    for ev in spans:
        if ev["cat"] == "phase":
            parent = roots[(ev["pid"], ev["tid"])]
            assert ev["ts"] >= parent["ts"] - 1000
            assert ev["ts"] + ev["dur"] <= parent["ts"] + parent["dur"] + 1000
    # The file form round-trips as JSON (what tools/check_trace.py gates):
    # the document is written as /tracez?format=chrome and tools/soak.py do.
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(rec.chrome_trace()))
    assert len(json.loads(path.read_text())["traceEvents"]) == len(events)


# ---------------------------------------------------- rolling-window metrics


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def test_rolling_qps_does_not_decay_like_lifetime():
    clock = FakeClock()
    m = ServerMetrics(window_s=60.0, clock=clock)
    # A server 8 s old serving 15 req/s must report ~15 qps, not
    # 120/60: the divisor shrinks to the actual age while younger than
    # the window.
    clock.t += 8
    for _ in range(120):
        m.observe("Predict", 0.004, ok=True)
    snap = m.snapshot()
    assert snap["qps"] == pytest.approx(120 / 8.0, rel=1e-6)
    # Half a window later the divisor is the elapsed 38 s.
    clock.t += 30
    snap = m.snapshot()
    assert snap["qps"] == pytest.approx(120 / 38.0, abs=0.01)
    # Idle for 10 minutes: the rolling rate goes to zero, the lifetime
    # value keeps decaying but stays nonzero — and the two are DISTINCT
    # keys (the old single `qps` conflated them).
    clock.t += 600
    snap = m.snapshot()
    assert snap["qps"] == 0.0
    assert 0 < snap["qps_lifetime"] < 1.0
    assert snap["rpcs"]["Predict"]["window"]["qps"] == 0.0
    assert snap["rpcs"]["Predict"]["count"] == 120  # lifetime untouched


def test_windowed_percentiles_reflect_recent_traffic_only():
    clock = FakeClock()
    w = WindowedLatency(window_s=60.0, slices=6, clock=clock)
    for _ in range(50):
        w.record(0.100)  # 100 ms regime
    snap = w.snapshot()
    assert snap["count"] == 50
    assert snap["p50_ms"] == pytest.approx(100, rel=0.2)
    # Regime change: 70 s later the old slice aged out entirely.
    clock.t += 70
    for _ in range(50):
        w.record(0.002)
    snap = w.snapshot()
    assert snap["count"] == 50
    assert snap["p50_ms"] == pytest.approx(2, rel=0.3)
    assert snap["p99_ms"] < 50  # the 100 ms regime is gone from the window


def test_per_model_labels_in_snapshot_and_prometheus():
    clock = FakeClock()
    m = ServerMetrics(window_s=60.0, clock=clock)
    m.observe("Predict", 0.01, ok=True, model="DCN")
    m.observe("Predict", 0.02, ok=True, model="DLRM")
    m.observe("Predict", 0.03, ok=False, model="DCN")
    snap = m.snapshot()
    assert snap["models"]["DCN"]["Predict"]["ok"] == 1
    assert snap["models"]["DCN"]["Predict"]["errors"] == 1
    assert snap["models"]["DLRM"]["Predict"]["ok"] == 1
    assert snap["models"]["DCN"]["Predict"]["window"]["qps"] > 0
    text = m.prometheus_text()
    assert 'dts_tpu_model_request_count{entrypoint="Predict",model_name="DCN",status="OK"} 1' in text
    assert 'dts_tpu_model_window_qps{entrypoint="Predict",model_name="DLRM"}' in text
    assert 'quantile="0.99"' in text
    # The TF-Serving-named aggregate series keep their label shape.
    assert ':tensorflow:serving:request_count{entrypoint="Predict",status="OK"} 2' in text


def test_prometheus_label_escaping():
    assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    m = ServerMetrics()
    weird = 'mo"del\\one\nx'
    m.observe("Predict", 0.01, ok=True, model=weird)
    text = m.prometheus_text()
    # Every exposition line stays a single line with a numeric value —
    # the raw quote/backslash/newline never leaks into the framing.
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        _, _, value = ln.rpartition(" ")
        float(value)  # malformed framing would put label text here
    assert 'model_name="mo\\"del\\\\one\\nx"' in text


def test_latency_histogram_snapshot_is_internally_consistent():
    h = LatencyHistogram()
    for ms in (1, 2, 3, 4, 5):
        h.record(ms / 1e3)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["mean_ms"] == pytest.approx(3.0, rel=0.05)
    assert h.count == 5
    assert h.mean_ms() == pytest.approx(3.0, rel=0.05)


def test_resilience_prometheus_text():
    text = resilience_prometheus_text({
        "hedges_fired": 3, "hedges_won": 2, "failovers": 1,
        "backoff_sleeps": 0, "partial_responses": 4,
        "scoreboard": {
            "ejections": 2, "probes": 5, "recoveries": 1,
            "backends": {
                "10.0.0.1:9999": {
                    "state": "ejected", "ewma_ms": 12.5,
                    "consecutive_failures": 3, "successes": 10, "failures": 4,
                },
            },
        },
    })
    assert "dts_tpu_client_hedges_fired_total 3" in text
    assert "dts_tpu_client_ejections_total 2" in text
    assert 'dts_tpu_client_backend_up{host="10.0.0.1:9999",state="ejected"} 0' in text
    assert 'dts_tpu_client_backend_ewma_ms{host="10.0.0.1:9999"} 12.5' in text


# ------------------------------------------------------------ REST surfaces


def _rest_run(impl, handler):
    async def go():
        runner, port = await start_rest_gateway(impl, port=0)
        try:
            async with aiohttp.ClientSession(
                f"http://127.0.0.1:{port}"
            ) as session:
                return await handler(session)
        finally:
            await runner.cleanup()

    return asyncio.run(go())


@pytest.fixture(scope="module")
def impl_stack():
    registry = ServableRegistry()
    registry.load(_servable())
    batcher = DynamicBatcher(buckets=(32, 64), max_wait_us=0).start()
    yield PredictionServiceImpl(registry, batcher)
    batcher.stop()


def test_tracez_and_monitoring_endpoints(impl_stack):
    rec = tracing.enable(sample_rate=1.0, slowest_n=4)
    arrays = _arrays(4)
    body = {"inputs": {k: v.tolist() for k, v in arrays.items()}}

    async def handler(session):
        for _ in range(3):
            async with session.post("/v1/models/DCN:predict", json=body) as r:
                assert r.status == 200
        async with session.get("/tracez") as r:
            tz = (r.status, await r.json())
        async with session.get("/tracez?format=chrome") as r:
            chrome = (r.status, await r.json())
        async with session.get("/monitoring") as r:
            mon = (r.status, await r.json())
        return tz, chrome, mon

    (tz_status, tz), (ch_status, chrome), (mon_status, mon) = _rest_run(
        impl_stack, handler
    )
    assert tz_status == ch_status == mon_status == 200
    assert tz["enabled"] is True
    assert tz["recorded"] >= 3
    assert tz["traces"] and tz["slowest"]
    tree = tz["traces"][0]["spans"][0]
    assert {"name", "trace_id", "span_id", "duration_us", "children"} <= set(tree)
    assert chrome["traceEvents"]
    # /monitoring: rolling windows + per-model labels + phases all present.
    assert "qps" in mon and "qps_lifetime" in mon
    assert mon["rpcs"]["REST.Predict"]["window"]["qps"] > 0
    assert mon["models"]["DCN"]["REST.Predict"]["ok"] == 3
    assert mon["tracing"]["enabled"] is True
    assert "phases" in mon
    # The slowest-N query surface answers the "explain THIS request" ask.
    assert len(tz["slowest"]) <= 4
    rec2 = tracing.recorder()
    assert rec2 is rec


def test_tracing_disabled_is_inert(impl_stack):
    """With tracing off (the default), requests run and /tracez answers
    with an empty, disabled recorder — no spans accumulate anywhere."""
    tracing.disable()
    before = tracing.recorder().recorded
    arrays = _arrays(4)
    body = {"inputs": {k: v.tolist() for k, v in arrays.items()}}

    async def handler(session):
        async with session.post("/v1/models/DCN:predict", json=body) as r:
            assert r.status == 200
        async with session.get("/tracez") as r:
            return await r.json()

    tz = _rest_run(impl_stack, handler)
    assert tz["enabled"] is False
    assert tracing.recorder().recorded == before


def test_batcher_submit_ignores_span_when_disabled(impl_stack):
    """submit(span=...) with tracing off must not retain the handle (the
    <=1%-overhead contract: disabled tracing leaves no per-request work
    or references behind)."""
    tracing.disable()
    sp = tracing.Span("orphan")
    servable = impl_stack.registry.resolve("DCN", None, None)
    fut = impl_stack.batcher.submit(servable, _arrays(4), span=sp)
    fut.result(timeout=30)
    assert not sp.children
