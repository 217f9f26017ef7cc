"""Adaptive overload control + graceful degradation (ISSUE 5,
serving/overload.py): AIMD limit convergence under a fake clock, doomed-
work refusal at enqueue, criticality-lane shed ordering, the pressure
state machine (including the deterministic `pressure` fault site),
brownout stale-serve through the real batcher (degraded marker set, no
cache fill, stale window respected), retry-after pushback honored by the
client's failover backoff, pushback-never-ejects on the scoreboard, and
the SIGTERM-driven graceful drain serving every accepted request."""

import asyncio
import os
import signal
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from distributed_tf_serving_tpu import faults
from distributed_tf_serving_tpu.cache import ScoreCache
from distributed_tf_serving_tpu.client import (
    BackendScoreboard,
    PredictClientError,
    ScoreboardConfig,
    ShardedPredictClient,
    build_predict_request,
)
from distributed_tf_serving_tpu.client import client as client_mod
from distributed_tf_serving_tpu.client.health import EJECTED, HEALTHY
from distributed_tf_serving_tpu.models import (
    ModelConfig,
    Servable,
    ServableRegistry,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.proto import health as health_proto
from distributed_tf_serving_tpu.serving import (
    DynamicBatcher,
    PredictionServiceImpl,
    ServiceError,
    create_server,
)
from distributed_tf_serving_tpu.serving import overload as overload_mod
from distributed_tf_serving_tpu.serving.batcher import (
    AdmissionRefusedError,
    QueueOverloadError,
)
from distributed_tf_serving_tpu.serving.overload import (
    BROWNOUT,
    NOMINAL,
    SHED,
    AdmissionController,
)
from distributed_tf_serving_tpu.serving.server import GracefulShutdown, GrpcHealthService
from distributed_tf_serving_tpu.utils.config import OverloadConfig, load_config

F = 6
CFG = ModelConfig(
    name="DCN", num_fields=F, vocab_size=1 << 10, embed_dim=4,
    mlp_dims=(8,), num_cross_layers=1, compute_dtype="float32",
)


@pytest.fixture(scope="module")
def servable():
    model = build_model("dcn", CFG)
    return Servable(
        name="DCN", version=1, model=model,
        params=model.init(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(F),
    )


def make_arrays(n, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "feat_ids": rng.randint(0, 1 << 40, size=(n, F)).astype(np.int64),
        "feat_wts": rng.rand(n, F).astype(np.float32),
    }


@pytest.fixture(autouse=True)
def _clean_overload_state():
    """Constructing an AdmissionController flips the module-global fast
    path on; leaked state would make unrelated tests scan metadata (or
    consume stray degraded markers) nondeterministically."""
    yield
    faults.reset()
    overload_mod._set_active(False)
    overload_mod.consume_degraded()


def _cfg(**kw) -> OverloadConfig:
    return OverloadConfig(enabled=True, **kw)


# ------------------------------------------------------- AIMD convergence


def test_limit_converges_down_then_up_with_fake_clock():
    clock = [0.0]
    ctrl = AdmissionController(
        _cfg(
            target_queue_wait_ms=50.0, queue_wait_window_s=1.0,
            adjust_interval_s=0.5, increase_candidates=10,
            decrease_factor=0.5, min_limit_candidates=16,
            max_limit_candidates=128,
        ),
        clock=lambda: clock[0],
    )
    assert ctrl.limit == 128  # starts at max: unloaded == static bound
    # Sustained over-target queue wait: multiplicative shrink to the floor.
    for want in (64, 32, 16, 16):
        ctrl.note_queue_wait(0.2)  # 200ms >> 50ms target
        clock[0] += 0.6
        ctrl.state()  # opportunistic tick
        assert ctrl.limit == want
    assert ctrl.limit_decreases == 3
    # Pressure gone (samples age out of the window): additive growth back
    # to the max, never past it.
    clock[0] += 2.0
    for _ in range(20):
        ctrl.note_queue_wait(0.001)
        clock[0] += 0.6
        ctrl.state()
    assert ctrl.limit == 128
    assert ctrl.limit_increases >= 11
    snap = ctrl.snapshot()
    assert snap["min_limit"] == 16 and snap["max_limit"] == 128


def test_bind_resolves_auto_limits_from_batcher_geometry():
    ctrl = AdmissionController(_cfg(), clock=lambda: 0.0)
    ctrl.bind(largest_bucket=4096, queue_capacity=65536)
    assert ctrl.min_limit == 4096  # a full bucket always admits when idle
    assert ctrl.max_limit == 65536  # never looser than the static bound
    assert ctrl.limit == 65536


# ----------------------------------------------------- doomed-work refusal


def test_doomed_work_refused_at_enqueue():
    ctrl = AdmissionController(
        _cfg(min_limit_candidates=1000, max_limit_candidates=10000,
             adjust_interval_s=1e9),
        clock=lambda: 0.0,
    )
    ctrl.note_batch(100, 1.0)  # EWMA: 10ms per candidate
    d = ctrl.admit(10, backlog=500, deadline_s=1.0)  # est wait 5s > 1s
    assert not d.admitted and d.reason == "doomed"
    assert d.retry_after_ms == 2000  # 2.5s half-drain hint, capped
    assert ctrl.doomed_refusals == 1
    # Enough budget, or no deadline at all: admitted.
    assert ctrl.admit(10, backlog=500, deadline_s=10.0).admitted
    assert ctrl.admit(10, backlog=500).admitted
    # No service-time estimate yet = no refusal (never guess a doom).
    fresh = AdmissionController(
        _cfg(min_limit_candidates=1000, adjust_interval_s=1e9),
        clock=lambda: 0.0,
    )
    assert fresh.admit(10, backlog=500, deadline_s=0.001).admitted


def test_deadline_refusal_config_gate():
    ctrl = AdmissionController(
        _cfg(deadline_refusal=False, min_limit_candidates=1000,
             adjust_interval_s=1e9),
        clock=lambda: 0.0,
    )
    ctrl.note_batch(100, 1.0)
    assert ctrl.admit(10, backlog=500, deadline_s=0.001).admitted


# ------------------------------------------------------ criticality lanes


def test_lane_shed_ordering():
    ctrl = AdmissionController(
        _cfg(min_limit_candidates=100, max_limit_candidates=100,
             adjust_interval_s=1e9),
        clock=lambda: 0.0,
    )
    # Backlog 68 + 5 = 73: past the probe (50) and sheddable (70) lane
    # caps, inside default (90) and critical (100) — sheddable traffic is
    # refused FIRST as backlog builds.
    assert not ctrl.admit(5, 68, lane="probe").admitted
    assert not ctrl.admit(5, 68, lane="sheddable").admitted
    assert ctrl.admit(5, 68, lane="default").admitted
    assert ctrl.admit(5, 68, lane="critical").admitted
    # A request landing on an EMPTY queue always admits (warming the
    # largest bucket on an idle server must never be lane-refused).
    assert ctrl.admit(10_000, 0, lane="probe").admitted
    # Unknown lanes map to default: a typo'd criticality neither grants
    # critical treatment nor marks traffic sheddable.
    assert overload_mod.normalize_criticality("CRITICAL") == "critical"
    assert overload_mod.normalize_criticality("best-effort") == "default"
    assert overload_mod.normalize_criticality(None) == "default"
    snap = ctrl.snapshot()
    assert snap["sheds_by_lane"]["probe"] == 1
    assert snap["sheds_by_lane"]["sheddable"] == 1


def test_shed_state_refuses_sheddable_outright():
    ctrl = AdmissionController(
        _cfg(min_limit_candidates=100, adjust_interval_s=1e9),
        clock=lambda: 0.0,
    )
    ctrl._state = SHED  # unit test: pin the machine (faults path below)
    assert not ctrl.admit(1, 0, lane="sheddable").admitted
    assert not ctrl.admit(1, 0, lane="probe").admitted
    d = ctrl.admit(1, 0, lane="default")
    assert d.admitted  # empty queue: non-sheddable work still flows


def test_brownout_still_admits_probe_warmup():
    """Version-rollout warmup rides the probe lane; a server sitting in
    BROWNOUT for minutes must still admit it (empty queue / under the
    probe lane fraction) or the version watcher blacklists the new
    version after max_load_attempts — only full SHED refuses outright."""
    ctrl = AdmissionController(
        _cfg(min_limit_candidates=100, max_limit_candidates=100,
             adjust_interval_s=1e9),
        clock=lambda: 0.0,
    )
    ctrl._state = BROWNOUT
    assert ctrl.admit(32, 0, lane="probe").admitted      # idle: warmup flows
    assert ctrl.admit(5, 40, lane="probe").admitted      # under probe cap (50)
    assert not ctrl.admit(5, 60, lane="probe").admitted  # over probe cap
    assert ctrl.admit(1, 0, lane="sheddable").admitted   # brownout != shed


# -------------------------------------------------- pressure state machine


def test_pressure_state_machine_escalates_and_recovers():
    clock = [0.0]
    ctrl = AdmissionController(
        _cfg(
            target_queue_wait_ms=50.0, queue_wait_window_s=1.0,
            adjust_interval_s=0.5, brownout_after_intervals=2,
            shed_after_intervals=4, recover_after_intervals=2,
            min_limit_candidates=16, max_limit_candidates=128,
        ),
        clock=lambda: clock[0],
    )

    def tick(over: bool):
        if over:
            ctrl.note_queue_wait(0.2)
        clock[0] += 0.6
        return ctrl.state()

    assert tick(True) == NOMINAL      # over x1
    assert tick(True) == BROWNOUT     # over x2 -> brownout (counter resets)
    # shed_after_intervals counts FURTHER over ticks past the brownout
    # transition (the documented semantics), not cumulatively from
    # NOMINAL: 4 more over ticks, not 4 total.
    assert tick(True) == BROWNOUT     # +1
    assert tick(True) == BROWNOUT     # +2
    assert tick(True) == BROWNOUT     # +3
    assert tick(True) == SHED         # +4 -> shed
    clock[0] += 2.0                   # age the window out
    assert tick(False) == SHED        # under x1
    assert tick(False) == BROWNOUT    # under x2 -> one level down
    assert tick(False) == BROWNOUT    # under x1 (counter reset on step)
    assert tick(False) == NOMINAL     # under x2 -> nominal
    assert ctrl.state_changes == 4


def test_pressure_fault_site_pins_state():
    """The deterministic test hook: a `pressure` fault rule whose code
    names a state forces the machine there with no real load."""
    clock = [0.0]
    ctrl = AdmissionController(_cfg(adjust_interval_s=0.0), clock=lambda: clock[0])
    faults.get().add("pressure", "error", code="BROWNOUT")
    assert ctrl.state() == BROWNOUT
    assert ctrl.stale_serve_active()  # default stale window is 30s
    faults.reset()
    faults.get().add("pressure", "error", code="SHED")
    assert ctrl.state() == SHED
    faults.reset()
    # Rule gone: normal (under-target, empty window) ticks recover.
    cfg = ctrl.cfg
    for _ in range(int(cfg.recover_after_intervals) * 2 + 1):
        ctrl.state()
    assert ctrl.state() == NOMINAL


# --------------------------------------------- batcher admission (armed)


def test_batcher_refusal_carries_retry_after_and_maps_resource_exhausted(servable):
    release = threading.Event()

    def blocked_run(sv, arrays):
        release.wait(10.0)
        n = next(iter(arrays.values())).shape[0]
        return {"prediction_node": np.zeros(n, np.float32)}

    ctrl = AdmissionController(
        _cfg(min_limit_candidates=8, max_limit_candidates=8,
             adjust_interval_s=1e9),
    )
    batcher = DynamicBatcher(
        buckets=(8,), max_wait_us=0, run_fn=blocked_run, overload=ctrl,
    ).start()
    futs, err = [], None
    try:
        for i in range(6):
            try:
                futs.append(batcher.submit(servable, make_arrays(4, seed=i)))
            except AdmissionRefusedError as e:
                err = e
                break
        assert err is not None, "adaptive limit never refused"
        # Status mapping: subclassing QueueOverloadError keeps the
        # RESOURCE_EXHAUSTED mapping and every existing handler.
        assert isinstance(err, QueueOverloadError)
        assert err.retry_after_ms is not None and err.retry_after_ms >= 25
        assert ctrl.sheds >= 1
    finally:
        release.set()
        for f in futs:
            f.result(timeout=30)  # accepted work still completes
        batcher.stop()


def test_disabled_mode_keeps_static_bound(servable):
    """overload=None: the static queue_capacity_candidates check is
    untouched and the module fast path stays off."""
    assert not overload_mod.active()
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0).start()
    try:
        assert batcher.overload is None
        out = batcher.submit(servable, make_arrays(4)).result(timeout=60)
        assert out["prediction_node"].shape == (4,)
    finally:
        batcher.stop()
    assert OverloadConfig().build() is None  # enabled=false builds nothing


# ------------------------------------------------- brownout stale-serve


def test_brownout_serves_stale_cache_marked_degraded_no_refill(servable):
    cache_clock = [0.0]
    cache = ScoreCache(ttl_s=1.0, clock=lambda: cache_clock[0])
    ctrl = AdmissionController(
        _cfg(adjust_interval_s=0.0, stale_while_overloaded_s=5.0,
             recover_after_intervals=1),
    )
    batcher = DynamicBatcher(
        buckets=(32,), max_wait_us=0, score_cache=cache, overload=ctrl,
    ).start()
    try:
        arrays = make_arrays(4, seed=7)
        fresh = batcher.submit(servable, arrays).result(timeout=60)
        assert overload_mod.consume_degraded() is None
        # Entry expires (past TTL, inside the 5s stale window)...
        cache_clock[0] = 1.5
        # ...and pressure goes BROWNOUT (deterministic fault site).
        faults.get().add("pressure", "error", code="BROWNOUT")
        assert ctrl.state() == BROWNOUT
        stale = batcher.submit(servable, arrays).result(timeout=60)
        np.testing.assert_array_equal(
            stale["prediction_node"], fresh["prediction_node"]
        )
        assert overload_mod.consume_degraded() == "stale"
        assert ctrl.snapshot()["brownout_serves"] == 1
        assert cache.snapshot()["stale_serves"] == 1
        # NEVER re-filled from the stale serve: back at NOMINAL the same
        # key misses (expired entry dropped) and recomputes fresh.
        faults.reset()
        assert ctrl.state() == NOMINAL  # recover_after_intervals=1
        misses_before = cache.snapshot()["misses"]
        again = batcher.submit(servable, arrays).result(timeout=60)
        assert overload_mod.consume_degraded() is None
        assert cache.snapshot()["misses"] == misses_before + 1
        np.testing.assert_array_equal(
            again["prediction_node"], fresh["prediction_node"]
        )
        # Stale WINDOW respected: past ttl + stale_while_overloaded_s the
        # entry is gone even under brownout — recompute, not degraded.
        cache_clock[0] = 1.5 + 1.0 + 5.1
        faults.get().add("pressure", "error", code="BROWNOUT")
        assert ctrl.state() == BROWNOUT
        recomputed = batcher.submit(servable, arrays).result(timeout=60)
        assert overload_mod.consume_degraded() is None
        assert ctrl.snapshot()["brownout_serves"] == 1  # unchanged
        np.testing.assert_array_equal(
            recomputed["prediction_node"], fresh["prediction_node"]
        )
    finally:
        batcher.stop()


# ------------------------------------------ client pushback + scoreboard


def test_retry_after_extraction_is_defensive():
    class Hinted:
        def trailing_metadata(self):
            return (("retry-after-ms", "125"),)

    class Broken:
        def trailing_metadata(self):
            raise RuntimeError("no metadata")

    assert client_mod._retry_after_ms_of(Hinted()) == 125
    assert client_mod._retry_after_ms_of(Broken()) is None
    assert client_mod._retry_after_ms_of(object()) is None


def test_pushback_never_ejects_and_biases_steering():
    clock = [0.0]
    sb = BackendScoreboard(
        ["a", "b"],
        ScoreboardConfig(failure_threshold=1, pushback_busy_s=0.25),
        clock=lambda: clock[0],
    )
    # Ten pushbacks against a threshold of ONE: no ejection, ever.
    for _ in range(10):
        sb.record_failure(0, kind="pushback", retry_after_s=0.5)
    assert sb.ejections == 0 and sb.pushbacks == 10
    assert sb.state(0) == HEALTHY
    snap = sb.snapshot()
    assert snap["backends"]["a"]["pushbacks"] == 10
    assert snap["backends"]["a"]["busy"] is True
    assert snap["backends"]["a"]["consecutive_failures"] == 0
    # Steering prefers the non-busy healthy peer; hedges NEVER target a
    # busy host (optional duplicate work is what it asked not to get).
    assert sb.pick(0) == 1
    assert sb.hedge_target(exclude=(1,)) is None
    # Busy window passes: home host again.
    clock[0] = 0.6
    assert sb.pick(0) == 0
    # Every healthy host busy: rotation order unchanged (send somewhere).
    sb.record_failure(0, kind="pushback")
    sb.record_failure(1, kind="pushback")
    assert sb.pick(0) == 0


def test_pushback_recovers_ejected_host_as_alive():
    clock = [0.0]
    sb = BackendScoreboard(
        ["a", "b"], ScoreboardConfig(failure_threshold=1),
        clock=lambda: clock[0],
    )
    sb.record_failure(0)
    assert sb.state(0) == EJECTED
    # A pushback PROVES the host answers: recovered (but busy), no
    # doubled re-ejection.
    sb.record_failure(0, kind="pushback")
    assert sb.state(0) == HEALTHY
    assert sb.ejections == 1 and sb.recoveries == 1


def test_grpc_pushback_end_to_end(servable):
    """Armed server pinned in SHED: sheddable traffic is refused with
    RESOURCE_EXHAUSTED + retry-after-ms trailing metadata; the client
    honors the hint in its backoff, records pushback (not death — zero
    ejections at failure_threshold=1), and default-criticality traffic
    still flows on the same connection."""
    ctrl = AdmissionController(_cfg(adjust_interval_s=0.0))
    faults.get().add("pressure", "error", code="SHED")
    registry = ServableRegistry()
    registry.load(servable)
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0, overload=ctrl).start()
    impl = PredictionServiceImpl(registry, batcher)
    server, port = create_server(impl, "127.0.0.1:0")
    server.start()
    host = f"127.0.0.1:{port}"

    async def go():
        async with ShardedPredictClient(
            [host], "DCN", criticality="sheddable",
            failover_attempts=1, backoff_initial_s=0.0,
            scoreboard=BackendScoreboard(
                [host], ScoreboardConfig(failure_threshold=1)
            ),
        ) as shed_client:
            with pytest.raises(PredictClientError) as ei:
                await shed_client.predict(make_arrays(4, seed=1))
            counters = shed_client.resilience_counters()
            code = getattr(ei.value.code, "name", str(ei.value.code))
        async with ShardedPredictClient([host], "DCN") as ok_client:
            scores = await ok_client.predict(make_arrays(4, seed=1))
        return code, counters, scores

    try:
        code, counters, scores = asyncio.run(go())
    finally:
        server.stop(0)
        batcher.stop()
    assert code == "RESOURCE_EXHAUSTED"
    # Two attempts (primary + failover), both refused; the failover
    # backoff honored the server's trailing-metadata hint.
    assert counters["pushbacks_received"] >= 2
    assert counters["retry_after_honored"] >= 1
    sb = counters["scoreboard"]
    assert sb["ejections"] == 0 and sb["pushbacks"] >= 2
    assert sb["backends"][host]["state"] == HEALTHY
    # Criticality threads end-to-end: default-lane traffic was admitted
    # by the very server that shed the sheddable lane.
    assert scores.shape == (4,)
    assert ctrl.sheds_by_lane["sheddable"] >= 2
    assert ctrl.snapshot()["state"] == SHED


# ------------------------------------------------------- graceful drain


def test_graceful_drain_serves_accepted_then_refuses_new(servable):
    release = threading.Event()

    def slow_run(sv, arrays):
        release.wait(5.0)
        n = next(iter(arrays.values())).shape[0]
        return {"prediction_node": np.full(n, 0.5, np.float32)}

    registry = ServableRegistry()
    registry.load(servable)
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0, run_fn=slow_run).start()
    impl = PredictionServiceImpl(registry, batcher)
    impl.warmup_complete = True
    gs = GracefulShutdown(impl, batcher, grace_s=10.0)
    try:
        futs = [batcher.submit(servable, make_arrays(4, seed=i)) for i in range(3)]
        t = threading.Thread(target=gs.shutdown)
        t.start()
        for _ in range(500):
            if impl.draining:
                break
            time.sleep(0.01)
        assert impl.draining
        # New admissions refused UNAVAILABLE with the draining detail, and
        # health reports NOT_SERVING so balancers stop routing here.
        with pytest.raises(ServiceError) as ei:
            impl.predict(build_predict_request(make_arrays(2), "DCN"))
        assert ei.value.code == "UNAVAILABLE" and "draining" in str(ei.value)
        assert GrpcHealthService(impl)._status("") == health_proto.NOT_SERVING
        release.set()
        t.join(timeout=30)
        assert not t.is_alive()
        assert gs.drained is True
        for f in futs:  # every ACCEPTED request was answered
            assert f.result(timeout=1)["prediction_node"].shape == (4,)
    finally:
        release.set()
        batcher.stop()


def test_drain_grace_expiry_reports_undrained(servable):
    started = threading.Event()

    def slow_run(sv, arrays):
        started.set()
        time.sleep(0.5)
        n = next(iter(arrays.values())).shape[0]
        return {"prediction_node": np.zeros(n, np.float32)}

    registry = ServableRegistry()
    registry.load(servable)
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0, run_fn=slow_run).start()
    impl = PredictionServiceImpl(registry, batcher)
    gs = GracefulShutdown(impl, batcher, grace_s=0.05)
    fut = batcher.submit(servable, make_arrays(4))
    assert started.wait(10.0)
    gs.shutdown()
    assert gs.drained is False  # grace expired with work in flight
    assert fut.result(timeout=10)["prediction_node"].shape == (4,)


def test_sigterm_installs_and_triggers_drain(servable):
    registry = ServableRegistry()
    registry.load(servable)
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0).start()
    impl = PredictionServiceImpl(registry, batcher)
    gs = GracefulShutdown(impl, batcher, grace_s=2.0)
    assert gs.install_signal_handler()
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        assert gs._done.wait(20.0)
        assert impl.draining and gs.drained is True
        # Idempotent: a second shutdown (the serve() finally block racing
        # the signal thread) returns immediately.
        gs.shutdown()
    finally:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        batcher.stop()


# ------------------------------------------------ config + observability


def test_overload_config_section(tmp_path):
    p = tmp_path / "cfg.toml"
    p.write_text(
        "[server]\n"
        "[overload]\n"
        "enabled = true\n"
        "target_queue_wait_ms = 20.0\n"
        "min_limit_candidates = 64\n"
        "decrease_factor = 0.5\n"
        "stale_while_overloaded_s = 3.0\n"
        "drain_grace_s = 2.5\n"
    )
    oc = load_config(str(p))["overload"]
    assert oc.enabled and oc.target_queue_wait_ms == 20.0
    assert oc.min_limit_candidates == 64 and oc.decrease_factor == 0.5
    assert oc.stale_while_overloaded_s == 3.0 and oc.drain_grace_s == 2.5
    ctrl = oc.build()
    assert ctrl is not None and ctrl.min_limit == 64


def test_build_stack_overload_master_switch():
    from distributed_tf_serving_tpu.serving.server import build_stack
    from distributed_tf_serving_tpu.utils.config import ServerConfig

    cfg = ServerConfig(warmup=False, buckets=(32,), num_fields=F)
    for enabled in (False, True):
        _r, batcher, impl, _s, _m, _w = build_stack(
            cfg, model_config=CFG,
            overload_config=OverloadConfig(enabled=enabled),
        )
        try:
            assert (batcher.overload is not None) == enabled
            if enabled:
                # Auto limits resolved against the real geometry.
                assert batcher.overload.min_limit == batcher.buckets[-1]
                assert (
                    batcher.overload.max_limit
                    == batcher.queue_capacity_candidates
                )
                assert impl.overload_stats()["enabled"] is True
            else:
                assert impl.overload_stats() is None
        finally:
            batcher.stop()


def test_overload_prometheus_series():
    from distributed_tf_serving_tpu.utils.metrics import ServerMetrics

    ctrl = AdmissionController(
        _cfg(min_limit_candidates=100, adjust_interval_s=1e9),
        clock=lambda: 0.0,
    )
    ctrl.admit(5, 68, lane="sheddable")  # one refusal on the books
    text = ServerMetrics().prometheus_text(overload=ctrl.snapshot())
    assert "dts_tpu_overload_limit_candidates 100" in text
    assert "dts_tpu_overload_sheds_total 1" in text
    assert 'dts_tpu_overload_lane_sheds_total{lane="sheddable"} 1' in text
    assert 'dts_tpu_overload_pressure_state{state="nominal"} 1' in text
    assert 'dts_tpu_overload_pressure_state{state="shed"} 0' in text


def test_rest_overload_headers():
    from aiohttp import web

    from distributed_tf_serving_tpu.serving.rest import _json_error, _mark_degraded

    r = _json_error("RESOURCE_EXHAUSTED", "shed", retry_after_ms=25)
    assert r.status == 429
    assert r.headers["Retry-After"] == "1"  # ceil to whole seconds
    assert r.headers["retry-after-ms"] == "25"
    assert "Retry-After" not in _json_error("NOT_FOUND", "x").headers
    overload_mod._set_active(True)
    overload_mod.mark_degraded("stale")
    resp = _mark_degraded(web.json_response({}))
    assert resp.headers["X-DTS-Degraded"] == "stale"
    # Consumed: the next response in this context is clean.
    assert "X-DTS-Degraded" not in _mark_degraded(web.json_response({})).headers
