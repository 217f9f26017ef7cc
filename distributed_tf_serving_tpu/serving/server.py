"""gRPC frontend: the TPU-VM shim exposing PredictionService on the DCN edge.

The reference's serving endpoint was `tensorflow_model_server` on port 9999
(DCNClient.java:28); this is its in-tree replacement. A thin adapter maps
ServiceError codes onto grpc status codes, records per-RPC latency/outcome
metrics, and delegates everything else to PredictionServiceImpl. Handler
threads block on batcher futures, so the thread pool size bounds in-flight
RPCs while the batcher thread serializes device work.

CLI (`python -m distributed_tf_serving_tpu.serving.server`) supports the
full knob set via flags or a TOML config (utils/config.py), serves either a
demo-initialized model or a training checkpoint, and optionally shards
execution over a device mesh.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import logging
import os
import signal
import socket
import threading
import time
from concurrent import futures

import grpc
import jax

from ..models import ModelConfig, Servable, ServableRegistry, build_model, ctr_signatures
from ..proto.service_grpc import (
    KEEPALIVE_SERVER_OPTIONS,
    LARGE_MESSAGE_CHANNEL_OPTIONS,
)
from ..proto import (
    add_HealthServicer_to_server,
    add_ModelServiceServicer_to_server,
    add_PredictionServiceServicer_to_server,
)
from ..proto import health as health_proto
from .. import codec
from ..utils.config import ServerConfig, load_config
from ..utils.metrics import ServerMetrics
from ..utils import tracing
from ..utils.tracing import request_trace
from . import lifecycle as lifecycle_mod
from . import overload as overload_mod
from .batcher import DynamicBatcher
from .service import PredictionServiceImpl, ServiceError

log = logging.getLogger("dts_tpu.server")


def _status(code_name: str) -> grpc.StatusCode:
    return getattr(grpc.StatusCode, code_name, grpc.StatusCode.UNKNOWN)


def _model_of(request) -> str | None:
    """The resolved model label for metrics/tracing (None when the request
    shape carries no top-level model_spec, e.g. MultiInference)."""
    return getattr(getattr(request, "model_spec", None), "name", "") or None


def _traceparent_of(context) -> str | None:
    """The W3C traceparent from the RPC's invocation metadata ((key, value)
    pairs); None when absent. Only called when tracing is enabled."""
    try:
        for key, value in context.invocation_metadata() or ():
            if key == "traceparent":
                return value
    except Exception:  # noqa: BLE001 — tracing must never fail an RPC
        return None
    return None


def _criticality_of(context) -> str | None:
    """The request's criticality lane from invocation metadata
    (x-dts-criticality). Only scanned while a plane that CONSUMES the
    lane is armed — the overload controller (lane-ordered shedding) or
    the lifecycle controller (probe-lane-first canary routing) — two
    module-bool reads otherwise."""
    if not (overload_mod.active() or lifecycle_mod.active()):
        return None
    try:
        for key, value in context.invocation_metadata() or ():
            if key == overload_mod.CRITICALITY_KEY:
                return overload_mod.normalize_criticality(value)
    except Exception:  # noqa: BLE001 — a metadata quirk must not fail the RPC
        return None
    return None


def _stream_chunk_of(context) -> int | None:
    """Per-request sub-batch-size override for PredictStream from the
    x-dts-stream-chunk metadata key (candidates per sub-batch; the server
    still clamps the resulting chunk count). None = use the configured
    stream_chunk_candidates default."""
    try:
        for key, value in context.invocation_metadata() or ():
            if key == "x-dts-stream-chunk":
                return max(int(value), 0) or None
    except Exception:  # noqa: BLE001 — a malformed hint must not fail the RPC
        return None
    return None


def _input_crc_of(context, impl) -> str | None:
    """The client's x-dts-input-crc wire-integrity stamp (ISSUE 20), or
    None. Only scanned while the impl's integrity plane (wire layer) is
    armed — two attribute reads per RPC otherwise."""
    integ = impl.integrity
    if integ is None or not integ.config.wire_checksums:
        return None
    try:
        for key, value in context.invocation_metadata() or ():
            if key == codec.CRC_INPUT_MD:
                return str(value)
    except Exception:  # noqa: BLE001 — a metadata quirk must not fail the RPC
        return None
    return None


def _stamp_response_crc(impl, context, resp) -> None:
    """x-dts-score-crc trailing-metadata stamp over the encoded response
    tensors (ISSUE 20). Advisory: a stamping failure must never fail a
    good response, and an armed overload plane's degraded/pushback
    trailing metadata (set later on the same context) wins the slot — the
    client treats an absent stamp as "server didn't verify", exactly like
    a plane-less server."""
    try:
        sidecar = impl.response_crc_sidecar(resp)
        if sidecar:
            context.set_trailing_metadata(((codec.CRC_SCORE_MD, sidecar),))
    except Exception:  # noqa: BLE001 — advisory, never fatal
        pass


def _push_overload_metadata(context, exc: ServiceError | None) -> None:
    """Overload-plane trailing metadata: the retry-after-ms pushback hint
    on refusals, and the degraded marker on brownout stale-served
    successes (exc None). Callers gate on overload.active(), so the plane
    costs nothing when it is off."""
    try:
        if exc is not None:
            ra = getattr(exc, "retry_after_ms", None)
            if ra:
                context.set_trailing_metadata(
                    ((overload_mod.RETRY_AFTER_KEY, str(int(ra))),)
                )
        else:
            degraded = overload_mod.consume_degraded()
            if degraded:
                context.set_trailing_metadata(
                    ((overload_mod.DEGRADED_KEY, degraded),)
                )
    except Exception:  # noqa: BLE001 — hints are advisory, never fatal
        pass


# Initial-metadata peer-role stamp (ISSUE 18 satellite): traced callers
# label their client.rpc span's resolved peer (router vs replica) from
# this, so stitched fleet trees name each hop without guessing from
# ports. INITIAL metadata — trailing already carries the overload and
# degraded markers. Only sent on traced requests: the disabled hot path
# stays one enabled() read.
_PEER_ROLE_KEY = "x-dts-peer-role"


def _send_peer_role(context) -> None:
    try:
        context.send_initial_metadata(((_PEER_ROLE_KEY, "replica"),))
    except Exception:  # noqa: BLE001 — advisory only
        pass


class _RpcStamps:
    """One RPC's clock reads (time.perf_counter), each taken where the work
    happens, from the pool's `submit` to the call's termination:

      t_submit   poller thread: `_server.py` hands the RPC to the pool
      t_taken    pool thread: the pool's callable starts, before it asks the
                 call for the request's message
      t_handler  pool thread: `_call`'s first line; between t_taken and here
                 the poller thread read and parsed the message (`rpc.parse`)
                 and woke this thread
      t_return   pool thread: `_call`'s `finally`; the handler's own time,
                 t_return - t_handler, is what `rpc.listener<i>` holds
      (t_done)   poller thread: `done`, once the status has gone out

    `done` records the four phases that, with the handler's time, tile the
    RPC as this process sees it: `rpc.server` = `rpc.pool_wait` +
    `rpc.request_wait` + handler + `rpc.reply`, exactly. Differences of
    stamps of two threads: aggregate only, not on the profiler's clock.

    While a profiler capture is open (`tracing.capture_open`, asked once an
    RPC at t_taken) the pool thread's CPU clock (time.thread_time) is read
    beside t_taken and t_return, and `done` adds `cpu.rpc_handler`: what
    one Predict cost its handler thread in CPU, of the wall time between
    the same two stamps. `cpu_taken` is None outside a capture."""

    __slots__ = ("t_submit", "t_taken", "t_handler", "t_return",
                 "cpu_taken", "cpu_return")

    def done(self) -> None:
        t_done = time.perf_counter()
        phases = (
            ("rpc.pool_wait", self.t_taken - self.t_submit, 1),
            ("rpc.request_wait", self.t_handler - self.t_taken, 1),
            ("rpc.reply", t_done - self.t_return, 1),
            ("rpc.server", t_done - self.t_submit, 1),
        )
        if self.cpu_taken is not None:
            phases += (("cpu.rpc_handler", self.cpu_return - self.cpu_taken, 1),)
        request_trace.add_many(phases)


class _TakenRpc(threading.local):
    """The stamps of the RPC this pool thread is running, for its handler."""

    stamps: _RpcStamps | None = None


_TAKEN = _TakenRpc()


class _StampedPool(futures.ThreadPoolExecutor):
    """The handler pool of create_server. grpc's `_server.py` calls nothing
    of its pool but `submit`, once an RPC and on the listener's poller
    thread: that is where an RPC's record starts. Every RPC passes through
    (health checks and ModelService calls too); only a handler that asks
    for the record (`GrpcPredictionService.Predict`) has it recorded."""

    def submit(self, fn, /, *args, **kwargs):
        stamps = _RpcStamps()
        stamps.t_submit = time.perf_counter()

        def taken():
            stamps.t_taken = time.perf_counter()
            stamps.cpu_taken = time.thread_time() if tracing.capture_open() else None
            _TAKEN.stamps = stamps
            try:
                return fn(*args, **kwargs)
            finally:
                _TAKEN.stamps = None

        return super().submit(taken)


class _SyncServicerBase:
    """Shared adapter plumbing for sync servicers: ServiceError -> grpc
    status mapping + per-RPC metrics (+ the per-request server root span
    when tracing is on)."""

    def __init__(
        self,
        impl: PredictionServiceImpl,
        metrics: ServerMetrics | None = None,
        listener: int = 0,
    ):
        self.impl = impl
        self.metrics = metrics or ServerMetrics()
        # One adapter a listener (create_server), all over the one impl and
        # the one ServerMetrics: each RPC a listener's connections carried
        # is counted, with its handler's time, under that listener's phase.
        self._listener_phase = f"{LISTENER_PHASE}{listener}"

    def _observe(self, name: str, t0: float, ok: bool, model) -> float:
        """Count the RPC and its handler's time; returns the clock read that
        ended it."""
        t1 = time.perf_counter()
        self.metrics.observe(name, t1 - t0, ok, model=model)
        request_trace.add_many(((self._listener_phase, t1 - t0, 1),))
        return t1

    def _call(self, name: str, fn, request, context, stamps: _RpcStamps | None = None):
        t0 = time.perf_counter()
        ok = False
        model = _model_of(request)
        overload_on = overload_mod.active()
        if overload_on:
            # Clear any degraded marker a failed PREVIOUS request left in
            # this handler thread's context (markers are consumed only on
            # the success path).
            overload_mod.consume_degraded()
        if tracing.enabled():
            # Server-side LOCAL ROOT: adopts the client's trace id (and
            # parents onto the exact shard-attempt span that carried the
            # RPC) when a traceparent arrived; a fresh trace otherwise.
            span_ctx = tracing.start_root(
                f"server.{name}",
                traceparent=_traceparent_of(context),
                attrs={"entrypoint": name, **({"model": model} if model else {})},
            )
            _send_peer_role(context)
        else:
            span_ctx = None
        try:
            if span_ctx is not None:
                with span_ctx:
                    resp = fn(request)
            else:
                resp = fn(request)
            ok = True
            if overload_on:
                # Brownout stale-serves announce themselves in trailing
                # metadata so callers can tell degraded from fresh.
                _push_overload_metadata(context, None)
            return resp
        except ServiceError as e:
            if overload_on:
                # Overload refusals carry the retry-after-ms pushback hint
                # the client's failover backoff honors.
                _push_overload_metadata(context, e)
            context.abort(_status(e.code), str(e))
        except Exception as e:  # internal bug: surface as INTERNAL, keep serving
            log.exception("internal error serving %s", name)
            context.abort(grpc.StatusCode.INTERNAL, f"internal error: {e}")
        finally:
            t1 = self._observe(name, t0, ok, model)
            if stamps is not None:
                stamps.t_handler, stamps.t_return = t0, t1
                if stamps.cpu_taken is not None:
                    stamps.cpu_return = time.thread_time()
                # grpc runs the callback on the poller thread once the
                # status has gone out. A call that has ended already (the
                # client cancelled under the handler) takes none: it ends here.
                if not context.add_callback(stamps.done):
                    stamps.done()

    def _call_stream(self, name: str, fn, request, context):
        """_call for server-streaming RPCs: `fn(request)` returns a chunk
        generator; the same error mapping / metrics / tracing wrap the
        whole stream (one observe per stream, error status aborts
        mid-stream — grpc sends already-yielded chunks first)."""
        t0 = time.perf_counter()
        ok = False
        model = _model_of(request)
        overload_on = overload_mod.active()
        if overload_on:
            overload_mod.consume_degraded()
        if tracing.enabled():
            span_ctx = tracing.start_root(
                f"server.{name}",
                traceparent=_traceparent_of(context),
                attrs={"entrypoint": name, **({"model": model} if model else {})},
            )
            _send_peer_role(context)
        else:
            span_ctx = None
        try:
            if span_ctx is not None:
                with span_ctx:
                    yield from fn(request)
            else:
                yield from fn(request)
            ok = True
            if overload_on:
                _push_overload_metadata(context, None)
        except ServiceError as e:
            if overload_on:
                _push_overload_metadata(context, e)
            context.abort(_status(e.code), str(e))
        except Exception as e:  # internal bug: surface as INTERNAL, keep serving
            log.exception("internal error serving %s", name)
            context.abort(grpc.StatusCode.INTERNAL, f"internal error: {e}")
        finally:
            self._observe(name, t0, ok, model)


def _deadline_of(context) -> float | None:
    """The client's remaining budget from the RPC context (None = no
    deadline), threaded into the impl so the batcher can shed expired work
    instead of burning its fixed 120s bound on an abandoned request."""
    remaining = context.time_remaining()
    # grpc returns None when the client set no deadline; some transports
    # report float('inf') — both mean "no client bound".
    if remaining is None or remaining == float("inf"):
        return None
    return remaining


class GrpcPredictionService(_SyncServicerBase):
    """grpc servicer adapter: error mapping + per-RPC metrics. The three
    batching RPCs propagate the client deadline into the impl."""

    def Predict(self, request, context):
        deadline_s = _deadline_of(context)
        crit = _criticality_of(context)
        input_crc = _input_crc_of(context, self.impl)

        def handler(req):
            resp = self.impl.predict(
                req, deadline_s=deadline_s, criticality=crit,
                input_crc=input_crc,
            )
            if self.impl.integrity is not None:
                _stamp_response_crc(self.impl, context, resp)
            return resp

        return self._call("Predict", handler, request, context, _TAKEN.stamps)

    def Classify(self, request, context):
        deadline_s = _deadline_of(context)
        crit = _criticality_of(context)
        return self._call(
            "Classify",
            lambda req: self.impl.classify(
                req, deadline_s=deadline_s, criticality=crit
            ),
            request, context,
        )

    def Regress(self, request, context):
        deadline_s = _deadline_of(context)
        crit = _criticality_of(context)
        return self._call(
            "Regress",
            lambda req: self.impl.regress(
                req, deadline_s=deadline_s, criticality=crit
            ),
            request, context,
        )

    def MultiInference(self, request, context):
        deadline_s = _deadline_of(context)
        crit = _criticality_of(context)
        return self._call(
            "MultiInference",
            lambda req: self.impl.multi_inference(
                req, deadline_s=deadline_s, criticality=crit
            ),
            request, context,
        )

    def GetModelMetadata(self, request, context):
        return self._call("GetModelMetadata", self.impl.get_model_metadata, request, context)

    def PredictStream(self, request, context):
        deadline_s = _deadline_of(context)
        crit = _criticality_of(context)
        chunk = _stream_chunk_of(context)
        return self._call_stream(
            "PredictStream",
            lambda req: self.impl.predict_stream(
                req, deadline_s=deadline_s, criticality=crit, chunk=chunk
            ),
            request, context,
        )


class GrpcModelService(_SyncServicerBase):
    """tensorflow.serving.ModelService adapter (sync): status + reload.
    Shares the impl's registry and the server's metrics/error mapping."""

    def GetModelStatus(self, request, context):
        return self._call("GetModelStatus", self.impl.get_model_status, request, context)

    def HandleReloadConfigRequest(self, request, context):
        return self._call(
            "HandleReloadConfigRequest", self.impl.handle_reload_config, request, context
        )


# Trailing-metadata key naming WHY a health Check answered NOT_SERVING
# ("draining" | "quarantined" | "starting"): the fan-out client and the
# fleet router steer a draining replica straight to the DRAINING
# scoreboard state instead of cycling the rebuilding retry window.
HEALTH_REASON_METADATA_KEY = "x-dts-health-reason"


class GrpcHealthService:
    """grpc.health.v1 Health over the serving state (proto/health.py glue;
    standard health-checking clients and the fan-out client's half-open
    probes both speak it):

    - service "" (the whole server): SERVING once the load+warmup phase
      completed (impl.warmup_complete — build_stack flips it) AND at least
      one model has a ready version; NOT_SERVING before — a server still
      compiling its bucket ladder must not receive traffic.
    - service "<model>": SERVING when the registry holds a ready version;
      NOT_SERVING when the server is CONFIGURED for the model (a watcher or
      lifecycle owns it) but no version landed yet; grpc NOT_FOUND for
      names this server was never told about (the health spec's
      unknown-service answer).
    """

    # How often Watch re-evaluates serving state. Each watcher holds
    # a thread-pool worker for the stream's lifetime, so this is a
    # router-tier surface (a handful of subscribers), not an edge one.
    watch_poll_s = 0.2

    def __init__(self, impl: PredictionServiceImpl):
        self.impl = impl

    def _status(self, service: str) -> int | None:
        served = self.impl.registry.models()
        if not service:
            ready = any(served.values())
            # A draining server (SIGTERM received, GracefulShutdown in
            # progress) reports NOT_SERVING so load balancers stop routing
            # to it while accepted work finishes. So does a QUARANTINED
            # one (recovery plane mid quarantine/reinit/replay): clients
            # failover via the scoreboard until the rebuilt executor has
            # drained its replay.
            recovery = getattr(self.impl, "recovery", None)
            return (
                health_proto.SERVING
                if (self.impl.warmup_complete and ready
                    and not getattr(self.impl, "draining", False)
                    and not (recovery is not None and recovery.not_serving()))
                else health_proto.NOT_SERVING
            )
        if served.get(service):
            return health_proto.SERVING
        # Same "configured" definition as GetModelStatus's START-vs-
        # NOT_FOUND split, so the two probe surfaces can never disagree.
        return (
            health_proto.NOT_SERVING
            if self.impl.is_configured(service)
            else None
        )

    def _reason(self, service: str) -> str:
        """WHY the overall service is NOT_SERVING, as the
        x-dts-health-reason trailer: "draining" (GracefulShutdown — the
        process is leaving; steer away and do NOT re-probe it on the
        rebuild cadence), "quarantined" (recovery cycle — it comes back),
        or "starting" (warmup not finished). Empty for per-model checks,
        whose NOT_SERVING already means "configured, no version"."""
        if service:
            return ""
        if getattr(self.impl, "draining", False):
            return "draining"
        recovery = getattr(self.impl, "recovery", None)
        if recovery is not None and recovery.not_serving():
            return "quarantined"
        return "starting"

    def Check(self, request, context):
        st = self._status(request.service)
        if st is None:
            context.abort(
                grpc.StatusCode.NOT_FOUND,
                f"unknown service {request.service!r}",
            )
        if st == health_proto.NOT_SERVING:
            reason = self._reason(request.service)
            if reason:
                context.set_trailing_metadata(
                    ((HEALTH_REASON_METADATA_KEY, reason),)
                )
        return health_proto.HealthCheckResponse(status=st)

    def Watch(self, request, context):
        """grpc.health.v1 streaming Watch: current status immediately,
        then a message per CHANGE. Per the health spec an unknown service
        streams SERVICE_UNKNOWN (no abort) so the watcher sees it appear
        later. Fleet routers subscribe here instead of half-open
        polling."""
        last = None
        while context.is_active():
            st = self._status(request.service)
            if st is None:
                st = health_proto.SERVICE_UNKNOWN
            if st != last:
                last = st
                yield health_proto.HealthCheckResponse(status=st)
            time.sleep(self.watch_poll_s)

    def watch_once(self, request, context):  # pragma: no cover - hook
        """Test seam: one Watch evaluation without the stream loop."""
        st = self._status(request.service)
        return health_proto.SERVICE_UNKNOWN if st is None else st


def _add_uds_port(server, uds_path: str) -> None:
    """Bind the server to a Unix-domain socket NEXT TO its TCP port
    (transport-floor satellite, ISSUE 9): co-located fan-out clients dial
    `unix:<path>` and skip the TCP/loopback stack — no checksums, no
    Nagle/ACK machinery, smaller per-message syscall cost. A stale socket
    file from a previous process is removed first (grpc refuses to bind
    over it)."""
    import os as _os

    try:
        if _os.path.exists(uds_path):
            _os.unlink(uds_path)
    except OSError:
        pass  # bind below gives the actionable error
    if server.add_insecure_port(f"unix:{uds_path}") == 0:
        raise RuntimeError(f"could not bind unix:{uds_path}")


# The phase a listener's RPCs are counted under, its index appended:
# `rpc.listener0`, `rpc.listener1`, ... on /monitoring?section=phases.
LISTENER_PHASE = "rpc.listener"

# How many listeners serve() opens on its one port: one for every
# CORES_A_LISTENER cores the process may run on, at least one and at most
# MAX_LISTENERS. On the 13-core host of one v5e chip that is four, which
# read ahead of two and of one where the wire paces the server (5 MB
# requests: +9 to +25% rows a second against +4 to +15% at two) and level
# with one where it does not (PERF.md section 6, PR 34, has the readings).
MAX_LISTENERS = 4
CORES_A_LISTENER = 3


def listener_count() -> tuple[int, int]:
    """(listeners serve() opens, the cores they were derived from). A
    Python `grpc.server` reads every connection's bytes on ONE thread (its
    completion queue's poller); with several servers on one port the reads
    spread over as many threads. Nothing configures this: a host too small
    to give a second poller a core keeps the one listener."""
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:  # not Linux
        cores = os.cpu_count() or 1
    return max(1, min(MAX_LISTENERS, cores // CORES_A_LISTENER)), cores


def _time_left(deadline: float | None) -> float | None:
    return None if deadline is None else max(deadline - time.monotonic(), 0.0)


class _AllStopped:
    """What `Listeners.stop` returns: waits like the `threading.Event` one
    `grpc.Server.stop` returns, for every listener's."""

    def __init__(self, events):
        self._events = events

    def is_set(self) -> bool:
        return all(e.is_set() for e in self._events)

    def wait(self, timeout: float | None = None) -> bool:
        deadline = None if timeout is None else time.monotonic() + timeout
        for e in self._events:
            e.wait(_time_left(deadline))
        return self.is_set()


class Listeners:
    """The `grpc.server` objects of one address, started, stopped and waited
    for as one server (`start`, `stop`, `wait_for_termination` as
    `grpc.Server` has them). Each has its own completion queue and poller
    thread; all share the servicers' impl, the metrics and the handler pool."""

    def __init__(self, servers: list[grpc.Server]):
        self.servers = servers

    def start(self) -> None:
        for server in self.servers:
            server.start()

    def stop(self, grace: float | None) -> _AllStopped:
        return _AllStopped([server.stop(grace) for server in self.servers])

    def wait_for_termination(self, timeout: float | None = None) -> bool:
        """True when `timeout` passed with a listener still serving."""
        deadline = None if timeout is None else time.monotonic() + timeout
        return any(server.wait_for_termination(_time_left(deadline)) for server in self.servers)


def _bind(server: grpc.Server, address: str, credentials) -> int:
    """The port `address` was bound to, 0 where it could not be."""
    try:
        if credentials is not None:
            return server.add_secure_port(address, credentials)
        return server.add_insecure_port(address)
    except RuntimeError:  # grpc raises where older releases returned 0
        return 0


def create_server(
    impl: PredictionServiceImpl,
    address: str = "127.0.0.1:0",
    max_workers: int = 16,
    metrics: ServerMetrics | None = None,
    credentials: "grpc.ServerCredentials | None" = None,
    uds_path: str | None = None,
    listeners: int = 1,
) -> tuple[Listeners, int]:
    """Build (not start) a server; returns (server, bound_port).
    `credentials` switches the port to TLS (ssl_server_credentials — the
    --ssl-config-file surface; see load_ssl_credentials). `uds_path`
    additionally binds a plaintext Unix-domain socket for co-located
    clients ([transport] uds_path).

    `listeners` > 1 binds that many `grpc.server` objects to the one port
    through SO_REUSEPORT (the first binds `address`, port 0 included, the
    others the port it got): the kernel spreads CONNECTIONS over them, and
    each reads its share of the wire on its own thread. They serve the same
    three services over the one `impl`, `metrics` and pool of `max_workers`
    handler threads; the Unix-domain socket cannot be shared and stays on
    the first. Where the host has no SO_REUSEPORT or a further listener
    cannot bind the port, the first serves alone."""
    if credentials is not None and uds_path:
        # The UDS listener is plaintext: binding it next to a TLS/mTLS
        # TCP port would silently open an unauthenticated side door
        # for any local process that can reach the socket file —
        # refuse the combination instead of downgrading.
        raise ValueError(
            "[transport] uds_path cannot be combined with "
            "--ssl-config-file: the unix socket is plaintext and "
            "would bypass the TLS/mTLS the TCP port enforces"
        )
    metrics = metrics or ServerMetrics()
    pool = _StampedPool(max_workers=max_workers, thread_name_prefix="rpc")
    options = list(LARGE_MESSAGE_CHANNEL_OPTIONS) + list(KEEPALIVE_SERVER_OPTIONS)

    def build(index: int) -> grpc.Server:
        server = grpc.server(pool, options=options)
        add_PredictionServiceServicer_to_server(
            GrpcPredictionService(impl, metrics, index), server)
        # Same port, second service — exactly tensorflow_model_server's layout.
        add_ModelServiceServicer_to_server(GrpcModelService(impl, metrics, index), server)
        # Third service: grpc.health.v1 (standard probes + client half-open
        # probing) — NOT_SERVING until warmup completes, per-model afterward.
        add_HealthServicer_to_server(GrpcHealthService(impl), server)
        return server

    servers = [build(0)]
    port = _bind(servers[0], address, credentials)
    if port == 0:
        raise RuntimeError(f"could not bind {address}")
    if uds_path:
        _add_uds_port(servers[0], uds_path)
    if listeners > 1 and not hasattr(socket, "SO_REUSEPORT"):
        log.warning("no SO_REUSEPORT on this host: one gRPC listener, not %d", listeners)
        listeners = 1
    shared = f"{address.rpartition(':')[0]}:{port}"
    for index in range(1, listeners):
        server = build(index)
        if _bind(server, shared, credentials) != port:
            log.warning(
                "gRPC listener %d of %d could not bind %s: the first serves alone",
                index + 1, listeners, shared,
            )
            # A bound listener that never accepts is still handed its share
            # of the connections, and grpc closes a listening socket only
            # on the way through a start and a stop.
            for extra in servers[1:] + [server]:
                extra.start()
                extra.stop(0).wait()
            del servers[1:]
            break
        servers.append(server)
    return Listeners(servers), port


def load_ssl_credentials(path) -> "grpc.ServerCredentials":
    """tensorflow_model_server's --ssl_config_file: a text-format SSLConfig
    whose fields carry the PEM CONTENTS inline (upstream convention).
    client_verify=true demands a client certificate chained to custom_ca
    (mTLS); custom_ca without client_verify merely offers it."""
    import pathlib

    from google.protobuf import text_format

    from ..proto import serving_apis_pb2 as apis

    cfg = text_format.Parse(pathlib.Path(path).read_text(), apis.SSLConfig())
    if not cfg.server_key or not cfg.server_cert:
        raise ValueError(
            f"{path}: SSLConfig requires both server_key and server_cert "
            "(PEM contents inline)"
        )
    if cfg.client_verify and not cfg.custom_ca:
        # grpc-python itself rejects require_client_auth without root
        # certificates ("Illegal to require client auth without providing
        # root certificates!"); surface the config-level fix instead.
        raise ValueError(
            f"{path}: client_verify requires custom_ca (the CA that signs "
            "client certificates; grpc refuses client auth without roots)"
        )
    return grpc.ssl_server_credentials(
        [(cfg.server_key.encode(), cfg.server_cert.encode())],
        root_certificates=cfg.custom_ca.encode() if cfg.custom_ca else None,
        require_client_auth=cfg.client_verify,
    )


def load_demo_servable(
    registry: ServableRegistry,
    kind: str = "dcn_v2",
    name: str = "DCN",
    version: int = 1,
    seed: int = 0,
    config: ModelConfig | None = None,
    **config_overrides,
) -> Servable:
    """Build + register a randomly-initialized servable (demo/bench path;
    production params come from train/checkpoint.py). An explicit `config`
    wins over keyword overrides."""
    config = config or ModelConfig(name=name, **config_overrides)
    model = build_model(kind, config)
    # Drawn in its serving shape: a table of gigabytes never exists twice.
    params = jax.jit(functools.partial(model.init, packed=True))(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    dense = config.num_dense_features if model.takes_dense else None
    servable = Servable(
        name=name,
        version=version,
        model=model,
        params=params,
        signatures=ctr_signatures(config.num_fields, with_dense=dense),
    )
    registry.load(servable)
    return servable


def start_rest_in_thread(impl, host: str, port: int, metrics=None) -> int:
    """Run the REST gateway (:8501 surface) on its own event loop in a
    daemon thread, next to a THREADED gRPC server — the gateway only
    touches the (thread-safe) impl/batcher. Startup is SYNCHRONIZED: an
    operator who asked for the surface gets a live port back or a
    RuntimeError, never a healthy-looking process with a dead thread
    (tensorflow_model_server exits on REST bind failure too; a wait()
    timeout counts as failure — the gateway state would be unknown).
    Shared by the single-host CLI and the multihost leader."""
    import asyncio
    import threading

    from .rest import start_rest_gateway

    rest_ready: dict = {}
    rest_up = threading.Event()

    def run_rest():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        try:
            _runner, bound = loop.run_until_complete(
                start_rest_gateway(impl, host, port, metrics)
            )
            rest_ready["port"] = bound
        except BaseException as exc:  # noqa: BLE001 — reported to caller
            rest_ready["error"] = exc
            return
        finally:
            rest_up.set()
        loop.run_forever()

    threading.Thread(target=run_rest, name="rest", daemon=True).start()
    if not rest_up.wait(timeout=30) or "error" in rest_ready:
        raise RuntimeError(
            f"REST gateway failed to start on {host}:{port}: "
            f"{rest_ready.get('error', 'startup timed out after 30s')}"
        )
    return rest_ready["port"]


def _replay_warmup(warmup_file, servable, batcher) -> int:
    from .warmup import replay_warmup_file

    return replay_warmup_file(warmup_file, servable, batcher)


def _servable_change_hook(score_cache, quality, row_cache=None):
    """ONE on_servable_change callable for the version watchers, fanning
    out to every armed plane that cares about registry mutations: the
    cache plane's generation invalidation (by model name) — BOTH tiers,
    the whole-request store and the row-granular store — and the quality
    plane's version-change accounting. None
    when nothing is armed, so the watcher keeps its no-hook fast path."""
    hooks = []
    if score_cache is not None:
        hooks.append(score_cache.invalidate_model)
    if row_cache is not None:
        hooks.append(row_cache.invalidate_model)
    if quality is not None:
        hooks.append(quality.note_servable_change)
    if not hooks:
        return None
    if len(hooks) == 1:
        return hooks[0]

    def hook(model_name: str) -> None:
        for h in hooks:
            h(model_name)

    return hook


class ModelLifecycle:
    """The model LIST as a runtime-reconcilable object (--model-config-file
    deployments): one version watcher per served model, plus `apply()` —
    the full HandleReloadConfigRequest semantics, where the supplied
    model_config_list REPLACES the model list (upstream behavior):

    - new entries start a watcher (whose synchronous first poll loads any
      ready version — the RPC returns with new models REGISTERED, like
      upstream's reload, which equally blocks on load);
    - entries absent from the new config stop their watcher and unload
      the model; an entry whose base_path or model_platform CHANGED is a
      remove+add (the watcher restarts on the new source);
    - unchanged entries get their version_labels applied DECLARATIVELY
      now (a label naming an unloaded version is FAILED_PRECONDITION;
      labels of restarted/new models seed via desired_labels as versions
      land).

    Reloads serialize on one lock — two concurrent conflicting reloads
    must not interleave — which also means a reload loading large models
    holds off shutdown until it completes (document-level trade-off,
    matching the blocking upstream RPC).

    build_stack returns it in the watcher slot (.stop() tears everything
    down, signalling all watchers before joining so drain time is the
    max, not the sum)."""

    def __init__(self, cfg, registry, batcher, model_config, mesh,
                 tensor_parallel: bool | None = None):
        import threading

        self._cfg = cfg
        self._registry = registry
        self._batcher = batcher
        self._model_config = model_config
        self._mesh = mesh
        # The EFFECTIVE layout knob: the [mesh] section's value when that
        # mode armed the mesh, cfg.tensor_parallel otherwise — watcher
        # loads must pre-place params in the layout the executor serves.
        self._tensor_parallel = (
            cfg.tensor_parallel if tensor_parallel is None else tensor_parallel
        )
        self._watchers: dict[str, object] = {}
        self._sources: dict[str, tuple[str, str]] = {}  # name -> (path, platform)
        self._lock = threading.Lock()  # reloads arrive on RPC threads

    @property
    def watchers(self):
        with self._lock:
            return list(self._watchers.values())

    def configured_models(self) -> set[str]:
        """Names this lifecycle owns a watcher for — configured, whether or
        not a version has landed yet (GetModelStatus reports START for the
        not-yet-ready ones instead of NOT_FOUND)."""
        with self._lock:
            return set(self._watchers)

    def _make_watcher(self, mc):
        from .version_watcher import VersionWatcher, VersionWatcherConfig

        cfg, batcher = self._cfg, self._batcher
        score_cache = getattr(batcher, "score_cache", None)
        row_cache = getattr(batcher, "row_cache", None)
        quality = getattr(batcher, "quality", None)
        kind = mc.model_platform or cfg.model_kind
        if kind == "tensorflow":  # upstream's only platform string
            kind = cfg.model_kind
        return VersionWatcher(
            mc.base_path,
            self._registry,
            VersionWatcherConfig(
                model_name=mc.name,
                model_kind=kind,
                desired_labels=tuple(
                    sorted((l, int(v)) for l, v in mc.version_labels.items())
                ),
                poll_interval_s=cfg.file_system_poll_wait_seconds,
                max_load_attempts=cfg.max_num_load_retries + 1,
            ),
            warmup=batcher.warmup_via_queue if cfg.warmup else None,
            warmup_replay=(
                (lambda sv, wf: _replay_warmup(wf, sv, batcher))
                if cfg.warmup else None
            ),
            model_config=self._model_config,
            mesh=self._mesh,
            tensor_parallel=self._tensor_parallel,
            # Version swaps drop the swapped model's cached scores the
            # moment the registry flips (cache-plane generation hook) and
            # tick the quality plane's version-change counter (ISSUE 7 —
            # version-pair drift reads the per-version sketches directly).
            on_servable_change=_servable_change_hook(
                score_cache, quality, row_cache=row_cache
            ),
        ).start()

    @staticmethod
    def _source_of(mc) -> tuple[str, str]:
        return (mc.base_path, mc.model_platform)

    def apply(self, model_configs) -> None:
        """Reconcile toward `model_configs` (validated entries). Raises
        registry label errors (ModelNotFound/VersionNotFound/ValueError)
        BEFORE mutating anything for the label changes it applies now."""
        with self._lock:
            wanted = {mc.name: mc for mc in model_configs}
            # An entry whose SOURCE changed is not "existing" — its
            # watcher must restart on the new base_path/platform
            # (upstream applies base-path moves on this same RPC).
            unchanged = {
                name for name in set(self._watchers) & set(wanted)
                if self._sources.get(name) == self._source_of(wanted[name])
            }
            # Declarative labels for UNCHANGED models: validate+apply
            # atomically first, so a bad label aborts the reload before
            # any watcher is started or stopped.
            existing_label_maps = {
                name: {l: int(v) for l, v in wanted[name].version_labels.items()}
                for name in unchanged
            }
            if existing_label_maps:
                self._registry.replace_label_maps(existing_label_maps)
            for name in sorted(set(self._watchers) - unchanged):
                w = self._watchers.pop(name)
                self._sources.pop(name, None)
                w.stop()
                try:
                    self._registry.unload(name)
                except KeyError:
                    pass  # never had a ready version
                log.info(
                    "reload: %s model %r",
                    "restarting" if name in wanted else "removed", name,
                )
            for name in sorted(set(wanted) - unchanged):
                self._watchers[name] = self._make_watcher(wanted[name])
                self._sources[name] = self._source_of(wanted[name])
                log.info("reload: added model %r (base_path=%s)",
                         name, wanted[name].base_path)

    def stop(self) -> None:
        with self._lock:
            watchers = list(self._watchers.values())
        for w in watchers:  # signal everyone first: drain in parallel
            w.request_stop()
        for w in watchers:
            w.stop()


def _parse_model_server_config(path):
    """Parse+validate a --model_config_file BEFORE any threads start, so a
    typo'd config fails with nothing to tear down. Returns the validated
    model_config_list entries."""
    import pathlib

    from google.protobuf import text_format

    from ..proto import serving_apis_pb2 as apis

    msc = text_format.Parse(
        pathlib.Path(path).read_text(), apis.ModelServerConfig()
    )
    if msc.WhichOneof("config") != "model_config_list" or not msc.model_config_list.config:
        raise ValueError(
            f"{path}: a model_config_list with at least one model is required"
        )
    from ..utils.config import validate_model_config_entries

    return validate_model_config_entries(msc.model_config_list.config, str(path))


def _start_model_config_watchers(
    cfg, model_configs, registry, batcher, model_config, mesh,
    tensor_parallel: bool | None = None,
):
    """tensorflow_model_server's --model_config_file: one version watcher
    per model_config_list entry — multi-model serving over ONE registry/
    batcher/impl (the registry keys servables by name, the batcher jit
    caches per servable, so nothing else changes shape).

    Upstream field mapping: `name` and `base_path` as-is; `model_platform`
    carries the zoo family here (upstream's "tensorflow" means "use the
    server's default family", since every model is a TF graph there);
    `version_labels` seed per-model label maps. Per-model ARCHITECTURE
    comes from each version's own artifact (native checkpoints carry a
    manifest; SavedModel dirs infer or use the global [model] section), so
    heterogeneous models need self-describing artifacts.
    """
    lifecycle = ModelLifecycle(
        cfg, registry, batcher, model_config, mesh,
        tensor_parallel=tensor_parallel,
    )
    lifecycle.apply(model_configs)
    return lifecycle


class GracefulShutdown:
    """Drain-aware teardown — ONE path for every way the server stops.

    SIGTERM (the deploy orchestrator's stop signal), REST-startup failure,
    and normal wait_for_termination exit all converge here, replacing the
    historical server.stop(0)-here / server.stop(2).wait()-there split.
    The sequence:

    1. `impl.draining = True`: the grpc.health.v1 servicer flips to
       NOT_SERVING (load balancers stop routing) and every NEW inference
       admission is refused UNAVAILABLE with a "draining" detail — fan-out
       clients reroute to another backend immediately.
    2. Version watchers stop (no new loads/warmups enter the batcher).
    3. `batcher.drain(grace_s)`: queued + staged + in-flight batches run
       to completion, bounded by the grace period — work the server
       ACCEPTED is work it answers.
    4. `server.stop(grace)`, which stops every listener of the port and
       waits for all (`Listeners`), with the grace budget REMAINING after
       the drain (plus a small floor so handler threads can encode the
       responses the drain just completed), then batcher/request-log
       teardown.

    Idempotent and thread-safe: the first caller runs the sequence,
    everyone else (the SIGTERM thread racing the finally block, say)
    blocks until it finishes. `shutdown()` is safe from any thread;
    `install_signal_handler()` must run on the main thread."""

    # Floor for the post-drain RPC grace: even a fully-drained server
    # needs a beat for handler threads to serialize responses.
    MIN_RPC_GRACE_S = 1.0

    def __init__(
        self,
        impl,
        batcher,
        grace_s: float = 5.0,
        watcher=None,
        request_logger=None,
        lifecycle=None,
        recovery=None,
    ):
        self.impl = impl
        self.batcher = batcher
        self.grace_s = max(float(grace_s), 0.0)
        self.watcher = watcher
        self.request_logger = request_logger
        # Lifecycle controller (serving/lifecycle.py): stopped BEFORE the
        # watcher so a mid-drain tick can't publish/promote/rollback into
        # a stack that is tearing down.
        self.lifecycle = lifecycle
        # Recovery controller (serving/recovery.py): aborted BEFORE the
        # batcher drain — a SIGTERM arriving mid-REINIT must not leave
        # drain() waiting its whole grace on replayed batches the dying
        # replica will never finish (quarantine × shutdown interplay,
        # ISSUE 11 satellite). Captured-but-unreplayed items fail
        # UNAVAILABLE so their clients reroute immediately.
        self.recovery = recovery
        # Fleet plane (fleet/replica.py): announced IMMEDIATELY after the
        # draining flip — peers and the router hear the drain through
        # gossip before their next health probe — then stopped with the
        # transport.
        self.fleet = None
        self.server = None  # attached once created (create_server)
        self.drained: bool | None = None
        self._lock = threading.Lock()
        self._started = False
        self._done = threading.Event()

    def install_signal_handler(self) -> bool:
        """Route SIGTERM through the drain sequence (main thread only —
        CPython restriction; embedded/test callers just call shutdown())."""
        try:
            signal.signal(signal.SIGTERM, self._on_sigterm)
        except ValueError:  # not the main thread
            return False
        return True

    def _on_sigterm(self, signum, frame) -> None:
        # Handlers run on the main thread, which is parked inside
        # wait_for_termination — the drain must run elsewhere so stop()
        # can unblock it.
        log.info("SIGTERM: draining (grace %.1fs)", self.grace_s)
        threading.Thread(
            target=self.shutdown, name="graceful-drain", daemon=True
        ).start()

    def shutdown(self) -> None:
        with self._lock:
            if self._started:
                run_it = False
            else:
                self._started = True
                run_it = True
        if not run_it:
            self._done.wait()
            return
        try:
            t0 = time.perf_counter()
            # 1. Refuse new work; health goes NOT_SERVING.
            self.impl.draining = True
            # 1.5. Tell the fleet NOW (one immediate push-pull round, not
            # the next interval): the router folds the draining record
            # into its scoreboard before this replica's first refused RPC.
            if self.fleet is not None:
                try:
                    self.fleet.announce()
                except Exception:
                    log.debug("fleet drain announce failed", exc_info=True)
            # 2. No new loads/warmups behind the drain: the lifecycle
            # controller first (its ticks drive the watcher), then the
            # watcher itself.
            if self.lifecycle is not None:
                self.lifecycle.stop()
            if self.watcher is not None:
                self.watcher.stop()
            cascade_watcher = getattr(self.impl, "cascade_watcher", None)
            if cascade_watcher is not None:
                cascade_watcher.stop()
            # 2.5. Abort any in-flight recovery cycle BEFORE the drain:
            # its watchdog stops, captured-but-unreplayed work fails
            # UNAVAILABLE (clients reroute — this replica is going away),
            # and drain() below can no longer deadlock waiting on a
            # replay that will never be issued.
            if self.recovery is not None:
                self.recovery.shutdown_for_drain(self.grace_s)
            # 3. Answer everything already accepted, bounded by grace.
            self.drained = self.batcher.drain(self.grace_s)
            if not self.drained:
                log.warning(
                    "drain grace %.1fs expired with work still in flight; "
                    "stopping anyway", self.grace_s,
                )
            # 4. Stop the transport with whatever grace remains (handlers
            # are unblocking off the just-completed batcher futures), then
            # the batcher and the log writer.
            left = max(
                self.grace_s - (time.perf_counter() - t0),
                self.MIN_RPC_GRACE_S,
            )
            if self.server is not None:
                self.server.stop(left).wait()
            if self.fleet is not None:
                self.fleet.stop()
            self.batcher.stop()
            if self.request_logger is not None:
                self.request_logger.close()
            log.info(
                "shutdown complete (drained=%s, %.1fs)",
                self.drained, time.perf_counter() - t0,
            )
        finally:
            self._done.set()


def build_stack(
    cfg: ServerConfig,
    checkpoint: str | None = None,
    savedmodel: str | None = None,
    model_config: ModelConfig | None = None,
    model_base_path: str | None = None,
    cache_config=None,
    overload_config=None,
    utilization_config=None,
    quality_config=None,
    lifecycle_config=None,
    batching_config=None,
    transport_config=None,
    recovery_config=None,
    mesh_config=None,
    elastic_config=None,
    cascade_config=None,
    integrity_config=None,
    on_impl=None,
):
    """Registry + batcher (+ mesh executor) + impl from a ServerConfig.
    on_impl(impl), when given, is called once the impl exists and BEFORE
    the parameters are made and the ladder is warmed: serve() listens from
    there, health NOT_SERVING and inference refused UNAVAILABLE until the
    warm-up is complete.
    model_config (the TOML [model] section) pins the architecture for the
    demo and SavedModel-import paths; checkpoints carry their own.
    model_base_path switches to TF-Serving's versioned-directory lifecycle
    (serving/version_watcher.py) instead of a fixed artifact;
    cfg.model_config_file switches to MULTI-model serving (one watcher per
    model_config_list entry). cache_config (the TOML [cache] section, a
    utils.config.CacheConfig) arms the cache plane: an exact-match score
    cache + single-flight coalescing at submit, intra-batch dedup in the
    batcher, generation invalidation wired to every version watcher.
    overload_config (the TOML [overload] section, a utils.config.
    OverloadConfig) arms the adaptive overload plane: a self-tuning
    admission limit replaces the static queue_capacity_candidates bound,
    with criticality lanes, doomed-work refusal, brownout stale-serve
    (through the score cache, when armed), and retry-after pushback.
    utilization_config (the TOML [utilization] section, a utils.config.
    UtilizationConfig) arms the device-utilization attribution plane:
    an occupancy ledger + gap waterfall behind GET /utilz, the
    `utilization` block in /monitoring, dts_tpu_utilization_* Prometheus
    series, and a per-device counter track in the Chrome export.
    quality_config (the TOML [quality] section, a utils.config.
    QualityConfig) arms the model-quality plane: per-(model, version)
    score-distribution sketches fed from the batcher completer, PSI/JS
    drift vs a pinned reference and between live versions, the /labelz
    label-feedback join (windowed AUC + calibration), drift-linked trace
    exemplars, GET /qualityz, a `quality` block in /monitoring, and
    dts_tpu_quality_* Prometheus series.
    lifecycle_config (the TOML [lifecycle] section, a utils.config.
    LifecycleConfig) arms the continuous-freshness plane: canary
    admission over the version watcher's hot-swaps, drift/AUC
    auto-rollback with retire+blacklist, the optional fine-tune
    publisher, GET /lifecyclez, a `lifecycle` block in /monitoring, and
    dts_tpu_lifecycle_* Prometheus series — requires model_base_path
    (the watched dir IS the rollout mechanism) and an armed quality
    plane (the rollback signal).
    mesh_config (the TOML [mesh] section, a utils.config.MeshConfig)
    arms the MESH SERVING MODE (ISSUE 13): a ("data", "model") device
    mesh over the slice's chips with a hardened ShardedExecutor as the
    batcher's run_fn — candidate rows scattered over the data axis,
    embedding vocab over the model axis per the family's named partition
    rules, same wire protocol, one process spanning N chips. Mode
    conflicts are EXPLICIT build-time refusals, never runtime surprises:
    [recovery] scope='per_chip' (an SPMD executable spans
    every chip; whole-executor recovery COMPOSES — the mesh executor
    quarantines/reinits/replays as one unit), output_top_k (a
    single-chip jitted-entry variant), and the legacy [server]
    mesh_devices knob (pick one surface).
    elastic_config (the TOML [elastic] section, a utils.config.
    ElasticConfig; requires [mesh]) arms ELASTIC MESH SERVING
    (ISSUE 15): a pre-built, pre-warmed ladder of ("data", "model")
    splits over the same devices with a pressure/load-driven controller
    switching the serving split at runtime — hitlessly (new dispatches
    route to the target split while in-flight batches on the old split
    drain behind the per-split in-flight barrier; executables are
    warmup-compiled per rung, so a switch never compiles on the serving
    path). Surfaces: the `elastic` block in /meshz//monitoring and
    dts_tpu_elastic_* Prometheus series."""
    # Validate plane prerequisites BEFORE any threads exist — a typo'd
    # config must leave nothing to tear down.
    mesh_armed = mesh_config is not None and mesh_config.enabled
    if mesh_armed:
        if cfg.mesh_devices or cfg.model_parallel != 1 or cfg.tensor_parallel:
            raise ValueError(
                "[mesh] enabled conflicts with the legacy [server] mesh "
                "knobs (mesh_devices/model_parallel/tensor_parallel): "
                "configure the mesh in ONE place — the [mesh] section is "
                "the serving mode; drop the [server] copies"
            )
        if cfg.output_top_k:
            raise ValueError(
                "[mesh] enabled conflicts with output_top_k: top-k "
                "output compaction is a single-chip jitted-entry "
                "variant the sharded executor does not provide — "
                "disable one of them"
            )
        if (
            recovery_config is not None and recovery_config.enabled
            and getattr(recovery_config, "scope", "executor") == "per_chip"
        ):
            # The ISSUE-15 scoped lift: WHOLE-MESH recovery composes (the
            # watchdog treats the mesh executor as one unit — quarantine
            # captures everything, REINIT clears the executor's placed
            # params + sharded executables via clear_for_recovery, replay
            # re-dispatches through the re-warmed mesh). What stays
            # refused is the finer granularity nobody implements:
            raise ValueError(
                "[recovery] scope='per_chip' conflicts with [mesh]: an "
                "SPMD executable spans every chip of the mesh, so there "
                "is no per-chip quarantine to run — a sick chip takes "
                "the executor with it. Use scope='executor' (the "
                "default): the mesh executor quarantines, reinits, and "
                "replays as ONE unit"
            )
    lifecycle_armed = lifecycle_config is not None and lifecycle_config.enabled
    if lifecycle_armed:
        if not model_base_path:
            raise ValueError(
                "[lifecycle] enabled requires --model-base-path: the "
                "watched versioned dir is both the publish target and "
                "the hot-swap mechanism the canary/rollback loop drives"
            )
        if quality_config is None or not quality_config.enabled:
            raise ValueError(
                "[lifecycle] enabled requires [quality] enabled (or "
                "--quality): the rollback gate reads the quality plane's "
                "version-pair drift and per-version label AUC — a "
                "lifecycle with no signal could only ever promote blind"
            )
    elastic_armed = elastic_config is not None and elastic_config.enabled
    if elastic_armed and not mesh_armed:
        raise ValueError(
            "[elastic] enabled requires [mesh] enabled: the elastic "
            "plane re-factorizes the MESH's devices at runtime — the "
            "[mesh] section's split is where serving starts (and the "
            "ladder's rungs must factorize its device count). Arm both, "
            "or drop [elastic]"
        )
    integrity_armed = integrity_config is not None and integrity_config.enabled
    if (
        integrity_armed
        and integrity_config.shadow_fraction > 0
        and cache_config is not None
        and cache_config.enabled
    ):
        # Shadow verification's headline guarantee is "every delivered
        # score was (sampled-)verified bit-identical against a second
        # execution". Exact-match cache hits bypass the batcher entirely
        # — bytes inserted BEFORE the plane armed (or before a sick
        # period was detected) would be re-served for their whole TTL
        # with no detection layer ever touching them again. Refuse the
        # combination instead of silently weakening the guarantee; the
        # row cache COMPOSES (cold rows execute through the
        # shadow-eligible path).
        raise ValueError(
            "[integrity] shadow_fraction > 0 conflicts with [cache] "
            "enabled: exact-match cache hits re-serve cached score bytes "
            "without re-execution, so sampled shadow verification can "
            "never re-check them — the zero-corrupt-delivery guarantee "
            "would silently exclude every cache hit. Disable the score "
            "cache or set shadow_fraction = 0 (wire checksums and "
            "readback screens still compose with the cache)"
        )
    cascade_armed = cascade_config is not None and cascade_config.enabled
    if cascade_armed:
        if cfg.output_top_k:
            raise ValueError(
                "[cascade] enabled conflicts with output_top_k: the "
                "top-k wire replaces the score vector with (score, "
                "index) pairs, but the cascade's scatter needs the full "
                "vector to fill non-survivors from stage-1 scores — the "
                "two selections cannot both own the response shape. "
                "The cascade IS the retrieval-style compaction; drop "
                "output_top_k"
            )
        if mesh_armed:
            raise ValueError(
                "[cascade] enabled conflicts with [mesh] (and [elastic]):"
                " the stage-1 prune is a single-chip jitted-entry "
                "variant the sharded run_fn does not provide, so the "
                "cascade could only ever run its host fallback — "
                "disable one of them"
            )
    model_configs = None
    if cfg.model_config_file:
        if model_base_path or checkpoint or savedmodel:
            raise ValueError(
                "--model-config-file is mutually exclusive with "
                "--model-base-path/--checkpoint/--savedmodel (the config "
                "file owns the model list)"
            )
        if cfg.version_labels:
            raise ValueError(
                "--version-label / [server] version_labels have no meaning "
                "with --model-config-file; put per-model version_labels "
                "maps in the config file's model entries instead"
            )
        model_configs = _parse_model_server_config(cfg.model_config_file)
    registry = ServableRegistry()
    run_fn = None
    mesh = None
    tensor_parallel = cfg.tensor_parallel
    if mesh_armed:
        # First-class mesh serving mode (ISSUE 13): [mesh] / --mesh.
        from ..parallel import ShardedExecutor, make_mesh

        n_devices = mesh_config.devices or len(jax.devices())
        # The [mesh] section is AUTHORITATIVE for the layout (the legacy
        # [server] knobs were refused above, so no silent OR-merge).
        tensor_parallel = mesh_config.tensor_parallel
        if elastic_armed:
            # Elastic mesh serving (ISSUE 15): one ShardedExecutor per
            # ladder rung over the SAME devices, the [mesh] split as the
            # initial rung; warmup below pre-compiles every rung so a
            # runtime switch never pays a compile on the serving path.
            from ..parallel.elastic import (
                ElasticMeshExecutor,
                resolve_ladder,
            )

            if n_devices % mesh_config.model_parallel != 0:
                # Same refusal (and wording) make_mesh raises on the
                # static path — a typo'd [mesh] factorization must not
                # surface as a confusing ladder-entry error here.
                raise ValueError(
                    f"n_devices={n_devices} not divisible by "
                    f"model_parallel={mesh_config.model_parallel}"
                )
            initial = (
                n_devices // mesh_config.model_parallel,
                mesh_config.model_parallel,
            )
            ladder = resolve_ladder(elastic_config.splits, n_devices, initial)
            run_fn = ElasticMeshExecutor(
                splits=ladder,
                initial=initial,
                devices=list(jax.devices())[:n_devices],
                compress_transfer=cfg.compress_transfer,
                tensor_parallel=tensor_parallel,
                output_wire_dtype=cfg.output_wire_dtype,
                history_events=elastic_config.history_events,
            )
            mesh = run_fn.mesh
            log.info(
                "elastic mesh serving on: %d devices, ladder %s (initial "
                "%s) — `elastic` block in /meshz//monitoring, "
                "dts_tpu_elastic_* series",
                n_devices,
                [f"{d}x{m}" for d, m in ladder],
                f"{initial[0]}x{initial[1]}",
            )
        else:
            # make_mesh validates device availability and the
            # devices/model_parallel factorization (explicit refusals).
            mesh = make_mesh(
                n_devices, model_parallel=mesh_config.model_parallel
            )
            run_fn = ShardedExecutor(
                mesh,
                compress_transfer=cfg.compress_transfer,
                tensor_parallel=tensor_parallel,
                output_wire_dtype=cfg.output_wire_dtype,
            )
        log.info(
            "mesh serving mode on: %d devices as %s tensor_parallel=%s "
            "wire=%s — `mesh` block in /monitoring, dts_tpu_mesh_* series",
            n_devices, dict(mesh.shape), tensor_parallel,
            cfg.output_wire_dtype,
        )
    elif cfg.mesh_devices:
        # Legacy [server] mesh knobs (the dryrun/bench surface) — kept
        # working unchanged; production deployments use [mesh].
        from ..parallel import ShardedExecutor, make_mesh

        mesh = make_mesh(cfg.mesh_devices, model_parallel=cfg.model_parallel)
        run_fn = ShardedExecutor(
            mesh,
            compress_transfer=cfg.compress_transfer,
            tensor_parallel=cfg.tensor_parallel,
            output_wire_dtype=cfg.output_wire_dtype,
        )
    score_cache = cache_config.build() if cache_config is not None else None
    if score_cache is not None:
        log.info(
            "score cache on: max_entries=%d max_bytes=%d ttl_s=%.1f "
            "coalesce=%s dedup=%s — GET /cachez on the REST surface",
            cache_config.max_entries, cache_config.max_bytes,
            cache_config.ttl_s, cache_config.coalesce, cache_config.dedup,
        )
    row_cache = cache_config.build_row() if cache_config is not None else None
    if row_cache is not None:
        log.info(
            "row-granular score cache on: max_entries=%d max_bytes=%d "
            "ttl_s=%.1f coalesce=%s — only cold rows execute; `row_cache` "
            "block in /cachez and /monitoring",
            cache_config.row_max_entries, cache_config.row_max_bytes,
            cache_config.row_ttl_s, cache_config.row_coalesce,
        )
    utilization_ledger = (
        utilization_config.build() if utilization_config is not None else None
    )
    if utilization_ledger is not None:
        # Name the ledger's track after the real device (jax is already
        # initialized by this point on every build_stack path). Over a
        # mesh the ledger additionally attributes occupancy PER DEVICE:
        # SPMD batches occupy every chip of the mesh simultaneously, so
        # each device carries the busy timeline (snapshot per_device +
        # one Perfetto counter track per chip).
        try:
            if mesh is not None:
                utilization_ledger.devices = [
                    str(d) for d in mesh.devices.flat
                ]
                utilization_ledger.device = (
                    f"mesh{dict(mesh.shape)}"
                )
            else:
                utilization_ledger.device = str(jax.devices()[0])
        except Exception:  # noqa: BLE001 — a label, never a dependency
            pass
        log.info(
            "utilization attribution on: ring=%d window_s=%.1f "
            "calibrated=%s — GET /utilz on the REST surface",
            utilization_config.ring, utilization_config.window_seconds,
            bool(utilization_config.calibration_file),
        )
    quality_monitor = (
        quality_config.build() if quality_config is not None else None
    )
    if quality_monitor is not None:
        log.info(
            "model-quality observability on: bins=%d window_s=%.1f "
            "drift_threshold_psi=%.2f reference_file=%s — GET /qualityz "
            "and POST /labelz on the REST surface",
            quality_config.bins, quality_config.window_seconds,
            quality_config.drift_threshold_psi,
            quality_config.reference_file or "<none>",
        )
    overload_ctrl = (
        overload_config.build() if overload_config is not None else None
    )
    if overload_ctrl is not None:
        log.info(
            "adaptive overload control on: target_queue_wait_ms=%.1f "
            "brownout_after=%d shed_after=%d stale_while_overloaded_s=%.1f "
            "— `overload` block in /monitoring",
            overload_config.target_queue_wait_ms,
            overload_config.brownout_after_intervals,
            overload_config.shed_after_intervals,
            overload_config.stale_while_overloaded_s,
        )
    # Continuous-batching pipeline knobs ([batching], ISSUE 9): the
    # section's pipeline_depth (when nonzero) wins over the legacy
    # [server] location; the in-flight window / buffer ring / stream
    # split live only in the section and default off.
    pipeline_depth = cfg.pipeline_depth
    inflight_window = 0
    buffer_ring = False
    if batching_config is not None:
        pipeline_depth = batching_config.pipeline_depth or pipeline_depth
        inflight_window = batching_config.inflight_window
        buffer_ring = batching_config.buffer_ring
        if inflight_window or buffer_ring or batching_config.pipeline_depth:
            log.info(
                "continuous-batching pipeline: depth=%d inflight_window=%s "
                "buffer_ring=%s stream_chunk=%d",
                pipeline_depth, inflight_window or "unbounded", buffer_ring,
                batching_config.stream_chunk_candidates,
            )
    batcher = DynamicBatcher(
        buckets=cfg.buckets,
        max_wait_us=cfg.max_wait_us,
        compress_transfer=cfg.compress_transfer,
        run_fn=run_fn,
        pipeline_depth=pipeline_depth,
        inflight_window=inflight_window,
        buffer_ring=buffer_ring,
        queue_capacity_candidates=cfg.queue_capacity_candidates,
        completion_workers=cfg.completion_workers,
        output_wire_dtype=cfg.output_wire_dtype,
        output_top_k=cfg.output_top_k,
        pipelined_dispatch=cfg.pipelined_dispatch,
        score_cache=score_cache,
        row_cache=row_cache,
        # `enabled` is the MASTER switch for the whole cache plane: a
        # config with enabled=false and dedup=true must arm nothing.
        dedup=(
            cache_config.enabled and cache_config.dedup
            if cache_config is not None else False
        ),
        overload=overload_ctrl,
        utilization=utilization_ledger,
        quality=quality_monitor,
    ).start()
    # The batcher's phases also go into an open jax.profiler capture from
    # here on (utils/tracing.py imports no jax, so the server hands it
    # the annotation class).
    tracing.bind_annotation(jax.profiler.TraceAnnotation)
    impl = PredictionServiceImpl(registry, batcher)
    if run_fn is not None and hasattr(run_fn, "snapshot"):
        # Mesh serving surface: /monitoring's `mesh` block and the
        # dts_tpu_mesh_* Prometheus series read the executor's snapshot
        # (geometry, per-device list, pad/batch counters, layout source)
        # — wired for the legacy mesh knobs too, so the dryrun/bench
        # surface reports identically.
        impl.mesh_executor = run_fn
    if elastic_armed:
        # Elastic controller (ISSUE 15): pressure (overload state, when
        # that plane is armed) + the batcher's queue-load/bucket-occupancy
        # EWMA drive runtime split switches. No thread — ticks ride the
        # dispatch path and monitoring scrapes (the overload precedent).
        from ..parallel.elastic import ElasticController

        impl.elastic = ElasticController(
            elastic_config,
            run_fn,
            overload=overload_ctrl,
            load_fn=batcher.queue_load,
            largest_bucket=max(cfg.buckets),
        )
        log.info(
            "elastic controller on: tick=%.2fs dwell=%.1fs up/down after "
            "%d/%d ticks, load thresholds %.2f/%.2f, overload pressure "
            "%s",
            elastic_config.tick_interval_s, elastic_config.dwell_s,
            elastic_config.up_after_ticks, elastic_config.down_after_ticks,
            elastic_config.load_up_threshold,
            elastic_config.load_down_threshold,
            "wired" if overload_ctrl is not None else "absent (load-only)",
        )
    if batching_config is not None:
        # Streamed sub-batch default ([batching] stream_chunk_candidates;
        # a request's x-dts-stream-chunk metadata overrides per call).
        impl.stream_chunk_candidates = batching_config.stream_chunk_candidates
    if transport_config is not None and transport_config.response_arena:
        # Reusable response-encode scratch ([transport] response_arena).
        impl.response_arena = True
        log.info("response-encode arenas on ([transport] response_arena)")
    if recovery_config is not None and recovery_config.enabled:
        # Device-failure recovery plane (serving/recovery.py): attaches
        # itself as batcher.recovery; impl.recovery drives the health
        # flip and /recoveryz. The watchdog thread starts in serve() —
        # embedded callers drive check()/run_cycle() themselves.
        from .recovery import RecoveryController

        impl.recovery = RecoveryController(
            recovery_config, batcher, registry=registry, impl=impl
        )
        log.info(
            "device-failure recovery on: wedge_quarantine_s=%.1f "
            "replay_budget=%d poison_kills=%d — GET /recoveryz on the "
            "REST surface",
            recovery_config.wedge_quarantine_s,
            recovery_config.replay_budget, recovery_config.poison_kills,
        )
    if integrity_armed:
        # Data-integrity plane (serving/integrity.py, ISSUE 20): ONE
        # plane object shared by every hook site — the batcher (shadow
        # sampling + readback screens + escalation), the impl (input CRC
        # verify, response stamping, /integrityz), and the gRPC adapters
        # (metadata read/write) all reach the same counters.
        integrity_plane = integrity_config.build()
        batcher.integrity = integrity_plane
        impl.integrity = integrity_plane
        log.info(
            "data-integrity plane on: wire_checksums=%s screen=%s "
            "shadow_fraction=%.3f trips/window=%d/%.1fs — GET /integrityz "
            "on the REST surface",
            integrity_config.wire_checksums, integrity_config.screen,
            integrity_config.shadow_fraction,
            integrity_config.screen_trips_per_window,
            integrity_config.screen_window_s,
        )
    # Health gating: the grpc.health.v1 servicer reports the overall server
    # NOT_SERVING until the load+warmup phase below completes (standard
    # probes and the client's half-open probing key off this).
    impl.warmup_complete = False
    if on_impl is not None:
        on_impl(impl)

    if cascade_armed:
        # Multi-stage ranking cascade (serving/cascade.py, ISSUE 19): the
        # first-stage servable is a NORMAL registry entry under its own
        # model name — published/hot-swapped through the same versioned-
        # dir machinery as any other model when stage1_base_path is set,
        # else built in-process from the primary architecture (towers
        # share the feature layout; two_tower's user/item split must stay
        # a real split).
        from .cascade import CascadeOrchestrator

        base_mc = model_config or ModelConfig(
            name=cfg.model_name, num_fields=cfg.num_fields
        )
        s1_overrides = {"name": cascade_config.stage1_model}
        if (
            cascade_config.stage1_kind == "two_tower"
            and base_mc.num_user_fields >= base_mc.num_fields
        ):
            s1_overrides["num_user_fields"] = max(1, base_mc.num_fields // 2)
        stage1_mc = dataclasses.replace(base_mc, **s1_overrides)
        if cascade_config.stage1_base_path:
            from .version_watcher import VersionWatcher, VersionWatcherConfig

            impl.cascade_watcher = VersionWatcher(
                cascade_config.stage1_base_path,
                registry,
                VersionWatcherConfig(
                    model_name=cascade_config.stage1_model,
                    model_kind=cascade_config.stage1_kind,
                    poll_interval_s=cfg.file_system_poll_wait_seconds,
                    max_load_attempts=cfg.max_num_load_retries + 1,
                ),
                warmup=batcher.warmup_via_queue if cfg.warmup else None,
                model_config=stage1_mc,
                on_servable_change=_servable_change_hook(
                    score_cache, quality_monitor, row_cache=row_cache
                ),
            ).start()
        else:
            stage1_sv = load_demo_servable(
                registry,
                kind=cascade_config.stage1_kind,
                name=cascade_config.stage1_model,
                config=stage1_mc,
            )
            if cfg.warmup:
                batcher.warmup(stage1_sv)
        impl.cascade = CascadeOrchestrator(
            registry, batcher,
            stage1_model=cascade_config.stage1_model,
            survivor_k=cascade_config.survivor_k,
            survivor_fraction=cascade_config.survivor_fraction,
            score_threshold=cascade_config.score_threshold,
            min_candidates=cascade_config.min_candidates,
        )
        log.info(
            "cascade on: stage1=%s (%s%s) survivors=%s threshold=%s "
            "min_candidates=%d — GET /cascadez on the REST surface",
            cascade_config.stage1_model, cascade_config.stage1_kind,
            f" from {cascade_config.stage1_base_path}"
            if cascade_config.stage1_base_path else " demo",
            cascade_config.survivor_k or
            f"{cascade_config.survivor_fraction:.0%}",
            cascade_config.score_threshold or "<off>",
            cascade_config.min_candidates,
        )

    if model_configs is not None:
        watchers = _start_model_config_watchers(
            cfg, model_configs, registry, batcher, model_config, mesh,
            tensor_parallel=tensor_parallel,
        )
        # Runtime model-list reloads (HandleReloadConfigRequest) reconcile
        # through the same lifecycle object.
        impl.model_lifecycle = watchers
        served = registry.models()
        if served:
            log.info("serving %d model(s) from %s: %s",
                     len(served), cfg.model_config_file,
                     {k: v for k, v in sorted(served.items())})
        else:
            log.warning("no ready versions for any configured model yet; watching")
        # Representative servable for the startup banner: the configured
        # default name when it is served, else any ready model — 'awaiting
        # versions' must mean NOTHING is ready, not 'DCN isn't configured'.
        ready = cfg.model_name if cfg.model_name in served else (
            sorted(served)[0] if served else None
        )
        servable = registry.resolve(ready) if ready else None
        impl.warmup_complete = True
        return registry, batcher, impl, servable, mesh, watchers
    if model_base_path:
        if checkpoint or savedmodel:
            raise ValueError(
                "--model-base-path is mutually exclusive with "
                "--checkpoint/--savedmodel (the base path owns version lifecycle)"
            )
        from .version_watcher import VersionWatcher, VersionWatcherConfig

        watcher = VersionWatcher(
            model_base_path,
            registry,
            VersionWatcherConfig(
                model_name=cfg.model_name,
                model_kind=cfg.model_kind,
                desired_labels=cfg.version_labels,
                poll_interval_s=cfg.file_system_poll_wait_seconds,
                # Upstream semantics: N RETRIES after the first attempt,
                # so total attempts = N + 1.
                max_load_attempts=cfg.max_num_load_retries + 1,
            ),
            # warmup_via_queue: compilation rides the batching thread, so a
            # hot-load never races the jit caches with live traffic.
            warmup=batcher.warmup_via_queue if cfg.warmup else None,
            warmup_replay=(
                (lambda sv, wf: _replay_warmup(wf, sv, batcher))
                if cfg.warmup else None
            ),
            model_config=model_config
            or ModelConfig(name=cfg.model_name, num_fields=cfg.num_fields),
            mesh=mesh,
            tensor_parallel=tensor_parallel,
            on_servable_change=_servable_change_hook(
                score_cache, quality_monitor, row_cache=row_cache
            ),
        ).start()
        # Label-only reloads may re-state this source verbatim (deploy
        # tools replay full configs); anything ELSE is a rejected move.
        impl.served_sources[cfg.model_name] = (str(model_base_path), cfg.model_kind)
        impl.version_watcher = watcher
        if lifecycle_armed:
            from .lifecycle import LifecycleController

            impl.lifecycle = LifecycleController(
                lifecycle_config,
                registry=registry,
                model_name=cfg.model_name,
                watcher=watcher,
                quality=quality_monitor,
            )
            log.info(
                "continuous-freshness lifecycle on: probe_only=%.1fs "
                "ramp %.2f+%.2f/%.1fs to %.2f, promote_after=%.1fs, "
                "rollback psi>=%.2f auc_drop>=%.3f, fine_tune every %s — "
                "GET /lifecyclez on the REST surface",
                lifecycle_config.canary_probe_only_s,
                lifecycle_config.canary_initial_fraction,
                lifecycle_config.canary_ramp_step,
                lifecycle_config.canary_step_dwell_s,
                lifecycle_config.canary_max_fraction,
                lifecycle_config.promote_after_s,
                lifecycle_config.rollback_psi,
                lifecycle_config.rollback_auc_drop,
                (f"{lifecycle_config.fine_tune_interval_s:.0f}s"
                 if lifecycle_config.fine_tune_interval_s > 0 else "<off>"),
            )
        versions = registry.models().get(cfg.model_name, [])
        if not versions:
            log.warning("no ready versions under %s yet; watching", model_base_path)
            servable = None
        else:
            servable = registry.resolve(cfg.model_name)
            log.info("serving %s versions %s from %s", cfg.model_name, versions, model_base_path)
        impl.warmup_complete = True
        return registry, batcher, impl, servable, mesh, watcher
    load_t0 = time.perf_counter()
    if savedmodel:
        from ..interop import import_savedmodel
        from .warmup import warmup_file_for

        servable = import_savedmodel(
            savedmodel,
            cfg.model_kind,
            model_config
            or ModelConfig(name=cfg.model_name, num_fields=cfg.num_fields),
            name=cfg.model_name,
        )
        wf = warmup_file_for(savedmodel)
        if wf is not None and cfg.warmup:
            n = _replay_warmup(wf, servable, batcher)
            log.info("replayed %d warmup records from %s", n, wf)
        registry.load(servable)
        log.info("imported SavedModel %s: %s v%d", savedmodel, servable.name, servable.version)
    elif checkpoint:
        from ..train.checkpoint import load_servable

        servable = load_servable(checkpoint, mesh=mesh, tensor_parallel=tensor_parallel)
        registry.load(servable)
        log.info("loaded checkpoint %s: %s v%d", checkpoint, servable.name, servable.version)
    else:
        servable = load_demo_servable(
            registry,
            kind=cfg.model_kind,
            name=cfg.model_name,
            config=model_config,
            num_fields=cfg.num_fields,
        )
    # Parameter init / checkpoint load is asynchronous on the device:
    # blocked here, where the warm-up would block on it anyway, so that
    # the two stamps do not share it.
    jax.block_until_ready(servable.params)
    warmup_t0 = time.perf_counter()
    impl.startup["params_init_s"] = round(warmup_t0 - load_t0, 3)
    log.info(
        "loaded %s v%d in %.3fs: embedding_pack %s",
        servable.name, servable.version, warmup_t0 - load_t0, servable.embedding_pack,
    )
    if cfg.warmup:
        log.info("warming bucket ladder %s", cfg.buckets)
        batcher.warmup(servable)
    # Static-artifact paths load exactly the versions above, so a label
    # naming anything else is a config error — fail at startup, like
    # tensorflow_model_server refusing labels on unavailable versions
    # (the watcher path instead retries as versions land).
    for label, version in cfg.version_labels:
        registry.set_label(cfg.model_name, label, version)
        log.info("label %r -> %s v%d", label, cfg.model_name, version)
    # Load-time compilation (the ladder's warm-up) is set-up
    # time: reported in /monitoring's `runtime` block, never hidden.
    impl.warmup_s = round(time.perf_counter() - warmup_t0, 3)
    impl.warmup_complete = True
    return registry, batcher, impl, servable, mesh, None


def serve(argv=None) -> None:
    serve_t0 = time.perf_counter()
    parser = argparse.ArgumentParser(description="TPU-native PredictionService")
    parser.add_argument("--config", help="TOML config file ([server] section)")
    parser.add_argument("--checkpoint", help="servable checkpoint dir (train.save_servable)")
    parser.add_argument(
        "--savedmodel",
        help="TF SavedModel dir to import and serve (interop/savedmodel.py; "
        "model family/config from --model-kind/--num-fields)",
    )
    parser.add_argument(
        "--model-base-path", dest="model_base_path",
        help="TF-Serving-style versioned base dir (<base>/1/, <base>/2/, ...): "
        "hot-loads new versions, retires old ones (serving/version_watcher.py)",
    )
    parser.add_argument("--port", type=int)
    parser.add_argument("--host")
    parser.add_argument("--model-kind", dest="model_kind")
    parser.add_argument("--model-name", dest="model_name")
    parser.add_argument("--num-fields", dest="num_fields", type=int)
    parser.add_argument("--max-workers", dest="max_workers", type=int)
    parser.add_argument("--max-wait-us", dest="max_wait_us", type=int)
    parser.add_argument(
        "--mesh", action="store_true", default=None,
        help="mesh serving mode (ISSUE 13): shard serving over a "
        "('data', 'model') device mesh — candidate rows over the data "
        "axis, embedding vocab over the model axis, one process "
        "spanning N chips behind the same wire protocol. Equivalent to "
        "[mesh] enabled=true; with --mesh, --mesh-devices / "
        "--model-parallel / --tensor-parallel configure the MESH "
        "section (`mesh` block in /monitoring, dts_tpu_mesh_* series). "
        "Refuses [recovery] scope='per_chip' and "
        "output_top_k at build time; whole-executor [recovery] and "
        "[elastic] compose",
    )
    parser.add_argument(
        "--elastic", action="store_true", default=None,
        help="elastic mesh serving (ISSUE 15; requires --mesh or [mesh]): "
        "pre-build a ladder of ('data', 'model') splits over the same "
        "devices and let a pressure/load-driven controller switch the "
        "serving split at runtime — hitlessly, with warmup-compiled "
        "executables per rung. Equivalent to [elastic] enabled=true "
        "(`elastic` block in /meshz//monitoring, dts_tpu_elastic_* "
        "series)",
    )
    parser.add_argument(
        "--cascade", action="store_true", default=None,
        help="in-server multi-stage ranking cascade (ISSUE 19): score the "
        "full candidate batch with a cheap first-stage servable (its own "
        "registry entry — hot-swappable like any model), take the top "
        "survivors ON DEVICE so only survivor rows cross the wire-dtype "
        "D2H, then rank just the survivors with the primary model; "
        "non-survivors keep their stage-1 scores and every row carries "
        "stage provenance in the response. Equivalent to [cascade] "
        "enabled=true (`cascade` block in /monitoring, GET /cascadez, "
        "dts_tpu_cascade_* series). Refuses output_top_k and [mesh]/"
        "[elastic] at build time",
    )
    parser.add_argument("--mesh-devices", dest="mesh_devices", type=int)
    parser.add_argument("--model-parallel", dest="model_parallel", type=int)
    parser.add_argument(
        "--tensor-parallel", dest="tensor_parallel", action="store_true", default=None,
        help="shard dense MLP/cross weights over the model axis",
    )
    parser.add_argument("--no-warmup", action="store_true")
    parser.add_argument("--rest-port", dest="rest_port", type=int, default=0,
                        help="also serve the TF-Serving REST API (:8501 "
                        "surface, /v1/models/... routes) on this port")
    parser.add_argument("--metrics-every-s", type=float, default=0.0,
                        help="periodically log a metrics snapshot")
    parser.add_argument(
        "--tracing", action="store_true", default=None,
        help="per-request span tracing (W3C traceparent propagation; GET "
        "/tracez on the REST surface, ?format=chrome for a Perfetto-"
        "loadable export). Equivalent to [observability] tracing=true",
    )
    parser.add_argument(
        "--cache", action="store_true", default=None,
        help="exact-match score cache + single-flight coalescing at the "
        "batcher (cache/score_cache.py; GET /cachez on the REST surface). "
        "Equivalent to [cache] enabled=true; the [cache] section carries "
        "the capacity/ttl/coalesce/dedup knobs and the row-granular tier "
        "(row_granular: per-row score caching — only cold rows execute)",
    )
    parser.add_argument(
        "--overload", action="store_true", default=None,
        help="adaptive overload control (serving/overload.py): self-tuning "
        "admission limit driven by queue-wait vs target, criticality "
        "lanes, doomed-work refusal, brownout stale-serve, retry-after "
        "pushback. Equivalent to [overload] enabled=true; the [overload] "
        "section carries the target/limit/brownout/stale knobs",
    )
    parser.add_argument(
        "--utilization", action="store_true", default=None,
        help="device-utilization attribution (serving/utilization.py): "
        "occupancy ledger + idle-gap waterfall (GET /utilz on the REST "
        "surface, `utilization` block in /monitoring, "
        "dts_tpu_utilization_* Prometheus series, Perfetto counter "
        "track) with a live achieved_fraction_of_device_limit estimate. "
        "Equivalent to [utilization] enabled=true; the [utilization] "
        "section carries the ring/window/calibration knobs",
    )
    parser.add_argument(
        "--quality", action="store_true", default=None,
        help="model-quality observability (serving/quality.py): "
        "per-(model, version) score-distribution sketches fed from the "
        "batcher completer, PSI/JS drift vs a pinned reference "
        "(POST /qualityz/snapshot) and between live versions, label "
        "feedback via POST /labelz (windowed AUC + calibration), and "
        "drift-linked /tracez exemplars (GET /qualityz, `quality` block "
        "in /monitoring, dts_tpu_quality_* Prometheus series). "
        "Equivalent to [quality] enabled=true; the [quality] section "
        "carries the bins/window/drift/label knobs",
    )
    parser.add_argument(
        "--lifecycle", action="store_true", default=None,
        help="continuous-freshness lifecycle (serving/lifecycle.py): "
        "canary admission over the version watcher's hot-swaps (probe "
        "lane first, then a configurable default-lane ramp), drift/AUC "
        "auto-rollback with retire+blacklist, and the optional "
        "fine-tune publisher ([lifecycle] fine_tune_interval_s). "
        "Requires --model-base-path and --quality (the rollback "
        "signal). Equivalent to [lifecycle] enabled=true; the "
        "[lifecycle] section carries the ramp/threshold/publisher knobs "
        "(GET /lifecyclez, `lifecycle` block in /monitoring, "
        "dts_tpu_lifecycle_* Prometheus series)",
    )
    parser.add_argument(
        "--recovery", action="store_true", default=None,
        help="device-failure recovery plane (serving/recovery.py): a "
        "watchdog escalates the batcher's wedge clock into a "
        "quarantine (health NOT_SERVING, new work refused UNAVAILABLE "
        "so clients failover), tears down and rebuilds the jitted "
        "executors in-process, replays every in-flight and queued "
        "request, and bisects a batch that deterministically kills the "
        "executor to isolate poisoned inputs (they alone fail "
        "INVALID_ARGUMENT). Equivalent to [recovery] enabled=true; the "
        "[recovery] section carries the watchdog/replay/bisection knobs "
        "(GET /recoveryz, `recovery` block in /monitoring, "
        "dts_tpu_recovery_* Prometheus series)",
    )
    parser.add_argument(
        "--fleet", action="store_true", default=None,
        help="fleet robustness plane (fleet/): join the cross-replica "
        "health gossip mesh and follow fleet-coordinated rollout state "
        "(fleet/gossip.py + fleet/rollout.py). Equivalent to [fleet] "
        "enabled=true; the [fleet] section carries the self_id/peers/"
        "gossip/rollout knobs (GET /fleetz, `fleet` block in /monitoring, "
        "dts_tpu_fleet_* Prometheus series)",
    )
    parser.add_argument(
        "--integrity", action="store_true", default=None,
        help="end-to-end data-integrity plane (serving/integrity.py): "
        "CRC32C wire checksums over tensor bytes both directions "
        "(x-dts-input-crc verified at decode — a corrupted request fails "
        "alone, never its batch; x-dts-score-crc stamped on responses "
        "for opted-in clients), post-readback NaN/Inf sanity screens "
        "that fail only the corrupted row, and sampled bit-identity "
        "shadow re-execution whose mismatches escalate into the "
        "[recovery] quarantine->reinit->replay cycle and gossip a "
        "`suspect` verdict fleet-wide. Equivalent to [integrity] "
        "enabled=true; the [integrity] section carries the "
        "screen/shadow knobs (GET /integrityz, POST /integrityz/audit, "
        "`integrity` block in /monitoring, dts_tpu_integrity_* "
        "Prometheus series)",
    )
    parser.add_argument(
        "--router", action="store_true", default=None,
        help="run as the FLEET ROUTER instead of a serving replica "
        "(fleet/router.py): a jax-free tier speaking the PredictionService "
        "wire protocol that embeds the sharded fan-out client as its "
        "steering brain — fleet-scope row affinity, hedging, failover, "
        "gossip-informed scoreboard, single-writer rollout coordination. "
        "Requires --config with [client] hosts (the replica fleet) and "
        "[fleet]; ignores every serving/model flag",
    )
    parser.add_argument(
        "--uds-path", dest="uds_path",
        help="also serve gRPC on this Unix-domain socket path (co-located "
        "fan-out clients dial unix:<path>, skipping the TCP/loopback "
        "stack). Equivalent to [transport] uds_path",
    )
    parser.add_argument(
        "--stream-chunk", dest="stream_chunk", type=int,
        help="default candidates per PredictStream sub-batch (server-side "
        "split; 0 = single chunk). Equivalent to [batching] "
        "stream_chunk_candidates; requests override via "
        "x-dts-stream-chunk metadata",
    )
    parser.add_argument(
        "--batching-parameters-file", dest="batching_parameters_file",
        help="tensorflow_model_server-format batching config (text-format "
        "BatchingParameters): allowed_batch_sizes -> bucket ladder, "
        "batch_timeout_micros -> max_wait_us, etc. (utils/config.py "
        "apply_batching_parameters); applied over [server] TOML values",
    )
    parser.add_argument(
        "--model-config-file", dest="model_config_file",
        help="multi-model serving: a tensorflow_model_server-format "
        "ModelServerConfig textproto (model_config_list of name/base_path/"
        "model_platform/version_labels; one version watcher per model)",
    )
    parser.add_argument(
        "--file-system-poll-wait-seconds", dest="file_system_poll_wait_seconds",
        type=float, help="version-watcher poll interval (upstream flag name)",
    )
    parser.add_argument(
        "--max-num-load-retries", dest="max_num_load_retries", type=int,
        help="bounded retries for a failing version load (upstream flag name)",
    )
    parser.add_argument(
        "--ssl-config-file", dest="ssl_config_file",
        help="serve gRPC over TLS: a tensorflow_model_server-format "
        "SSLConfig textproto (PEM contents inline; client_verify=true "
        "for mTLS) — load_ssl_credentials",
    )
    parser.add_argument(
        "--request-log-file", dest="request_log_file",
        help="log a sample of requests as PredictionLog TFRecords (the "
        "upstream LoggingConfig surface; output is directly usable as an "
        "assets.extra/tf_serving_warmup_requests file)",
    )
    parser.add_argument(
        "--request-log-sampling", dest="request_log_sampling", type=float,
        help="sampling rate in [0,1] for --request-log-file (default 0.01)",
    )
    parser.add_argument(
        "--version-label", dest="version_label_args", action="append",
        metavar="LABEL=VERSION", default=None,
        help="assign a version label (repeatable), e.g. --version-label "
        "stable=2 --version-label canary=3; requests may then address "
        "/labels/{label} (REST) or ModelSpec.version_label (gRPC)",
    )
    args = parser.parse_args(argv)

    if args.router:
        # Router tier: no model, no jax, no batcher — delegate to the
        # fleet router's own entry point before any stack build. Shared
        # transport flags pass through; everything else is replica-only.
        if not args.config:
            raise SystemExit("--router requires --config ([client] hosts "
                             "+ [fleet] section)")
        from ..fleet.router import main as router_main

        router_argv = ["--config", args.config]
        if args.host:
            router_argv += ["--host", args.host]
        if args.port:
            router_argv += ["--port", str(args.port)]
        if args.uds_path:
            router_argv += ["--uds-path", args.uds_path]
        return router_main(router_argv)

    from ..utils.config import (
        BatchingConfig,
        CacheConfig,
        CascadeConfig,
        ElasticConfig,
        FleetConfig,
        IntegrityConfig,
        LifecycleConfig,
        MeshConfig,
        ObservabilityConfig,
        OverloadConfig,
        QualityConfig,
        RecoveryConfig,
        TransportConfig,
        UtilizationConfig,
    )

    cfgs = load_config(args.config) if args.config else {"server": ServerConfig()}
    cfg = cfgs["server"]
    batching_config = cfgs.get("batching") or BatchingConfig()
    if args.stream_chunk is not None:
        batching_config = dataclasses.replace(
            batching_config, stream_chunk_candidates=max(args.stream_chunk, 0)
        )
    transport_config = cfgs.get("transport") or TransportConfig()
    if args.uds_path:
        transport_config = dataclasses.replace(
            transport_config, uds_path=args.uds_path
        )
    obs = cfgs.get("observability") or ObservabilityConfig()
    if args.tracing:
        obs = dataclasses.replace(obs, tracing=True)
    cache_config = cfgs.get("cache") or CacheConfig()
    if args.cache:
        cache_config = dataclasses.replace(cache_config, enabled=True)
    overload_config = cfgs.get("overload") or OverloadConfig()
    if args.overload:
        overload_config = dataclasses.replace(overload_config, enabled=True)
    utilization_config = cfgs.get("utilization") or UtilizationConfig()
    if args.utilization:
        utilization_config = dataclasses.replace(
            utilization_config, enabled=True
        )
    quality_config = cfgs.get("quality") or QualityConfig()
    if args.quality:
        quality_config = dataclasses.replace(quality_config, enabled=True)
    lifecycle_config = cfgs.get("lifecycle") or LifecycleConfig()
    if args.lifecycle:
        lifecycle_config = dataclasses.replace(lifecycle_config, enabled=True)
    recovery_config = cfgs.get("recovery") or RecoveryConfig()
    if args.recovery:
        recovery_config = dataclasses.replace(recovery_config, enabled=True)
    fleet_config = cfgs.get("fleet") or FleetConfig()
    if args.fleet:
        fleet_config = dataclasses.replace(fleet_config, enabled=True)
    mesh_config = cfgs.get("mesh") or MeshConfig()
    if args.mesh:
        mesh_config = dataclasses.replace(mesh_config, enabled=True)
    elastic_config = cfgs.get("elastic") or ElasticConfig()
    if args.elastic:
        elastic_config = dataclasses.replace(elastic_config, enabled=True)
        if not mesh_config.enabled:
            # The --elastic FLAG implies the mesh mode it resizes (the
            # --lifecycle/--quality precedent: the flag user's intent is
            # unambiguous). A TOML-only [elastic] without [mesh] is NOT
            # auto-armed — a serving-topology change must never ride a
            # config omission; build_stack refuses it explicitly.
            mesh_config = dataclasses.replace(mesh_config, enabled=True)
    cascade_config = cfgs.get("cascade") or CascadeConfig()
    if args.cascade:
        cascade_config = dataclasses.replace(cascade_config, enabled=True)
    integrity_config = cfgs.get("integrity") or IntegrityConfig()
    if args.integrity:
        integrity_config = dataclasses.replace(integrity_config, enabled=True)
    if mesh_config.enabled:
        # With the mesh MODE armed, the CLI mesh-geometry flags configure
        # the [mesh] section (and are withheld from the legacy [server]
        # knobs below, which would otherwise trip the pick-one-surface
        # refusal in build_stack).
        mesh_overrides = {
            k: v for k, v in {
                "devices": args.mesh_devices,
                "model_parallel": args.model_parallel,
                "tensor_parallel": args.tensor_parallel,
            }.items() if v is not None
        }
        if mesh_overrides:
            mesh_config = dataclasses.replace(mesh_config, **mesh_overrides)
        args.mesh_devices = None
        args.model_parallel = None
        args.tensor_parallel = None
    if lifecycle_config.enabled and not quality_config.enabled:
        # --lifecycle implies the quality plane it reads: arming the
        # actuator without its signal would fail build_stack's check, and
        # the flag user's intent is unambiguous.
        quality_config = dataclasses.replace(quality_config, enabled=True)
    model_config = cfgs.get("model")
    if model_config is not None:
        # Explicit CLI architecture flags win over the TOML [model] section
        # (same precedence as the ServerConfig overrides below).
        arch_overrides = {
            k: v
            for k, v in {"num_fields": args.num_fields, "name": args.model_name}.items()
            if v is not None
        }
        if arch_overrides:
            model_config = dataclasses.replace(model_config, **arch_overrides)
    field_names = {f.name for f in dataclasses.fields(ServerConfig)}
    overrides = {
        k: v for k, v in vars(args).items() if v is not None and k in field_names
    }
    if args.no_warmup:
        overrides["warmup"] = False
    if args.version_label_args:
        pairs = []
        for raw in args.version_label_args:
            label, sep, version = raw.partition("=")
            try:
                pairs.append((label, int(version)))
            except ValueError:
                sep = ""
            if not sep or not label:
                raise SystemExit(
                    f"--version-label expects LABEL=VERSION, got {raw!r}"
                )
        # CLI labels replace the TOML map entirely (same precedence as the
        # scalar overrides above).
        overrides["version_labels"] = tuple(sorted(pairs))
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    if args.batching_parameters_file:
        from ..utils.config import apply_batching_parameters

        cfg = apply_batching_parameters(cfg, args.batching_parameters_file)
    # Parse/validate BEFORE the (expensive) stack build: a typo'd PEM must
    # fail in milliseconds, not after checkpoint load + warmup compiles.
    credentials = (
        load_ssl_credentials(args.ssl_config_file)
        if args.ssl_config_file else None
    )

    logging.basicConfig(level=logging.INFO)
    from .. import native
    from ..utils.runtime import enable_compile_cache

    # A host-ops library that fails to build is a start-up error, not a
    # silent switch to the numpy host path (DTS_TPU_NO_NATIVE=1 opts out).
    native_t0 = time.perf_counter()
    native.ensure()
    compile_cache = enable_compile_cache()
    backend_t0 = time.perf_counter()
    jax.devices()  # the first call brings the backend (the TPU runtime) up
    backend_init_s = time.perf_counter() - backend_t0
    metrics = ServerMetrics(window_s=obs.window_seconds)
    transport: dict = {}

    def listen(impl: PredictionServiceImpl) -> None:
        # Listen, on every listener of the port, before the parameters are
        # made and the ladder is warmed (health NOT_SERVING, inference
        # refused UNAVAILABLE until then): a client that has been dialing
        # since before this process existed is deep in its reconnect
        # back-off and notices a connection only when its channel is next
        # polled, so the seconds of load and warm-up are the time it gets
        # to connect in (PERF.md, PR 26).
        listeners, cores = listener_count()
        transport["server"], transport["port"] = create_server(
            impl, f"{cfg.host}:{cfg.port}", cfg.max_workers, metrics,
            credentials=credentials,
            uds_path=transport_config.uds_path or None,
            listeners=listeners,
        )
        impl.startup["listeners"] = {
            "k": len(transport["server"].servers), "cores": cores,
        }
        transport["server"].start()

    try:
        registry, batcher, impl, servable, mesh, watcher = build_stack(
            cfg,
            on_impl=listen,
            checkpoint=args.checkpoint,
            savedmodel=args.savedmodel,
            model_config=model_config,
            model_base_path=args.model_base_path,
            cache_config=cache_config,
            overload_config=overload_config,
            utilization_config=utilization_config,
            quality_config=quality_config,
            lifecycle_config=lifecycle_config,
            batching_config=batching_config,
            transport_config=transport_config,
            recovery_config=recovery_config,
            mesh_config=mesh_config,
            elastic_config=elastic_config,
            cascade_config=cascade_config,
            integrity_config=integrity_config,
        )
    except BaseException:
        # A load or warm-up that fails must not leave a listening server
        # (its threads would keep the process alive).
        if "server" in transport:
            transport["server"].stop(0)
        raise
    server, port = transport["server"], transport["port"]
    impl.compile_cache = compile_cache
    impl.startup["native_build_s"] = round(backend_t0 - native_t0, 3)
    impl.startup["backend_init_s"] = round(backend_init_s, 3)
    if impl.lifecycle is not None:
        # The CLI server drives the controller with its background thread
        # (ticks + the fine-tune publisher cadence); embedded callers and
        # tests drive tick() themselves.
        impl.lifecycle.start()
    if impl.recovery is not None:
        # Watchdog thread: escalates the batcher's wedge clock into a
        # quarantine decision on its poll cadence; failure-triggered
        # cycles wake it early.
        impl.recovery.start()
    # ONE teardown path for every exit: SIGTERM, REST-startup failure, and
    # normal termination all drain through this (admissions refused, queued
    # + in-flight work answered up to [overload] drain_grace_s, transport
    # stopped with the remaining grace).
    shutdown = GracefulShutdown(
        impl, batcher,
        grace_s=overload_config.drain_grace_s,
        watcher=watcher,
        lifecycle=impl.lifecycle,
        recovery=impl.recovery,
    )
    request_logger = None
    if cfg.request_log_file:
        from .request_log import RequestLogger

        request_logger = RequestLogger(
            cfg.request_log_file, sampling_rate=cfg.request_log_sampling
        )
        impl.request_logger = request_logger
        shutdown.request_logger = request_logger
        log.info("request logging to %s (sampling %.4f)",
                 cfg.request_log_file, cfg.request_log_sampling)
    if obs.apply() is not None:
        log.info(
            "per-request tracing on (buffer=%d sample_rate=%.3f slowest_n=%d)"
            " — GET /tracez on the REST surface",
            obs.trace_buffer, obs.trace_sample_rate, obs.trace_slowest_n,
        )
    # The server has listened since before the load (`listen` above); health
    # has answered SERVING since build_stack returned.
    impl.startup["to_serving_s"] = round(time.perf_counter() - serve_t0, 3)
    if transport_config.uds_path:
        log.info("gRPC also on unix:%s (co-located transport)",
                 transport_config.uds_path)
    shutdown.server = server
    # SIGTERM = drain: health NOT_SERVING, new admissions refused
    # UNAVAILABLE("draining"), accepted work answered up to the grace.
    shutdown.install_signal_handler()
    if fleet_config.enabled:
        from ..fleet import gossip as fleet_gossip
        from ..fleet.replica import ReplicaFleetPlane

        # The gossip id defaults to this replica's serving address — the
        # SAME string the router lists in its [client] hosts, so a gossip
        # record steers the router's scoreboard without any id mapping.
        fleet_self_id = fleet_config.self_id or f"{cfg.host}:{port}"

        def _fleet_record() -> dict:
            # Published every gossip interval: cheap reads only.
            if impl.draining:
                state = fleet_gossip.DRAINING
            elif impl.recovery is not None and impl.recovery.not_serving():
                state = fleet_gossip.QUARANTINED
            elif not (impl.warmup_complete and registry.models()):
                state = fleet_gossip.STARTING
            else:
                state = fleet_gossip.SERVING
            rec = {
                "state": state,
                "versions": tuple(registry.models().get(cfg.model_name, ())),
            }
            ov = impl.overload_stats()
            if ov:
                rec["pressure"] = str(ov.get("state") or "")
            if impl.integrity is not None:
                # Integrity verdict (ISSUE 20): suspect rides every
                # gossip record so routers steer around a replica whose
                # shadow verification caught its device miscomputing —
                # cleared (and re-gossiped False) after the configured
                # number of clean shadow passes.
                rec["suspect"] = bool(impl.integrity.suspect)
            if impl.lifecycle is not None:
                rec.update(impl.lifecycle.fleet_record())
            # Observability digest (ISSUE 18): qps/latency summary +
            # scrape address piggybacked on every gossip record, so the
            # router's fleet aggregate degrades to these numbers instead
            # of dropping this member when the /monitoring scrape fails.
            plane = impl.fleet
            rec["obs"] = {
                **metrics.fleet_summary(),
                "addr": plane.agent.listen_addr if plane is not None else "",
                "trace_export": bool(obs.tracing and obs.trace_export),
            }
            return rec

        def _trace_export_route(query: dict) -> dict:
            # GET /tracez/export?since=CURSOR on the gossip port: kept
            # span trees for the router's TraceCollector. Gated on the
            # [observability] trace_export knob (off by default).
            if not (obs.tracing and obs.trace_export) or not tracing.enabled():
                return {"enabled": False, "cursor": 0, "spans": []}
            try:
                since = int(query.get("since", 0) or 0)
            except (TypeError, ValueError):
                since = 0
            return tracing.recorder().export_since(since)

        fleet_plane = ReplicaFleetPlane(
            dataclasses.replace(fleet_config, self_id=fleet_self_id),
            record_fn=_fleet_record,
            lifecycle=impl.lifecycle,
            extra_routes={"/monitoring": metrics.fleet_wire},
            query_routes={"/tracez/export": _trace_export_route},
        )
        impl.fleet = fleet_plane
        shutdown.fleet = fleet_plane
        fleet_plane.start()
        log.info(
            "fleet plane up (id=%s gossip=%s peers=%d rollout_follow=%s)",
            fleet_self_id, fleet_plane.agent.listen_addr,
            len(fleet_config.peers), impl.lifecycle is not None,
        )
    if credentials is not None:
        log.info("gRPC port is TLS-secured (--ssl-config-file)")
    if args.rest_port:
        try:
            bound = start_rest_in_thread(impl, cfg.host, args.rest_port, metrics)
        except RuntimeError as exc:
            shutdown.shutdown()
            raise SystemExit(str(exc)) from exc
        log.info("REST gateway on %s:%d (/v1/models/...)", cfg.host, bound)
    log.info(
        "PredictionService on %s:%d (listeners=%d model=%s kind=%s mesh=%s devices=%s)",
        cfg.host, port, len(server.servers),
        servable.name if servable else "<awaiting versions>",
        cfg.model_kind, dict(mesh.shape) if mesh else None, jax.devices(),
    )
    try:
        if args.metrics_every_s > 0:
            # grpc's wait_for_termination(timeout) returns True when the
            # timeout elapsed with the server still live, False once it
            # terminates — periodic logging AND termination detection in one
            # loop (verified against grpcio 1.76 behavior).
            while server.wait_for_termination(timeout=args.metrics_every_s):
                snap = metrics.snapshot(batcher.stats)
                snap["phases"] = request_trace.snapshot()
                log.info("metrics %s", json.dumps(snap))
        else:
            server.wait_for_termination()
    finally:
        log.info("shutting down")
        # Same drain path as SIGTERM (no-op if the signal already ran it:
        # shutdown() is idempotent and blocks until the first run finishes).
        shutdown.shutdown()


if __name__ == "__main__":
    serve()
