"""PredictionService semantics, transport-free.

Implements the server-side contract the reference reaches only through the
external tensorflow_model_server (SURVEY.md §3.5): ModelSpec resolution with
latest-version default (model.proto:12-14), signature lookup, input
validation against the signature, output_filter selection
(predict.proto:23-30), and the Classify/Regress/MultiInference Example path.
The gRPC layer (server.py) is a thin adapter over this class, so the same
logic is testable without sockets and reusable from an in-process client.

Error codes (per-RPC status codes — the failure-detection obligation from
SURVEY.md §5): unknown model/version -> NOT_FOUND; malformed tensors,
signature mismatches, bad Examples -> INVALID_ARGUMENT; oversized batches ->
RESOURCE_EXHAUSTED (wired to codes in server.py via ServiceError.code).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from .. import codec, faults
from ..utils import tracing
from ..utils.tracing import request_trace
from ..models.registry import (
    ModelNotFoundError,
    Servable,
    ServableRegistry,
    Signature,
    SignatureNotFoundError,
    VersionNotFoundError,
)
from ..proto import serving_apis_pb2 as apis
from ..proto import tf_framework_pb2 as fw
from . import cascade as cascade_mod
from .batcher import (
    SERVED_KERNELS,
    BatchTooLargeError,
    DeviceWedgedError,
    DynamicBatcher,
    PoisonedInputError,
    QueueOverloadError,
    RequestDeadlineError,
)
from .example_codec import ExampleDecodeError, decode_input
from .integrity import IntegrityScreenError

SIGNATURE_DEF_FIELD = "signature_def"


class ServiceError(Exception):
    """Carries a grpc-compatible status code name ('NOT_FOUND', ...).
    `retry_after_ms`, when set (overload-plane refusals), is the pushback
    hint the transport adapters forward in trailing metadata (gRPC) or
    the Retry-After header (REST)."""

    def __init__(self, code: str, message: str,
                 retry_after_ms: int | None = None):
        super().__init__(message)
        self.code = code
        self.retry_after_ms = retry_after_ms


def _wrap_lookup(fn):
    try:
        return fn()
    except (ModelNotFoundError, VersionNotFoundError, SignatureNotFoundError) as e:
        raise ServiceError("NOT_FOUND", str(e)) from e


class PredictionServiceImpl:
    """Registry + batcher -> the five PredictionService RPCs."""

    def __init__(self, registry: ServableRegistry, batcher: DynamicBatcher):
        self.registry = registry
        self.batcher = batcher
        # Flipped by build_stack around its load+warmup phase; the
        # grpc.health.v1 servicer reports the overall server NOT_SERVING
        # while False. Default True: directly-constructed impls (tests,
        # in-process embedding) are serving the moment they exist.
        self.warmup_complete = True
        # Optional sampled PredictionLog writer (serving/request_log.py);
        # assign a RequestLogger to enable — both transports and all four
        # RPC families flow through these entry points.
        self.request_logger = None
        # Optional runtime model-list reconciler (server.ModelLifecycle,
        # set by --model-config-file deployments): when present,
        # HandleReloadConfigRequest carries upstream's FULL semantics —
        # the supplied model list replaces the served set.
        self.model_lifecycle = None
        # name -> (base_path, model_kind) for single-model watcher mode:
        # lets label-only reloads accept a config that re-states the
        # CURRENT source (deploy tools replay their full config) while
        # rejecting an actual move this mode cannot honor.
        self.served_sources: dict[str, tuple[str, str]] = {}
        # Graceful drain (serving/server.py GracefulShutdown): True once a
        # SIGTERM/shutdown started — new inference admissions are refused
        # with UNAVAILABLE "draining" while queued + in-flight work
        # completes, and the grpc.health.v1 servicer reports NOT_SERVING.
        self.draining = False
        # Continuous-freshness lifecycle plane (serving/lifecycle.py):
        # when a LifecycleController is set, DEFAULT version resolution
        # of its model consults the canary router (requests pinning a
        # version or label are never touched). None (default) costs one
        # attribute read per resolution.
        self.lifecycle = None
        # The single-model version watcher, when one owns this impl's
        # model (build_stack sets it): the /monitoring `versions` block
        # reads loaded/on-disk/blacklist/pin state from it — present
        # whether or not the lifecycle controller is armed.
        self.version_watcher = None
        # Device-failure recovery plane (serving/recovery.py): when a
        # RecoveryController is set, the grpc.health.v1 servicer reports
        # NOT_SERVING through its quarantine/reinit/replay cycle and
        # GET /recoveryz serves its snapshot. None (default) costs one
        # attribute read where consulted.
        self.recovery = None
        # Mesh serving mode (ISSUE 13): the ShardedExecutor installed as
        # the batcher's run_fn, when serving spans a device mesh.
        # /monitoring's `mesh` block and the dts_tpu_mesh_* Prometheus
        # series read its snapshot; None (default) = single-chip.
        self.mesh_executor = None
        # Elastic mesh serving (ISSUE 15): the ElasticController driving
        # runtime split switches, when [elastic] armed the ladder. The
        # `elastic` /monitoring section and dts_tpu_elastic_* Prometheus
        # series read through it; None (default) = static split (or no
        # mesh at all).
        self.elastic = None
        # Multi-stage ranking cascade (serving/cascade.py, ISSUE 19):
        # when a CascadeOrchestrator is set, score-only-filtered Predicts
        # big enough to prune run retrieval->rank in one RPC — stage-1
        # prune on device, full model over the survivors, provenance in
        # the response. None (default) costs one attribute read per
        # Predict.
        self.cascade = None
        # Fleet robustness plane (fleet/replica.py, ISSUE 17): the
        # ReplicaFleetPlane (gossip membership + rollout follower) when
        # [fleet] armed it. GET /fleetz and the dts_tpu_fleet_*
        # Prometheus series read through it; None (default) costs one
        # attribute read where consulted.
        self.fleet = None
        # Data-integrity plane (serving/integrity.py, ISSUE 20): when an
        # IntegrityPlane is set (build_stack attaches the same object to
        # the batcher), x-dts-input-crc request stamps are verified at
        # decode (mismatch fails ONLY that request, INVALID_ARGUMENT with
        # a corrupt-wire detail), responses are stamped with
        # x-dts-score-crc trailing metadata, and GET /integrityz serves
        # its snapshot. None (default) costs one attribute read per hook.
        self.integrity = None
        # Streamed sub-batch results (ISSUE 9): default server-side split
        # size (candidates per sub-batch) for PredictStream. 0 = no split
        # (one chunk per request — streaming stays wire-available but the
        # behavior change is off); a request may override via the
        # x-dts-stream-chunk metadata the transport adapters thread in.
        self.stream_chunk_candidates = 0
        # Reusable encode scratch ([transport] response_arena): when True,
        # response encodes run through a per-thread codec.EncodeArena
        # (contiguity/widen copies and the Example decoder's dense batch
        # reuse one backing allocation) and each PredictStream reuses ONE
        # chunk message. Off by default = historical allocate-per-call.
        self.response_arena = False
        self._arenas = threading.local()
        # Start-up facts for /monitoring's `runtime` block: build_stack
        # records the load-time compile wall (the ladder's warm-up);
        # serve() attaches the persistent-compile-cache counter
        # (utils/runtime.py CompileCacheStats). None = not recorded.
        self.warmup_s: float | None = None
        self.compile_cache = None
        # Start-up as stamps, in seconds, for the runtime block's `startup`
        # (which adds `warmup_s` above): build_stack writes `params_init_s`,
        # serve() `backend_init_s`, `native_build_s` and `to_serving_s`, and
        # `listeners`: {"k": the gRPC listeners on its port, "cores": the
        # cores k was derived from}. A stamp nobody took (an embedded stack
        # has no serve()) is absent.
        self.startup: dict = {}

    def _arena(self):
        """The calling thread's EncodeArena, or None when the plane is
        off. Per-thread: arenas are single-owner scratch by design."""
        if not self.response_arena:
            return None
        arena = getattr(self._arenas, "arena", None)
        if arena is None:
            arena = self._arenas.arena = codec.EncodeArena()
        return arena

    def pipeline_stats(self) -> dict | None:
        """Continuous-batching pipeline snapshot (configured depth /
        in-flight window, live per-bucket occupancy, overlap fraction) —
        the `pipeline` block in /monitoring and the dts_tpu_pipeline_*
        Prometheus series. Always available: this is core batcher state,
        not a gated plane."""
        fn = getattr(self.batcher, "pipeline_stats", None)
        return fn() if callable(fn) else None

    def runtime_stats(self) -> dict:
        """What this process runs on, as jax reports it — platform,
        device_kind, device count, library versions — plus the load-time
        compile wall, the start-up's stamps (`startup`, with each loaded
        servable's embedding rows a candidate row, `lookups_per_row`, the
        `bags` they pool to, a sequence family's `layer_plan`, a routed family's `expert_plan`, the `attention_plan` of one whose attention differs by layer, its `params_bytes`, the `upload_format` of its batches and the
        `assembler` that builds them: "native" or "generic: <why>", and the `gather` of its
        embedding rows as traced: the Pallas kernel or XLA's, `models/embeddings.py`, and the `products` of a sequence family's entries: the operations in pieces against a weight and those whose pieces meet in one product, `models/sequence.py`), the pack factor of
        each loaded servable's embedding table (`embedding_pack`), persistent-cache
        traffic and whether the native host ops are loaded: the `runtime`
        block in /monitoring. jax falls back
        to the CPU with only a warning when it finds no accelerator; this
        block is where an operator (and chip_smoke.py) sees that."""
        from .. import native
        from ..utils.runtime import describe_devices

        block = describe_devices()
        block["warmup_s"] = self.warmup_s
        upload_formats = getattr(self.batcher, "upload_formats", None)
        assemblers = getattr(self.batcher, "assemblers", None)
        kernel_stamps = getattr(self.batcher, "kernel_stamps", None)
        products = getattr(self.batcher, "products", None)
        block["startup"] = {
            **self.startup,
            "warmup_s": self.warmup_s,
            "lookups_per_row": self.registry.per_servable("lookups_per_row"),
            "bags": self.registry.per_servable("bags"),
            "layer_plan": self.registry.per_servable("layer_plan"),
            "expert_plan": self.registry.per_servable("expert_plan"),
            "attention_plan": self.registry.per_servable("attention_plan"),
            "params_bytes": self.registry.per_servable("params_bytes"),
            "upload_format": upload_formats() if callable(upload_formats) else {},
            "assembler": assemblers() if callable(assemblers) else {},
            **(kernel_stamps() if callable(kernel_stamps)
               else {k.stamp: {} for k in SERVED_KERNELS}),
            "products": products() if callable(products) else {},
        }
        block["embedding_pack"] = self.registry.per_servable("embedding_pack")
        block["compile_cache"] = (
            self.compile_cache.snapshot()
            if self.compile_cache is not None else None
        )
        block["native_hostops"] = native.available()
        return block

    def _log_request(self, kind: str, request) -> None:
        if self.request_logger is not None:
            self.request_logger.maybe_log(kind, request)

    # ----------------------------------------------------------- cache plane

    def cache_stats(self) -> dict | None:
        """Cache-plane snapshot (per-model hit/miss/coalesced/eviction
        counters, occupancy, config) — the body of GET /cachez and the
        `cache` block in /monitoring. None when no score cache is armed,
        so both surfaces can distinguish "disabled" from "cold"."""
        cache = getattr(self.batcher, "score_cache", None)
        return cache.snapshot() if cache is not None else None

    def row_cache_stats(self) -> dict | None:
        """Row-granular cache snapshot (per-row hit/miss/coalesced
        counters, rows_executed vs rows_requested, occupancy) — the
        `row_cache` block in GET /cachez and /monitoring and the
        dts_tpu_cache_row_* Prometheus series. None when no row cache is
        armed ([cache] row_granular=false)."""
        rc = getattr(self.batcher, "row_cache", None)
        if rc is None:
            return None
        snap = rc.snapshot()
        stats = getattr(self.batcher, "stats", None)
        if stats is not None:
            snap["batcher"] = {
                "row_batches": stats.row_batches,
                "rows_requested": stats.rows_requested,
                "rows_executed": stats.rows_executed,
                "row_full_hit_batches": stats.row_full_hit_batches,
            }
        return snap

    def cache_flush(self, model: str | None = None) -> int:
        """Operator flush control: drop every cached score (or one
        model's), generation-bumped so in-flight fills of the flushed
        entries die too — the row-granular tier flushes with the request
        tier (one operator surface, both stores). Returns the total
        number of entries dropped."""
        cache = getattr(self.batcher, "score_cache", None)
        row_cache = getattr(self.batcher, "row_cache", None)
        if cache is None and row_cache is None:
            raise ServiceError(
                "FAILED_PRECONDITION",
                "no score cache is configured ([cache] enabled=false)",
            )
        dropped = cache.flush(model) if cache is not None else 0
        if row_cache is not None:
            dropped += row_cache.flush(model)
        return dropped

    def overload_stats(self) -> dict | None:
        """Overload-plane snapshot (adaptive limit, pressure state, shed /
        doomed / brownout counters) — the `overload` block in /monitoring
        and the dts_tpu_overload_* Prometheus series. None when no
        controller is armed ([overload] enabled=false)."""
        ctrl = getattr(self.batcher, "overload", None)
        return ctrl.snapshot() if ctrl is not None else None

    def utilization_stats(self, window_s: float | None = None) -> dict | None:
        """Utilization-plane snapshot (occupancy ledger + gap waterfall +
        live achieved_fraction_of_device_limit) — the body of GET /utilz,
        the `utilization` block in /monitoring, and the
        dts_tpu_utilization_* Prometheus series. None when no ledger is
        armed ([utilization] enabled=false)."""
        ledger = getattr(self.batcher, "utilization", None)
        return ledger.snapshot(window_s) if ledger is not None else None

    def quality_stats(
        self, model: str | None = None, version: int | None = None
    ) -> dict | None:
        """Quality-plane snapshot (per-(model, version) score sketches,
        PSI/JS drift vs reference and between live versions, label-join
        AUC/calibration, exemplar counters) — the body of GET /qualityz,
        the `quality` block in /monitoring, and the dts_tpu_quality_*
        Prometheus series. None when no monitor is armed ([quality]
        enabled=false)."""
        monitor = getattr(self.batcher, "quality", None)
        if monitor is None:
            return None
        return monitor.snapshot(model=model, version=version)

    def quality_ingest_labels(self, items) -> dict:
        """Label-feedback ingest (POST /labelz): join (id, label, ts)
        records onto the score reservoir. Raises FAILED_PRECONDITION when
        the plane is off, INVALID_ARGUMENT on malformed items."""
        monitor = getattr(self.batcher, "quality", None)
        if monitor is None:
            raise ServiceError(
                "FAILED_PRECONDITION",
                "no quality monitor is configured ([quality] enabled=false)",
            )
        try:
            return monitor.ingest_labels(items)
        except (TypeError, ValueError) as e:
            raise ServiceError("INVALID_ARGUMENT", str(e)) from e

    def quality_pin_reference(self) -> dict:
        """Pin the current windowed score distributions as the drift
        reference (POST /qualityz/snapshot) and persist the artifact when
        a reference_file is configured."""
        monitor = getattr(self.batcher, "quality", None)
        if monitor is None:
            raise ServiceError(
                "FAILED_PRECONDITION",
                "no quality monitor is configured ([quality] enabled=false)",
            )
        return monitor.pin_reference()

    def lifecycle_stats(self) -> dict | None:
        """Lifecycle-plane snapshot (state machine, canary routing
        fractions/counters, publish/promote/rollback history, watcher
        blacklist/pin state) — the body of GET /lifecyclez, the
        `lifecycle` block in /monitoring, and the dts_tpu_lifecycle_*
        Prometheus series. None when no controller is armed ([lifecycle]
        enabled=false)."""
        lc = self.lifecycle
        return lc.snapshot() if lc is not None else None

    def recovery_stats(self) -> dict | None:
        """Recovery-plane snapshot (state machine, quarantine/replay/
        bisection counters, last-cycle MTTR evidence) — the body of
        GET /recoveryz, the `recovery` block in /monitoring, and the
        dts_tpu_recovery_* Prometheus series. None when no controller is
        armed ([recovery] enabled=false)."""
        rec = self.recovery
        return rec.snapshot() if rec is not None else None

    def cascade_stats(self) -> dict | None:
        """Cascade-plane snapshot (per-stage latency totals, pruned/
        survivor/fallback counters, observed survivor fraction, survivor
        bucket histogram) — the body of GET /cascadez, the `cascade`
        block in /monitoring, and the dts_tpu_cascade_* Prometheus
        series. None when the plane is off ([cascade] enabled=false)."""
        casc = self.cascade
        return casc.snapshot() if casc is not None else None

    def fleet_stats(self) -> dict | None:
        """Fleet-plane snapshot (gossip membership view + exchange
        counters, rollout-follower state) — the body of GET /fleetz, the
        `fleet` block in /monitoring, and the dts_tpu_fleet_* Prometheus
        series. None when the plane is off ([fleet] enabled=false)."""
        fl = self.fleet
        return fl.fleet_stats() if fl is not None else None

    def integrity_stats(self) -> dict | None:
        """Integrity-plane snapshot (wire verify/reject counters, screen
        trips + window state, shadow batches/mismatches/audits, suspect
        verdict, escalations, bounded event history) — the body of
        GET /integrityz, the `integrity` block in /monitoring, and the
        dts_tpu_integrity_* Prometheus series. None when the plane is
        off ([integrity] enabled=false)."""
        integ = self.integrity
        return integ.snapshot() if integ is not None else None

    def response_crc_sidecar(self, resp) -> str | None:
        """The x-dts-score-crc trailing-metadata value for one encoded
        PredictResponse, or None when the plane (or its wire layer) is
        off. Called by the transport adapters after the handler returns —
        the stamp covers the exact tensors that ride the wire."""
        integ = self.integrity
        if integ is None or not integ.config.wire_checksums:
            return None
        return integ.response_sidecar(resp.outputs)

    def mesh_stats(self, utilization: dict | None = None) -> dict | None:
        """Mesh-mode snapshot (mesh geometry + device list, executor
        batch/pad counters, layout source per served model, per-device
        occupancy attribution when the utilization plane rides along) —
        the `mesh` block in /monitoring and the dts_tpu_mesh_*
        Prometheus series. None when serving is single-chip.

        `utilization` (an already-computed utilization_stats() snapshot)
        avoids recomputing the ledger's O(ring log ring) waterfall merge
        when the caller renders both blocks in one pass (the Prometheus
        scrape and the full /monitoring snapshot do)."""
        ex = self.mesh_executor
        if ex is None:
            return None
        snap = ex.snapshot()
        ledger = getattr(self.batcher, "utilization", None)
        if ledger is not None:
            # The per-device attribution has ONE implementation — the
            # ledger's own snapshot (OccupancyLedger.devices +
            # per_device) — lifted here, never rebuilt: two copies of
            # the spmd_uniform math would drift. An embedded ledger that
            # was never device-labeled (build_stack labels it; direct
            # construction may not) adopts the mesh's device list first
            # (idempotent), which forces one fresh snapshot.
            try:
                usnap = utilization
                if getattr(ledger, "devices", None) is None:
                    ledger.devices = list(snap["devices"])
                    usnap = None  # pre-label snapshot lacks per_device
                if usnap is None:
                    usnap = ledger.snapshot()
                if usnap.get("per_device") is not None:
                    snap["per_device"] = usnap["per_device"]
                    snap["occupancy_attribution"] = usnap.get(
                        "occupancy_attribution", "spmd_uniform"
                    )
            except Exception:  # noqa: BLE001 — telemetry, never a dependency
                pass
        return snap

    def elastic_stats(self, mesh: dict | None = None) -> dict | None:
        """Elastic-plane snapshot (current split, ladder, per-split serve
        counters + live in-flight, switch history ring, controller
        decision state) — the `elastic` /monitoring section and the
        dts_tpu_elastic_* Prometheus series. None when the plane is off
        ([elastic] enabled=false). The same block also rides inside
        mesh_stats()//meshz as snapshot()['elastic']; `mesh` (an
        already-computed mesh_stats() snapshot) lifts it from there
        instead of re-walking the executor locks + history ring when the
        caller renders both blocks in one pass (the Prometheus scrape
        and the full /monitoring snapshot do — the mesh_stats
        (utilization=) precedent)."""
        ctrl = self.elastic
        if ctrl is None:
            return None
        if mesh is not None and mesh.get("elastic") is not None:
            return mesh["elastic"]
        return ctrl.executor.elastic_snapshot()

    def versions_stats(self) -> dict | None:
        """Version-watcher snapshot (loaded versions, last reconcile
        pass's on-disk-ready view, blacklist/pin sets, failed load
        attempts) — the /monitoring `versions` block. Available whenever
        a single-model watcher owns this impl's model, lifecycle armed
        or not (the blacklist/pin API is operator-callable on its own)."""
        watcher = self.version_watcher
        return watcher.snapshot() if watcher is not None else None

    def lifecycle_route(
        self, name: str, version, label, criticality: str | None
    ) -> int | None:
        """Canary-admission version override for one request, or None.
        Only DEFAULT resolutions of the lifecycle's own model are routed
        — an explicit version or label pin is the client's choice and
        the rollout must never second-guess it."""
        lc = self.lifecycle
        if lc is None or version is not None or label is not None \
                or name != lc.model:
            return None
        return lc.route(criticality)

    def _refuse_if_draining(self) -> None:
        """Drain-aware admission gate: once shutdown started, new
        inference work is refused (UNAVAILABLE, so fan-out clients reroute
        to another backend) while already-accepted work completes."""
        if self.draining:
            raise ServiceError(
                "UNAVAILABLE",
                "server is draining (shutdown in progress); retry against "
                "another backend",
            )
        if not self.warmup_complete:
            # serve() listens before the load and the warm-up (whose direct
            # executions must not race live batches): health NOT_SERVING.
            raise ServiceError(
                "UNAVAILABLE",
                "server is loading and warming up; retry against another "
                "backend",
            )

    def is_configured(self, name: str) -> bool:
        """True when this server is CONFIGURED to serve `name` (a watcher
        or lifecycle owns it), whether or not a version is ready yet — the
        one definition shared by GetModelStatus (START vs NOT_FOUND) and
        the grpc.health.v1 servicer (NOT_SERVING vs NOT_FOUND)."""
        lifecycle = self.model_lifecycle
        return name in self.served_sources or (
            lifecycle is not None
            and name in getattr(lifecycle, "configured_models", lambda: ())()
        )

    # ------------------------------------------------------------ resolution

    @staticmethod
    def _version_choice(model_spec: apis.ModelSpec) -> tuple[int | None, str | None]:
        """(version, label) from a ModelSpec, enforcing the upstream oneof:
        the real model.proto wraps version/version_label in oneof
        version_choice, so setting both is a client error there — here the
        vendored proto (reference parity) has no oneof, and the server
        enforces the exclusivity instead."""
        version = model_spec.version.value if model_spec.HasField("version") else None
        label = model_spec.version_label or None
        if version is not None and label is not None:
            raise ServiceError(
                "INVALID_ARGUMENT",
                "model_spec sets both version and version_label; they are a "
                "oneof upstream — choose one",
            )
        return version, label

    def _resolve(
        self, model_spec: apis.ModelSpec, criticality: str | None = None
    ) -> tuple[Servable, Signature]:
        if not model_spec.name:
            raise ServiceError("INVALID_ARGUMENT", "model_spec.name is required")
        version, label = self._version_choice(model_spec)
        routed = self.lifecycle_route(
            model_spec.name, version, label, criticality
        )
        if routed is not None:
            try:
                servable = self.registry.resolve(model_spec.name, routed)
            except (ModelNotFoundError, VersionNotFoundError):
                # The routed version vanished mid-swap (rollback racing
                # this request): fall back to the latest-version default
                # — a rollout action must never FAIL live traffic.
                servable = _wrap_lookup(
                    lambda: self.registry.resolve(model_spec.name)
                )
            span = tracing.current_span()
            if span is not None:
                span.attrs["lifecycle_version"] = servable.version
        else:
            servable = _wrap_lookup(
                lambda: self.registry.resolve(model_spec.name, version, label)
            )
        signature = _wrap_lookup(lambda: servable.signature(model_spec.signature_name))
        return servable, signature

    def _echo_spec(self, servable: Servable, signature_name: str) -> apis.ModelSpec:
        spec = apis.ModelSpec(name=servable.name, signature_name=signature_name)
        spec.version.value = servable.version
        return spec

    # --------------------------------------------------------------- Predict

    def _decode_and_validate(
        self, servable: Servable, signature: Signature, inputs
    ) -> dict[str, np.ndarray]:
        arrays: dict[str, np.ndarray] = {}
        specs = signature.input_specs
        for key in inputs:
            if key not in specs:
                raise ServiceError(
                    "INVALID_ARGUMENT",
                    f"unexpected input {key!r}; signature expects {sorted(specs)}",
                )
        n = None
        for name, spec in specs.items():
            if name not in inputs:
                if name == "dense_features":
                    continue  # optional (DLRM serves the 2-input contract too)
                raise ServiceError("INVALID_ARGUMENT", f"missing required input {name!r}")
            try:
                arr = codec.to_ndarray(inputs[name])
            except codec.CodecError as e:
                raise ServiceError("INVALID_ARGUMENT", f"input {name!r}: {e}") from e
            if arr.dtype != codec.dtype_to_numpy(spec.dtype):
                # Compact-wire widening: the transport is >half the single-
                # core request budget (round-4 echo floor: ~1.7 ms/MB), so
                # clients may pre-apply the SERVER's own first transforms
                # and ship the result: int32 ids already folded into the
                # vocab (the host fold is exact mod, models re-fold
                # idempotently) and bf16 weights (the models' compute-dtype
                # cast, round-to-nearest-even either side). Scores are
                # bit-identical to the wide encoding; anything else stays a
                # hard INVALID_ARGUMENT.
                # Widening is accepted ONLY where it re-states a transform
                # the server itself performs on this model, so equivalence
                # is structural, not hoped-for: int32 ids only where the
                # host fold runs (folds_ids_on_host — graph-executor models
                # consume raw int64), bf16 only for the weights input of a
                # model that consumes weights through its bf16 compute-
                # dtype cast (wide_deep/deepfm's f32 sparse-linear term and
                # DLRM's dense_features must arrive f32).
                model = servable.model
                widened = (
                    spec.dtype == fw.DataType.DT_INT64
                    and arr.dtype == np.int32
                    and name == "feat_ids"
                    and model.folds_ids_on_host
                ) or (
                    spec.dtype == fw.DataType.DT_FLOAT
                    and arr.dtype == codec.dtype_to_numpy(fw.DataType.DT_BFLOAT16)
                    and name == "feat_wts"
                    and model.wts_in_compute_dtype
                    and model.config.compute_dtype == "bfloat16"
                )
                if not widened:
                    raise ServiceError(
                        "INVALID_ARGUMENT",
                        f"input {name!r}: dtype {arr.dtype} != signature "
                        f"{fw.DataType.Name(spec.dtype)}",
                    )
                if name == "feat_ids" and arr.size:
                    # int32 ids ride the u24 transfer pack, which truncates
                    # to 3 LE bytes — an unfolded or NEGATIVE id would
                    # corrupt lookups before the device's re-fold could
                    # save it (-1 packs to 0xFFFFFF, a wrong-but-valid
                    # row). The compact contract is pre-folded ids in
                    # [0, vocab); enforce both ends (~60 us min+max pass).
                    lo, hi = int(arr.min()), int(arr.max())
                    if lo < 0 or hi >= model.config.vocab_size:
                        raise ServiceError(
                            "INVALID_ARGUMENT",
                            f"input {name!r}: int32 compact ids must be "
                            f"pre-folded into [0, "
                            f"{model.config.vocab_size}) (got range "
                            f"[{lo}, {hi}])",
                        )
            if spec.shape is None:
                # Unknown-rank signature (imported SavedModels): any shape
                # passes EXCEPT rank 0 — batching needs a candidate dim.
                if arr.ndim == 0:
                    raise ServiceError(
                        "INVALID_ARGUMENT",
                        f"input {name!r}: scalar tensor has no candidate dimension",
                    )
            elif (
                arr.ndim != len(spec.shape)
                or any(s is not None and s != d for s, d in zip(spec.shape, arr.shape))
            ):
                raise ServiceError(
                    "INVALID_ARGUMENT",
                    f"input {name!r}: shape {arr.shape} incompatible with signature "
                    f"{spec.shape}",
                )
            if n is None:
                n = arr.shape[0]
            elif arr.shape[0] != n:
                raise ServiceError(
                    "INVALID_ARGUMENT",
                    f"inconsistent candidate counts: {name!r} has {arr.shape[0]}, "
                    f"expected {n}",
                )
            arrays[name] = arr
        if n == 0:
            raise ServiceError("INVALID_ARGUMENT", "empty candidate batch")
        return arrays

    # Bounded wait: a wedged batcher must not permanently consume an RPC
    # handler thread or a REST request's task (first compile of a large
    # bucket through a remote-compile path can legitimately take tens of
    # seconds).
    _BATCH_DEADLINE_S = 120.0

    @staticmethod
    def _translate_batcher_error(exc: Exception, fut) -> ServiceError:
        """ONE mapping from batcher failures to RPC status for both the
        threaded (_run) and coroutine (_run_async) paths — they must never
        return different codes for the same failure. Re-raises anything
        that is not a batcher failure."""
        if isinstance(exc, PoisonedInputError):
            # Recovery-plane bisection verdict: this request's bytes
            # deterministically kill the device executor — a DISTINCT,
            # non-retryable status (its batchmates were re-dispatched and
            # answered normally). Without this branch the ValueError
            # would re-raise and surface as INTERNAL.
            return ServiceError("INVALID_ARGUMENT", str(exc))
        if isinstance(exc, (BatchTooLargeError, QueueOverloadError)):
            # Overload-plane refusals (AdmissionRefusedError) carry a
            # retry-after-ms pushback hint; it rides the ServiceError so
            # the transport can attach it as trailing metadata.
            return ServiceError(
                "RESOURCE_EXHAUSTED", str(exc),
                retry_after_ms=getattr(exc, "retry_after_ms", None),
            )
        if isinstance(exc, DeviceWedgedError):
            return ServiceError("UNAVAILABLE", str(exc))
        if isinstance(exc, IntegrityScreenError):
            # Readback screen verdict (ISSUE 20): this request's score
            # rows came back NaN/Inf/implausible — retryable elsewhere
            # (a resilient client fails over), and per-row by design:
            # its batchmates delivered normally.
            return ServiceError("UNAVAILABLE", str(exc))
        if isinstance(exc, RequestDeadlineError):
            # The batcher shed the queued item itself (propagated client
            # deadline): the future already failed, nothing to withdraw.
            return ServiceError("DEADLINE_EXCEEDED", str(exc))
        if isinstance(exc, faults.InjectedFaultError):
            # Chaos at a batcher site (batcher.dispatch / readback) keeps
            # its injected status code instead of collapsing into the
            # RuntimeError->UNAVAILABLE catch-all below.
            return ServiceError(exc.code_name, str(exc))
        # Explicit tuple, not bare TimeoutError: asyncio.TimeoutError and
        # concurrent.futures.TimeoutError are aliases of the builtin only on
        # Python >= 3.11; on 3.10 a batcher deadline would surface as
        # INTERNAL and skip the fut.cancel() withdrawal below (round-3
        # advisor finding).
        import asyncio
        import concurrent.futures

        if isinstance(
            exc,
            (TimeoutError, asyncio.TimeoutError, concurrent.futures.TimeoutError),
        ):
            # Withdraw the work: a cancelled item is skipped by the batcher,
            # so an abandoned deadline never turns into a zombie dispatch
            # that delays everyone behind it.
            if fut is not None:
                fut.cancel()
            return ServiceError("DEADLINE_EXCEEDED", "batch execution timed out")
        if isinstance(exc, RuntimeError):
            return ServiceError("UNAVAILABLE", str(exc))
        raise exc

    @staticmethod
    def _clock_deadline(deadline_s: float | None) -> float | None:
        """Absolute give-up instant for a remaining-budget value, anchored
        at RPC ENTRY — captured before decode/validation, so pre-submit
        work spends the client's budget instead of silently extending it."""
        return None if deadline_s is None else time.perf_counter() + deadline_s

    @staticmethod
    def _budget_left(deadline_t: float | None) -> float | None:
        return None if deadline_t is None else deadline_t - time.perf_counter()

    def _effective_timeout(self, deadline_s: float | None) -> float:
        """Deadline propagation: the wait on the batcher future honors the
        CLIENT's remaining budget (context.time_remaining(), threaded down
        by the transport adapters) when it is tighter than the server's own
        wedge bound — a 2s-deadline Predict against a saturated batcher
        fails in ~2s, never the fixed 120s batch deadline. An already-
        expired deadline sheds before submit."""
        if deadline_s is None:
            return self._BATCH_DEADLINE_S
        if deadline_s <= 0:
            raise ServiceError(
                "DEADLINE_EXCEEDED", "client deadline already expired on arrival"
            )
        return min(deadline_s, self._BATCH_DEADLINE_S)

    def _run(
        self,
        servable: Servable,
        arrays: dict[str, np.ndarray],
        output_keys: tuple[str, ...] | None = None,
        deadline_s: float | None = None,
        criticality: str | None = None,
        prune_k: int = 0,
    ) -> dict[str, np.ndarray]:
        timeout = self._effective_timeout(deadline_s)
        fut = None
        try:
            # The current span (the transport adapter's server root, when
            # tracing is on) rides into the batcher so its threads can
            # attach queue/device/readback child spans per request. This
            # thread sleeps on the Future next, so it may as well cross the
            # batcher itself where the request is alone (_may_block; never
            # from _run_async, whose thread is the REST gateway's event
            # loop and carries every HTTP request).
            fut = self.batcher.submit(
                servable, arrays, output_keys=output_keys,
                deadline_s=deadline_s, span=tracing.current_span(),
                criticality=criticality, _prune_k=prune_k, _may_block=True,
            )
            out = fut.result(timeout=timeout)
            self._note_resumed(fut)
            self._consume_future_degraded(fut)
            return out
        except Exception as e:  # noqa: BLE001 — translator re-raises non-batcher
            raise self._translate_batcher_error(e, fut) from e

    async def _run_async(
        self,
        servable: Servable,
        arrays: dict[str, np.ndarray],
        output_keys: tuple[str, ...] | None = None,
        deadline_s: float | None = None,
        criticality: str | None = None,
        prune_k: int = 0,
    ) -> dict[str, np.ndarray]:
        """_run for the REST gateway's event loop (serving/rest.py, the only
        coroutine caller): the batcher Future is awaited instead of blocked
        on, so the gateway's one thread carries every in-flight HTTP
        request."""
        import asyncio

        timeout = self._effective_timeout(deadline_s)
        fut = None
        try:
            fut = self.batcher.submit(
                servable, arrays, output_keys=output_keys,
                deadline_s=deadline_s, span=tracing.current_span(),
                criticality=criticality, _prune_k=prune_k,
            )
            out = await asyncio.wait_for(
                asyncio.wrap_future(fut), timeout=timeout
            )
            self._note_resumed(fut)
            self._consume_future_degraded(fut)
            return out
        except Exception as e:  # noqa: BLE001 — translator re-raises non-batcher
            raise self._translate_batcher_error(e, fut) from e

    @staticmethod
    def _note_resumed(fut) -> None:
        """The last segment of the request timeline (batcher._Timeline):
        `req.resume`, from the completer's set_result to this handler
        running again. A future no batch resolved (a score-cache hit, a
        coalesced waiter) carries no stamp and adds nothing."""
        resolved_t = getattr(fut, "dts_resolved_t", None)
        if resolved_t is not None:
            request_trace.add("req.resume", time.perf_counter() - resolved_t)

    @staticmethod
    def _consume_future_degraded(fut) -> None:
        """Row-granular brownout stale-serve (ISSUE 14): the batcher's
        completer runs on its own threads, so it cannot set this request's
        degraded contextvar — it leaves the marker on the Future instead,
        and THIS thread (the RPC's context) forwards it so the transport
        adapters emit x-dts-degraded exactly like a whole-request stale
        serve. One getattr per request when nothing is marked."""
        degraded = getattr(fut, "dts_degraded", None)
        if degraded is not None:
            from . import overload as overload_mod

            overload_mod.mark_degraded(degraded)

    def _predict_prepare(
        self, request: apis.PredictRequest, criticality: str | None = None,
        input_crc: str | None = None,
    ):
        """Shared front half of Predict: resolution, decode/validation,
        output_filter handling. Returns (servable, arrays, out_names).
        `criticality` reaches resolution so the lifecycle plane can route
        probe-lane (then a ramp of default-lane) traffic to a canary.
        `input_crc` is the client's x-dts-input-crc stamp (transport
        metadata): verified here — BEFORE the batcher ever sees the
        request — so a corrupted request fails alone, never the
        coalesced batch it would have joined."""
        servable, signature = self._resolve(request.model_spec, criticality)
        if signature.method_name != "tensorflow/serving/predict":
            raise ServiceError(
                "INVALID_ARGUMENT",
                f"signature {request.model_spec.signature_name!r} has method "
                f"{signature.method_name!r}; use the matching RPC instead of Predict",
            )
        with request_trace.span("predict.decode"):
            try:
                # Named fault site (faults.py): decode-stage chaos surfaces
                # with its injected status code, not as INTERNAL.
                faults.fire("decode")
            except faults.InjectedFaultError as e:
                raise ServiceError(e.code_name, str(e)) from e
            arrays = self._decode_and_validate(servable, signature, request.inputs)
        integ = self.integrity
        if (
            input_crc is not None
            and integ is not None
            and integ.config.wire_checksums
        ):
            bad = integ.verify_inputs(arrays, input_crc)
            if bad:
                raise ServiceError(
                    "INVALID_ARGUMENT",
                    "corrupt-wire: input tensor bytes do not match the "
                    f"request's x-dts-input-crc stamp on {bad} — the "
                    "payload was damaged in transit; resend",
                )

        sig_outputs = signature.output_names
        if request.output_filter:
            missing = [k for k in request.output_filter if k not in sig_outputs]
            if missing:
                raise ServiceError(
                    "INVALID_ARGUMENT",
                    f"output_filter names unknown tensors {missing}; have {sig_outputs}",
                )
            # Deduplicate (order-preserving): the in-place repeated-field
            # encode APPENDS, so a duplicated filter name would otherwise
            # emit doubled float_val lists against a single-n shape.
            out_names = list(dict.fromkeys(request.output_filter))
            # A filtered request pins the batcher's output selection: the
            # jitted entry returns (and the D2H link carries) only these
            # tensors — a score-only filter is what arms top-k compaction.
            fetch_keys = tuple(out_names)
        else:
            out_names = sig_outputs
            # None = all outputs: unfiltered requests share one executable
            # variant instead of keying the jit cache on the signature's
            # output list.
            fetch_keys = None
        return servable, arrays, out_names, fetch_keys

    def predict(
        self, request: apis.PredictRequest, deadline_s: float | None = None,
        criticality: str | None = None,
        input_crc: str | None = None,
    ) -> apis.PredictResponse:
        self._refuse_if_draining()
        deadline_t = self._clock_deadline(deadline_s)
        servable, arrays, out_names, fetch_keys = self._predict_prepare(
            request, criticality, input_crc=input_crc
        )
        casc = self.cascade
        if casc is not None and casc.eligible(
            servable, fetch_keys, next(iter(arrays.values())).shape[0]
        ):
            # Multi-stage cascade (ISSUE 19): retrieval->rank in one RPC.
            # The provenance output rides the response as an extra tensor
            # beyond the signature.
            with request_trace.span("predict.execute"):
                outputs = casc.run(
                    self, servable, arrays, fetch_keys, deadline_t,
                    criticality,
                )
            out_names = [*out_names, cascade_mod.STAGE_OUTPUT]
        else:
            with request_trace.span("predict.execute"):
                outputs = self._run(
                    servable, arrays, output_keys=fetch_keys,
                    deadline_s=self._budget_left(deadline_t),
                    criticality=criticality,
                )
        resp = self._predict_finish(request, servable, out_names, outputs)
        # Log only SUCCEEDED requests: the file's contract is direct
        # usability as a warmup file, and one malformed client request
        # must never poison a future version rollout (review finding).
        self._log_request("predict", request)
        return resp

    async def predict_async(
        self, request: apis.PredictRequest, deadline_s: float | None = None,
        criticality: str | None = None,
        input_crc: str | None = None,
    ) -> apis.PredictResponse:
        """Predict for the REST gateway's event loop: identical semantics,
        awaits the batch instead of blocking a handler thread on it."""
        self._refuse_if_draining()
        deadline_t = self._clock_deadline(deadline_s)
        servable, arrays, out_names, fetch_keys = self._predict_prepare(
            request, criticality, input_crc=input_crc
        )
        casc = self.cascade
        if casc is not None and casc.eligible(
            servable, fetch_keys, next(iter(arrays.values())).shape[0]
        ):
            with request_trace.span("predict.execute"):
                outputs = await casc.run_async(
                    self, servable, arrays, fetch_keys, deadline_t,
                    criticality,
                )
            out_names = [*out_names, cascade_mod.STAGE_OUTPUT]
        else:
            with request_trace.span("predict.execute"):
                outputs = await self._run_async(
                    servable, arrays, output_keys=fetch_keys,
                    deadline_s=self._budget_left(deadline_t),
                    criticality=criticality,
                )
        resp = self._predict_finish(request, servable, out_names, outputs)
        self._log_request("predict", request)
        return resp

    def _check_produced(self, out_names, outputs) -> None:
        produced = [k for k in out_names if k in outputs]
        if len(produced) != len(out_names):
            # Signature promised tensors the model never produced — a servable
            # configuration bug, not a client error.
            raise ServiceError(
                "INTERNAL",
                f"model produced {sorted(outputs)} but signature declares "
                f"{out_names}",
            )

    @staticmethod
    def _mirror_content(request: apis.PredictRequest) -> bool:
        """Mirror the client's tensor encoding: a client that sent
        repeated fields (the grpc-java builder style, DCNClient.java:
        98-108) reads outputs via getFloatValList(), which is EMPTY if
        we reply with tensor_content — TF-Serving itself replies
        AsProtoField-style. Clients that sent tensor_content get the
        zero-copy fast path back.
        upb map iteration materializes each TensorProto wrapper, which
        is measurably slow at 500 QPS (round-3 profile: ~50 us/call);
        iterating keys and probing one field is several times cheaper,
        and any() still short-circuits on the first content-carrying
        input either way."""
        return any(
            request.inputs[name].tensor_content for name in request.inputs
        )

    def _encode_outputs(
        self, request, servable: Servable, out_names, outputs, dest,
        mirror_content: bool,
    ) -> None:
        """The ONE per-tensor response-encode loop, shared by unary
        responses and stream chunks (their wire encodings must never
        drift): the half-precision wire-dtype leak guard (custom run_fns
        returning the compact transport encoding widen back to the
        signature's DT_FLOAT; genuinely half-precision signatures pass
        through untouched), the client-encoding mirror, and the optional
        per-thread encode arena. `dest` is the response's outputs map."""
        half = (
            codec.dtype_to_numpy(fw.DataType.DT_BFLOAT16),
            np.dtype(np.float16),
        )
        sig_dtypes = None  # built lazily: the leak guard almost never
        # fires (the batcher completer already widened), and this encode
        # path is microbenchmark-hot (~50 us/call at 500 QPS).
        arena = self._arena()
        for name in out_names:
            arr = outputs[name]
            if arr.dtype in half:
                if sig_dtypes is None:
                    sig_dtypes = {
                        s.name: s.dtype
                        for s in servable.signature(
                            request.model_spec.signature_name
                        ).outputs
                    }
                if sig_dtypes.get(name) == fw.DataType.DT_FLOAT:
                    arr = (
                        arena.widen_f32(arr) if arena is not None
                        else arr.astype(np.float32)
                    )
            codec.from_ndarray(
                arr,
                use_tensor_content=mirror_content,
                out=dest[name],
                arena=arena,
            )

    def _predict_finish(
        self, request: apis.PredictRequest, servable: Servable, out_names,
        outputs,
    ) -> apis.PredictResponse:
        self._check_produced(out_names, outputs)
        with request_trace.span("predict.encode"):
            resp = apis.PredictResponse()
            resp.model_spec.CopyFrom(
                self._echo_spec(servable, request.model_spec.signature_name or "serving_default")
            )
            mirror = self._mirror_content(request)
            self._encode_outputs(
                request, servable, out_names, outputs, resp.outputs, mirror,
            )
        return resp

    # --------------------------------------------------------- PredictStream

    # Guard against pathological sub-batch explosions: a 32k-candidate
    # request with a 1-candidate chunk override must not mint 32k batcher
    # submits. The effective chunk size is raised until the request yields
    # at most this many sub-batches.
    _STREAM_MAX_CHUNKS = 64

    def _stream_plan(
        self, n: int, chunk: int | None
    ) -> list[tuple[int, int]]:
        """[(offset, count)] sub-batch split of an n-candidate request.
        `chunk` (per-request override, e.g. the x-dts-stream-chunk
        metadata) wins over the configured stream_chunk_candidates; 0 or
        absent on both = one chunk (streaming stays wire-available with
        the behavior change off)."""
        chunk_n = int(chunk) if chunk else int(self.stream_chunk_candidates or 0)
        if chunk_n <= 0 or chunk_n >= n:
            return [(0, n)]
        chunk_n = max(chunk_n, -(-n // self._STREAM_MAX_CHUNKS))
        return [(off, min(chunk_n, n - off)) for off in range(0, n, chunk_n)]

    def _stream_submit(
        self, request, deadline_t, criticality, chunk
    ):
        """Front half of predict_stream: resolve, decode, split, and
        submit EVERY sub-batch up front — the
        sub-batches ride the batcher's k-deep pipeline independently, so
        sub-batch k+1 uploads while k executes and k-1 reads back. Returns
        (servable, out_names, mirror_content, total, {future: (off, n)}).
        A submit failure mid-fan-out cancels the siblings already queued
        before translating."""
        servable, arrays, out_names, fetch_keys = self._predict_prepare(
            request, criticality
        )
        total = next(iter(arrays.values())).shape[0]
        plan = self._stream_plan(total, chunk)
        span = tracing.current_span()
        futs: dict = {}
        # A split stream's sub-batches submit _solo so the coalescer never
        # concatenates them back into the one big batch they were split
        # from; an unsplit request keeps ordinary coalescing semantics.
        solo = len(plan) > 1
        try:
            for off, cnt in plan:
                sub = {k: v[off: off + cnt] for k, v in arrays.items()}
                fut = self.batcher.submit(
                    servable, sub, output_keys=fetch_keys,
                    deadline_s=self._budget_left(deadline_t),
                    span=span, criticality=criticality, _solo=solo,
                )
                futs[fut] = (off, cnt)
        except Exception as e:  # noqa: BLE001 — translator re-raises non-batcher
            for f in futs:
                f.cancel()
            raise self._translate_batcher_error(e, None) from e
        return servable, out_names, self._mirror_content(request), total, futs

    def _encode_stream_chunk(
        self, request, servable, out_names, outputs,
        off: int, cnt: int, total: int, final: bool,
        mirror_content: bool, msg=None,
    ) -> apis.PredictStreamChunk:
        """One sub-batch -> one PredictStreamChunk (PredictResponse encode
        semantics — _encode_outputs is the SHARED per-tensor loop, so the
        streamed and unary wire encodings cannot drift). `msg` reuses one
        chunk message across the stream (the response-arena mode): gRPC
        serializes each yielded message before the generator resumes, so
        Clear+refill after yield is safe."""
        self._check_produced(out_names, outputs)
        with request_trace.span("predict.encode"):
            if msg is None:
                chunk = apis.PredictStreamChunk()
            else:
                chunk = msg
                chunk.Clear()
            chunk.model_spec.CopyFrom(self._echo_spec(
                servable, request.model_spec.signature_name or "serving_default"
            ))
            chunk.offset = int(off)
            chunk.count = int(cnt)
            chunk.total = int(total)
            chunk.final = bool(final)
            self._encode_outputs(
                request, servable, out_names, outputs, chunk.outputs,
                mirror_content,
            )
        return chunk

    def predict_stream(
        self, request: apis.PredictRequest, deadline_s: float | None = None,
        criticality: str | None = None, chunk: int | None = None,
    ):
        """Server-streaming Predict (ISSUE 9): a generator of
        PredictStreamChunk — the request is split into sub-batches that
        ride the batcher pipeline independently, and each chunk is yielded
        the moment its readback completes (possibly OUT OF ORDER; chunks
        carry offset/count for the client's incremental merge), so the
        caller's first scores decouple from the slowest sub-batch. Unary
        Predict semantics otherwise: same resolution/validation/encode
        path, same error classification — a failed sub-batch aborts the stream
        with the translated status after cancelling its siblings. A
        deadline expiring mid-stream cancels the remaining sub-batches
        and aborts DEADLINE_EXCEEDED."""
        import concurrent.futures as cf

        self._refuse_if_draining()
        deadline_t = self._clock_deadline(deadline_s)
        timeout = self._effective_timeout(deadline_s)
        give_up_t = time.perf_counter() + timeout
        servable, out_names, mirror_content, total, futs = (
            self._stream_submit(request, deadline_t, criticality, chunk)
        )
        reuse = apis.PredictStreamChunk() if self.response_arena else None
        pending = set(futs)
        emitted = 0
        try:
            while pending:
                left = give_up_t - time.perf_counter()
                if left <= 0:
                    raise ServiceError(
                        "DEADLINE_EXCEEDED",
                        "deadline expired mid-stream "
                        f"({emitted}/{len(futs)} sub-batches delivered)",
                    )
                done, pending = cf.wait(
                    pending, timeout=left,
                    return_when=cf.FIRST_COMPLETED,
                )
                if not done:
                    continue  # loop re-checks the give-up clock
                for fut in done:
                    try:
                        outputs = fut.result()
                    except Exception as e:  # noqa: BLE001 — translator re-raises
                        raise self._translate_batcher_error(e, fut) from e
                    # A stale-row brownout serve on any sub-batch marks
                    # the WHOLE stream degraded — the same trailer a
                    # whole-request stale serve emits (the generator runs
                    # in the RPC's context, so the contextvar reaches the
                    # transport adapter).
                    self._consume_future_degraded(fut)
                    off, cnt = futs[fut]
                    emitted += 1
                    yield self._encode_stream_chunk(
                        request, servable, out_names, outputs,
                        off, cnt, total, final=emitted == len(futs),
                        mirror_content=mirror_content, msg=reuse,
                    )
        except BaseException:
            # Mid-stream failure/deadline/disconnect: withdraw every
            # sub-batch still queued so abandoned work never dispatches.
            for f in pending:
                f.cancel()
            raise
        self._log_request("predict", request)

    # ----------------------------------------------------- Classify / Regress

    def _examples_prepare(self, request, criticality: str | None = None):
        """Shared front half of Classify/Regress: resolution + Example
        decode. Returns (servable, arrays)."""
        servable, _ = self._resolve(request.model_spec, criticality)
        try:
            arrays = decode_input(
                request.input, servable.model.config.num_fields,
                arena=self._arena(),
            )
        except ExampleDecodeError as e:
            raise ServiceError("INVALID_ARGUMENT", str(e)) from e
        return servable, arrays

    def _run_examples(
        self, request, deadline_s: float | None = None,
        criticality: str | None = None,
    ):
        deadline_t = self._clock_deadline(deadline_s)
        servable, arrays = self._examples_prepare(request, criticality)
        outputs = self._run(
            servable, arrays, output_keys=("prediction_node",),
            deadline_s=self._budget_left(deadline_t),
            criticality=criticality,
        )
        return servable, outputs

    async def _run_examples_async(
        self, request, deadline_s: float | None = None,
        criticality: str | None = None,
    ):
        """_run_examples for the REST gateway: its :classify/:regress
        routes ride the same event loop as :predict."""
        deadline_t = self._clock_deadline(deadline_s)
        servable, arrays = self._examples_prepare(request, criticality)
        outputs = await self._run_async(
            servable, arrays, output_keys=("prediction_node",),
            deadline_s=self._budget_left(deadline_t),
            criticality=criticality,
        )
        return servable, outputs

    def _classify_finish(
        self, request, servable, outputs
    ) -> apis.ClassificationResponse:
        scores = outputs["prediction_node"]
        resp = apis.ClassificationResponse()
        resp.model_spec.CopyFrom(
            self._echo_spec(servable, request.model_spec.signature_name or "classify")
        )
        for p in scores:
            cls = resp.result.classifications.add()
            cls.classes.add(label="0", score=float(1.0 - p))
            cls.classes.add(label="1", score=float(p))
        return resp

    def _classify_impl(
        self, request: apis.ClassificationRequest, deadline_s: float | None = None,
        criticality: str | None = None,
    ) -> apis.ClassificationResponse:
        """classify() minus request logging (multi_inference sub-calls ride
        this so a logged MultiInference record is not double-counted as its
        constituent classifications)."""
        servable, outputs = self._run_examples(
            request, deadline_s=deadline_s, criticality=criticality
        )
        return self._classify_finish(request, servable, outputs)

    def classify(
        self, request: apis.ClassificationRequest, deadline_s: float | None = None,
        criticality: str | None = None,
    ) -> apis.ClassificationResponse:
        self._refuse_if_draining()
        resp = self._classify_impl(
            request, deadline_s=deadline_s, criticality=criticality
        )
        self._log_request("classify", request)
        return resp

    async def classify_async(
        self, request: apis.ClassificationRequest, deadline_s: float | None = None,
        criticality: str | None = None,
    ) -> apis.ClassificationResponse:
        self._refuse_if_draining()
        servable, outputs = await self._run_examples_async(
            request, deadline_s=deadline_s, criticality=criticality
        )
        resp = self._classify_finish(request, servable, outputs)
        self._log_request("classify", request)
        return resp

    def _regress_finish(self, request, servable, outputs) -> apis.RegressionResponse:
        resp = apis.RegressionResponse()
        resp.model_spec.CopyFrom(
            self._echo_spec(servable, request.model_spec.signature_name or "regress")
        )
        for p in outputs["prediction_node"]:
            resp.result.regressions.add(value=float(p))
        return resp

    def _regress_impl(
        self, request: apis.RegressionRequest, deadline_s: float | None = None,
        criticality: str | None = None,
    ) -> apis.RegressionResponse:
        servable, outputs = self._run_examples(
            request, deadline_s=deadline_s, criticality=criticality
        )
        return self._regress_finish(request, servable, outputs)

    def regress(
        self, request: apis.RegressionRequest, deadline_s: float | None = None,
        criticality: str | None = None,
    ) -> apis.RegressionResponse:
        self._refuse_if_draining()
        resp = self._regress_impl(
            request, deadline_s=deadline_s, criticality=criticality
        )
        self._log_request("regress", request)
        return resp

    async def regress_async(
        self, request: apis.RegressionRequest, deadline_s: float | None = None,
        criticality: str | None = None,
    ) -> apis.RegressionResponse:
        self._refuse_if_draining()
        servable, outputs = await self._run_examples_async(
            request, deadline_s=deadline_s, criticality=criticality
        )
        resp = self._regress_finish(request, servable, outputs)
        self._log_request("regress", request)
        return resp

    # --------------------------------------------------------- MultiInference

    def multi_inference(
        self, request: apis.MultiInferenceRequest, deadline_s: float | None = None,
        criticality: str | None = None,
    ) -> apis.MultiInferenceResponse:
        self._refuse_if_draining()
        if not request.tasks:
            raise ServiceError("INVALID_ARGUMENT", "MultiInferenceRequest has no tasks")
        # Sub-calls run sequentially, so each gets the budget REMAINING at
        # its own start — handing every task the full entry-time deadline
        # would let server work extend tasks x deadline past the instant
        # the client gave up.
        deadline_t = self._clock_deadline(deadline_s)

        def remaining() -> float | None:
            left = self._budget_left(deadline_t)
            if left is not None and left <= 0:
                raise ServiceError(
                    "DEADLINE_EXCEEDED",
                    "client deadline expired between MultiInference tasks",
                )
            return left

        resp = apis.MultiInferenceResponse()
        for task in request.tasks:
            method = task.method_name
            if method == "tensorflow/serving/classify":
                sub = apis.ClassificationRequest(model_spec=task.model_spec, input=request.input)
                out = self._classify_impl(
                    sub, deadline_s=remaining(), criticality=criticality
                )
                r = resp.results.add()
                r.model_spec.CopyFrom(out.model_spec)
                r.classification_result.CopyFrom(out.result)
            elif method == "tensorflow/serving/regress":
                sub = apis.RegressionRequest(model_spec=task.model_spec, input=request.input)
                out = self._regress_impl(
                    sub, deadline_s=remaining(), criticality=criticality
                )
                r = resp.results.add()
                r.model_spec.CopyFrom(out.model_spec)
                r.regression_result.CopyFrom(out.result)
            else:
                raise ServiceError(
                    "INVALID_ARGUMENT",
                    f"unsupported MultiInference method {method!r} "
                    "(expected tensorflow/serving/classify or .../regress)",
                )
        self._log_request("multi_inference", request)
        return resp

    # ---------------------------------------------------------- ModelService

    def get_model_status(
        self, request: apis.GetModelStatusRequest
    ) -> apis.GetModelStatusResponse:
        """tensorflow.serving.ModelService/GetModelStatus (get_model_status
        .proto upstream): version states for readiness probes. Loaded
        versions are AVAILABLE by construction — the registry flips
        atomically after load+warmup, so the upstream LOADING/UNLOADING
        transients are never externally observable here.

        A model the server is CONFIGURED for (a watcher owns its base_path
        via --model-base-path or --model-config-file) whose first version
        has not landed yet reports state START — TF-Serving-style readiness
        probes poll through the rollout instead of treating the transient
        as an RPC error. NOT_FOUND remains the answer for names this server
        was never told about."""
        name = request.model_spec.name
        if not name:
            raise ServiceError("INVALID_ARGUMENT", "model_spec.name is required")
        loaded = self.registry.models().get(name)
        if not loaded:
            if not self.is_configured(name):
                raise ServiceError("NOT_FOUND", f"model {name!r} not found")
            version, _label = self._version_choice(request.model_spec)
            resp = apis.GetModelStatusResponse()
            st = resp.model_version_status.add()
            st.version = version or 0  # no version directory discovered yet
            st.state = apis.ModelVersionStatus.START
            st.status.error_code = 0
            return resp
        version, label = self._version_choice(request.model_spec)
        if label is not None:
            servable = _wrap_lookup(
                lambda: self.registry.resolve(name, None, label)
            )
            loaded = [servable.version]
        elif version is not None:
            if version not in loaded:
                raise ServiceError(
                    "NOT_FOUND",
                    f"model {name!r} has no version {version}; have {loaded}",
                )
            loaded = [version]
        resp = apis.GetModelStatusResponse()
        for v in sorted(loaded):
            st = resp.model_version_status.add()
            st.version = v
            st.state = apis.ModelVersionStatus.AVAILABLE
            st.status.error_code = 0
        return resp

    def handle_reload_config(
        self, request: apis.ReloadConfigRequest
    ) -> apis.ReloadConfigResponse:
        """tensorflow.serving.ModelService/HandleReloadConfigRequest
        (model_management.proto upstream).

        Two modes, by deployment shape:
        - multi-model (--model-config-file set `model_lifecycle`): the
          FULL upstream semantics — the supplied model_config_list
          REPLACES the served set (new entries start watchers, absent
          entries stop+unload, existing entries get declarative labels).
          An empty list is refused rather than interpreted as "unload
          everything".
        - single-model modes: scoped to the version_labels maps — the
          blue-green flip over the wire. Each named model's supplied map
          is the DECLARATIVE label state (labels absent from it are
          unassigned); a config naming an unserved model is NOT_FOUND
          (model-list lifecycle belongs to the startup artifact flags).
          Validation+application ride one registry lock acquisition
          (replace_label_maps), so a concurrent unload can never leave
          the reload half-applied."""
        cfg = request.config
        if cfg.WhichOneof("config") != "model_config_list":
            raise ServiceError(
                "INVALID_ARGUMENT",
                "only model_config_list reloads are supported "
                "(custom_model_config has no meaning here)",
            )
        if self.model_lifecycle is not None:
            # Multi-model mode: upstream's FULL reload — the supplied list
            # REPLACES the served model set (add/remove watchers,
            # declarative labels on existing models). Same entry
            # validation as startup.
            from ..utils.config import validate_model_config_entries

            try:
                entries = validate_model_config_entries(
                    cfg.model_config_list.config, "reload config"
                )
            except ValueError as e:
                raise ServiceError("INVALID_ARGUMENT", str(e)) from e
            if not entries:
                raise ServiceError(
                    "INVALID_ARGUMENT",
                    "refusing an empty model_config_list (it would unload "
                    "every model; unload explicitly per model instead)",
                )
            try:
                self.model_lifecycle.apply(entries)
            except ValueError as e:
                raise ServiceError("INVALID_ARGUMENT", str(e)) from e
            except (ModelNotFoundError, VersionNotFoundError) as e:
                raise ServiceError("FAILED_PRECONDITION", str(e)) from e
            resp = apis.ReloadConfigResponse()
            resp.status.error_code = 0
            return resp
        maps: dict[str, dict[str, int]] = {}
        served = self.registry.models()  # one snapshot for the advisory check
        for mc in cfg.model_config_list.config:
            if not mc.name:
                raise ServiceError("INVALID_ARGUMENT", "model config missing name")
            if mc.base_path or mc.model_platform:
                # A config may RE-STATE the served source (deploy tools
                # replay their full config to flip a label) — but silently
                # ignoring an actual base-path/platform CHANGE would let
                # the config claim one artifact while the server serves
                # another.
                src = self.served_sources.get(mc.name)
                moved = (
                    src is None
                    or (mc.base_path and mc.base_path != src[0])
                    or (mc.model_platform
                        and mc.model_platform not in ("tensorflow", src[1]))
                )
                if moved:
                    raise ServiceError(
                        "FAILED_PRECONDITION",
                        f"model {mc.name!r}: this server was started in "
                        "single-model mode and cannot apply base_path/"
                        "model_platform changes; model-list reloads require "
                        "--model-config-file (a config re-stating the "
                        "CURRENT source is accepted for label retargeting)",
                    )
            if not served.get(mc.name):
                raise ServiceError(
                    "NOT_FOUND",
                    f"model {mc.name!r} is not served here; reload applies "
                    "version_labels to already-served models (model-list "
                    "lifecycle rides the --model-base-path watcher)",
                )
            maps[mc.name] = {label: int(v) for label, v in mc.version_labels.items()}
        try:
            self.registry.replace_label_maps(maps)
        except ValueError as e:
            # e.g. an empty-string label key — a malformed request.
            raise ServiceError("INVALID_ARGUMENT", str(e)) from e
        except (ModelNotFoundError, VersionNotFoundError) as e:
            # Labels may only name loaded versions; a vanished model or
            # version is a precondition failure, applied-nothing.
            raise ServiceError("FAILED_PRECONDITION", str(e)) from e
        resp = apis.ReloadConfigResponse()
        resp.status.error_code = 0
        return resp

    # ------------------------------------------------------- GetModelMetadata

    def get_model_metadata(
        self, request: apis.GetModelMetadataRequest
    ) -> apis.GetModelMetadataResponse:
        fields = list(request.metadata_field) or [SIGNATURE_DEF_FIELD]
        unknown = [f for f in fields if f != SIGNATURE_DEF_FIELD]
        if unknown:
            raise ServiceError(
                "INVALID_ARGUMENT", f"unsupported metadata_field values {unknown}"
            )
        if not request.model_spec.name:
            raise ServiceError("INVALID_ARGUMENT", "model_spec.name is required")
        version, label = self._version_choice(request.model_spec)
        servable = _wrap_lookup(
            lambda: self.registry.resolve(request.model_spec.name, version, label)
        )

        resp = apis.GetModelMetadataResponse()
        resp.model_spec.CopyFrom(self._echo_spec(servable, ""))
        resp.model_spec.ClearField("signature_name")
        sig_map = apis.SignatureDefMap()
        for name, sd in servable.signature_def_map().items():
            sig_map.signature_def[name].CopyFrom(sd)
        resp.metadata[SIGNATURE_DEF_FIELD].Pack(sig_map)
        return resp
