"""TF-Serving-compatible REST gateway (the :8501 surface).

`tensorflow_model_server` serves every model on two ports: gRPC (:8500)
and a JSON REST API (:8501) with the `/v1/models/...` routes. The
reference client speaks gRPC only (DCNClient.java), but the ecosystem the
reference lives in — dashboards, canary probes, curl debugging — uses the
REST surface constantly; a drop-in replacement must answer it.

Routes (TF-Serving REST API v1 semantics; every POST verb also accepts
`/versions/{v}` or `/labels/{l}` segments — label routing matches the
model server's version_labels map):
- `POST /v1/models/{model}[/versions/{v}|/labels/{l}]:predict`
  body `{"instances": [...]}` (row format: one dict per instance, or the
  bare value for single-input models) -> `{"predictions": [...]}`;
  body `{"inputs": {...}}` (columnar) -> `{"outputs": ...}` (dict when
  the signature has several outputs, bare tensor when one);
  optional `"signature_name"`.
- `POST /v1/models/{model}[/versions/{v}]:classify` and `...:regress`
  body `{"examples": [{feat: val, ...}, ...], "context": {...}?}` ->
  `{"results": [...]}` (label/score pairs per example for classify, one
  value per example for regress), riding the same Example plane as the
  gRPC Classify/Regress RPCs (`example_codec.decode_input`).
- `GET  /v1/models/{model}` -> version status list.
- `GET  /v1/models/{model}/metadata` -> signature metadata (JSON).
- `GET  /monitoring/prometheus/metrics` -> Prometheus text exposition
  (the model server's monitoring endpoint; TF-Serving metric names).
- `GET  /monitoring[?section=NAME]` -> the metrics snapshot as JSON
  (rolling-window QPS + windowed percentiles next to lifetime values,
  per-model blocks, batcher gauges, phase means, one block per armed
  plane; ?section serves a single block without building the rest).
- `GET  /qualityz`, `POST /qualityz/snapshot`, `POST /labelz` -> the
  model-quality plane (serving/quality.py): score sketches + drift,
  reference pinning, label-feedback ingest.
- `GET  /tracez[?format=chrome][&limit=N]` -> the trace plane
  (utils/tracing.py): recent + slowest retained span trees as JSON, or a
  Perfetto-loadable Chrome-trace-event export.

Requests are converted to the SAME PredictRequest protos the gRPC path
parses and handed to PredictionServiceImpl.predict_async — one
implementation of resolution, validation, widening, batching, and error
classification; the gateway only translates JSON<->tensors and ServiceError
codes onto HTTP statuses (TF-Serving's own REST error shape:
`{"error": "..."}`).
"""

from __future__ import annotations

import json
import logging
import time

import numpy as np
from aiohttp import web

from .. import codec
from ..proto import serving_apis_pb2 as apis
from ..utils import tracing
from ..utils.tracing import request_trace
from . import lifecycle as lifecycle_mod
from . import overload as overload_mod
from .service import PredictionServiceImpl, ServiceError

log = logging.getLogger("dts_tpu.rest")

_HTTP_STATUS = {
    "NOT_FOUND": 404,
    "INVALID_ARGUMENT": 400,
    "RESOURCE_EXHAUSTED": 429,
    "UNAVAILABLE": 503,
    "DEADLINE_EXCEEDED": 504,
    "INTERNAL": 500,
}


def _json_error(
    code: str, message: str, retry_after_ms: int | None = None
) -> web.Response:
    resp = web.json_response(
        {"error": message}, status=_HTTP_STATUS.get(code, 500)
    )
    if retry_after_ms:
        # Overload pushback (serving/overload.py): the standard header in
        # whole seconds (ceil — a 25 ms hint must not round to "now") plus
        # the precise hint the in-tree client honors.
        resp.headers["Retry-After"] = str(max((retry_after_ms + 999) // 1000, 1))
        resp.headers[overload_mod.RETRY_AFTER_KEY] = str(int(retry_after_ms))
    return resp


def _criticality_of(request: web.Request) -> str | None:
    """The request's criticality lane from the x-dts-criticality header.
    Only scanned while a plane that consumes it is armed (overload lane
    shedding, or lifecycle probe-lane canary routing)."""
    if not (overload_mod.active() or lifecycle_mod.active()):
        return None
    value = request.headers.get(overload_mod.CRITICALITY_KEY)
    return overload_mod.normalize_criticality(value) if value else None


def _mark_degraded(resp: web.Response) -> web.Response:
    """Brownout stale-serves announce themselves in an X-DTS-Degraded
    response header, mirroring the gRPC trailing-metadata marker (the
    contextvar is task-local, so this request's handler task sees exactly
    its own marker)."""
    if overload_mod.active():
        degraded = overload_mod.consume_degraded()
        if degraded:
            resp.headers[overload_mod.DEGRADED_KEY] = degraded
    return resp


class RestGateway:
    """aiohttp application exposing a PredictionServiceImpl over REST.

    When a ServerMetrics is provided (the server CLI passes the gRPC
    server's instance, so both surfaces aggregate in one place), every
    REST request is observed under a `REST.<Verb>` entrypoint and the
    gateway answers `GET /monitoring/prometheus/metrics` — the model
    server's monitoring endpoint (enabled there via --monitoring_config_
    file; always on here, it is read-only and costs nothing when
    unscraped)."""

    def __init__(self, impl: PredictionServiceImpl, metrics=None):
        from ..utils.metrics import ServerMetrics

        self.impl = impl
        self.metrics = metrics or ServerMetrics()
        self.app = web.Application(client_max_size=256 * 1024 * 1024)
        self.app.add_routes([
            web.post("/v1/models/{model}:predict", self.predict),
            web.post(
                "/v1/models/{model}/versions/{version}:predict", self.predict
            ),
            web.post(
                "/v1/models/{model}/labels/{label}:predict", self.predict
            ),
            web.post("/v1/models/{model}:classify", self.classify),
            web.post(
                "/v1/models/{model}/versions/{version}:classify", self.classify
            ),
            web.post(
                "/v1/models/{model}/labels/{label}:classify", self.classify
            ),
            web.post("/v1/models/{model}:regress", self.regress),
            web.post(
                "/v1/models/{model}/versions/{version}:regress", self.regress
            ),
            web.post(
                "/v1/models/{model}/labels/{label}:regress", self.regress
            ),
            web.get("/v1/models/{model}", self.status),
            web.get("/v1/models/{model}/versions/{version}", self.status),
            web.get("/v1/models/{model}/labels/{label}", self.status),
            web.get("/v1/models/{model}/metadata", self.metadata),
            web.get(
                "/v1/models/{model}/versions/{version}/metadata", self.metadata
            ),
            web.get("/v1/models/{model}/labels/{label}/metadata", self.metadata),
            web.get("/monitoring/prometheus/metrics", self.prometheus),
            # Live-telemetry plane (ISSUE 3): the JSON twin of the
            # Prometheus surface (rolling-window QPS/percentiles next to
            # lifetime values, per-model blocks, batcher gauges, phase
            # means) and the trace viewer (recent + slowest span trees;
            # ?format=chrome exports Perfetto-loadable trace-event JSON).
            web.get("/monitoring", self.monitoring),
            web.get("/tracez", self.tracez),
            # Fleet trace export (ISSUE 18): incremental kept-span pull
            # for a router-side TraceCollector (also mounted on the
            # gossip port when the fleet plane is armed).
            web.get("/tracez/export", self.tracez_export),
            # Cache plane (ISSUE 4): per-model hit/miss/coalesced/eviction
            # counters + occupancy/config, and the operator flush control.
            web.get("/cachez", self.cachez),
            web.post("/cachez/flush", self.cachez_flush),
            # Utilization plane (ISSUE 6): the occupancy ledger's gap
            # waterfall (wall time decomposed into device/H2D/D2H plus
            # idle-by-cause, summing to wall) + the live
            # achieved_fraction_of_device_limit estimate, and on-demand
            # deep capture (jax.profiler device trace + host-thread stack
            # sampling over one window).
            web.get("/utilz", self.utilz),
            web.get("/profilez", self.profilez_status),
            web.post("/profilez/start", self.profilez_start),
            # Model-quality plane (ISSUE 7): per-(model, version) score
            # sketches + PSI/JS drift (vs the pinned reference and between
            # live versions) + label-join AUC/calibration, the reference-
            # pinning control, and the label-feedback ingest.
            web.get("/qualityz", self.qualityz),
            web.post("/qualityz/snapshot", self.qualityz_snapshot),
            web.post("/labelz", self.labelz),
            # Lifecycle plane (ISSUE 8): the continuous-freshness state
            # machine — canary routing fractions/counters, promote/
            # rollback history, and the version watcher's blacklist/pin
            # state.
            web.get("/lifecyclez", self.lifecyclez),
            # Operator rollback lever (ISSUE 17): demote the live canary
            # NOW — the same path the quality gate takes, so the fleet
            # coordinator sees rolled_back in the next gossip record and
            # blacklists the version fleet-wide.
            web.post("/lifecyclez/rollback", self.lifecyclez_rollback),
            # Fleet plane (ISSUE 17): this member's gossip view — every
            # known replica/router record, exchange counters, and the
            # rollout follower/coordinator state.
            web.get("/fleetz", self.fleetz),
            # Recovery plane (ISSUE 11): the device-failure recovery
            # state machine — quarantine/reinit/replay counters, the
            # poisoned-input bisection verdicts, and the last cycle's
            # duration (the live MTTR evidence).
            web.get("/recoveryz", self.recoveryz),
            # Mesh serving mode (ISSUE 13/15): geometry, device list,
            # executor pad/layout counters — and, with [elastic] armed,
            # the current split, switch history ring, and per-split
            # serve counters.
            web.get("/meshz", self.meshz),
            # Multi-stage ranking cascade (ISSUE 19): stage-1/prune/
            # stage-2 counters, row dispositions, observed survivor and
            # rank fractions, and the survivor-bucket histogram.
            web.get("/cascadez", self.cascadez),
            # Data-integrity plane (ISSUE 20): wire-checksum / readback-
            # screen / shadow-verification counters + suspect state and
            # the detection-event history, and the operator lever that
            # forces the NEXT batches through shadow verification.
            web.get("/integrityz", self.integrityz),
            web.post("/integrityz/audit", self.integrityz_audit),
        ])

    # ------------------------------------------------------------- helpers

    def _resolve_specs(
        self, model: str, version, signature_name: str, label=None,
        criticality=None,
    ):
        # ONE lookup-error classification, shared with the gRPC path. The
        # lifecycle plane's canary router overrides DEFAULT resolutions
        # here too — the gateway pins the CONCRETE resolved version into
        # the proto it hands the impl, so routing must happen at this
        # resolve or REST traffic would never carry canary share.
        from .service import _wrap_lookup

        routed = self.impl.lifecycle_route(model, version, label, criticality)
        if routed is not None:
            try:
                servable = self.impl.registry.resolve(model, routed)
            except KeyError:
                # Routed version vanished mid-swap (rollback racing this
                # request): serve the latest instead of failing traffic.
                servable = _wrap_lookup(
                    lambda: self.impl.registry.resolve(model)
                )
        else:
            servable = _wrap_lookup(
                lambda: self.impl.registry.resolve(model, version, label)
            )
        sig = _wrap_lookup(lambda: servable.signature(signature_name))
        return servable, sig

    @staticmethod
    def _parse_version(raw) -> int | None:
        if raw is None:
            return None
        try:
            return int(raw)
        except ValueError as e:
            # A non-numeric /versions/{v} segment is a CLIENT error, not an
            # internal one (label routing rides /labels/{l} instead).
            raise ServiceError(
                "INVALID_ARGUMENT", f"version must be an integer, got {raw!r}"
            ) from e

    @staticmethod
    def _fill_model_spec(spec, model: str, version: int | None, label) -> None:
        """ONE place that turns route segments into a ModelSpec, for all
        three POST verbs (version and label arrive from distinct routes, so
        the upstream oneof exclusivity holds by construction here; the
        service still enforces it for raw proto callers)."""
        spec.name = model
        if version is not None:
            spec.version.value = version
        if label:
            spec.version_label = label

    @staticmethod
    def _arrays_from_instances(instances, sig) -> dict[str, np.ndarray]:
        if not isinstance(instances, list) or not instances:
            raise ServiceError(
                "INVALID_ARGUMENT", "instances must be a non-empty list"
            )
        specs = sig.input_specs
        if isinstance(instances[0], dict):
            columns: dict[str, list] = {}
            for i, inst in enumerate(instances):
                if not isinstance(inst, dict):
                    raise ServiceError(
                        "INVALID_ARGUMENT",
                        f"instance {i} is not an object (mixed row formats)",
                    )
                for k, v in inst.items():
                    columns.setdefault(k, []).append(v)
            if any(len(v) != len(instances) for v in columns.values()):
                raise ServiceError(
                    "INVALID_ARGUMENT",
                    "every instance must carry the same input names",
                )
        else:
            # Bare-value shorthand: legal only for single-input signatures
            # (TF-Serving REST API rule).
            if len(specs) != 1:
                raise ServiceError(
                    "INVALID_ARGUMENT",
                    "bare-value instances require a single-input signature; "
                    f"this one expects {sorted(specs)}",
                )
            columns = {next(iter(specs)): instances}
        return RestGateway._to_ndarrays(columns, specs)

    @staticmethod
    def _to_ndarrays(columns: dict, specs) -> dict[str, np.ndarray]:
        arrays = {}
        for name, vals in columns.items():
            spec = specs.get(name)
            np_dtype = codec.dtype_to_numpy(spec.dtype) if spec else None
            try:
                arrays[name] = np.asarray(vals, dtype=np_dtype)
            except (TypeError, ValueError, OverflowError) as e:
                raise ServiceError(
                    "INVALID_ARGUMENT", f"input {name!r}: {e}"
                ) from e
        return arrays

    # -------------------------------------------------------------- routes

    async def _observed(self, name: str, handler, request) -> web.Response:
        t0 = time.perf_counter()
        if overload_mod.active():
            # Clear any degraded marker a FAILED previous request left in
            # this context (markers are consumed only on the success path,
            # and aiohttp reuses one task per keep-alive connection).
            overload_mod.consume_degraded()
        model = request.match_info.get("model")
        if tracing.enabled():
            # Server root span for the REST surface: adopts the caller's
            # trace via the standard W3C `traceparent` HTTP header.
            with tracing.start_root(
                f"server.{name}",
                traceparent=request.headers.get("traceparent"),
                attrs={"entrypoint": name, **({"model": model} if model else {})},
            ) as span:
                resp = await handler(request)
                # span can be None: disable() racing this request makes
                # start_root yield the no-op context mid-flight.
                if span is not None and resp.status >= 400:
                    span.status = "ERROR"
                    span.attrs["http_status"] = resp.status
        else:
            resp = await handler(request)
        self.metrics.observe(
            name, time.perf_counter() - t0, resp.status < 400, model=model
        )
        return resp

    async def predict(self, request: web.Request) -> web.Response:
        return await self._observed("REST.Predict", self._predict, request)

    async def _predict(self, request: web.Request) -> web.Response:
        model = request.match_info["model"]
        try:
            version = self._parse_version(request.match_info.get("version"))
            label = request.match_info.get("label")
            try:
                body = await request.json()
            except Exception as e:  # noqa: BLE001 — malformed JSON is a 400
                return _json_error("INVALID_ARGUMENT", f"invalid JSON body: {e}")
            if not isinstance(body, dict):
                return _json_error("INVALID_ARGUMENT", "body must be a JSON object")
            signature_name = body.get("signature_name", "")
            row_format = "instances" in body
            if row_format == ("inputs" in body):
                return _json_error(
                    "INVALID_ARGUMENT",
                    'body must carry exactly one of "instances" or "inputs"',
                )
            servable, sig = self._resolve_specs(
                model, version, signature_name, label,
                criticality=_criticality_of(request),
            )
            if row_format:
                arrays = self._arrays_from_instances(body["instances"], sig)
            else:
                cols = body["inputs"]
                if not isinstance(cols, dict):
                    # Bare columnar tensor: single-input shorthand.
                    specs = sig.input_specs
                    if len(specs) != 1:
                        return _json_error(
                            "INVALID_ARGUMENT",
                            "bare inputs require a single-input signature",
                        )
                    cols = {next(iter(specs)): cols}
                arrays = self._to_ndarrays(cols, sig.input_specs)

            # ONE semantics path: the same proto the gRPC surface parses.
            # The spec pins the CONCRETE version this gateway just resolved
            # (and validated inputs against) — re-sending the label (or an
            # absent version) would let the impl re-resolve, and a label
            # retarget / hot-swap landing between decode and execute would
            # pair one version's signature with another's execution.
            req = apis.PredictRequest()
            self._fill_model_spec(req.model_spec, model, servable.version, None)
            req.model_spec.signature_name = signature_name
            for key, arr in arrays.items():
                codec.from_ndarray(
                    arr, use_tensor_content=True, out=req.inputs[key]
                )
            resp = await self.impl.predict_async(
                req, criticality=_criticality_of(request)
            )
            outputs = {
                k: codec.to_ndarray(v).tolist() for k, v in resp.outputs.items()
            }
            if row_format:
                names = list(outputs)
                if len(names) == 1:
                    predictions = outputs[names[0]]
                else:
                    n = len(next(iter(outputs.values())))
                    predictions = [
                        {k: outputs[k][i] for k in names} for i in range(n)
                    ]
                return _mark_degraded(
                    web.json_response({"predictions": predictions})
                )
            if len(outputs) == 1:
                return _mark_degraded(
                    web.json_response({"outputs": next(iter(outputs.values()))})
                )
            return _mark_degraded(web.json_response({"outputs": outputs}))
        except ServiceError as e:
            return _json_error(
                e.code, str(e), retry_after_ms=e.retry_after_ms
            )
        except Exception as e:  # noqa: BLE001 — surface as 500, keep serving
            log.exception("internal error serving REST predict")
            return _json_error("INTERNAL", f"internal error: {e}")

    # ------------------------------------------------- classify / regress

    @staticmethod
    def _feature_from_json(key: str, value, feature) -> None:
        """Fill one tf.Example Feature from a JSON value (TF-Serving REST
        Example encoding: scalars or flat lists; ints -> int64_list, floats
        -> float_list with int coercion, strings -> bytes_list, and
        `{"b64": ...}` objects for binary — json_tensor.cc semantics)."""
        import base64

        vals = value if isinstance(value, list) else [value]
        if not vals:
            raise ServiceError(
                "INVALID_ARGUMENT", f"feature {key!r}: empty value list"
            )
        if any(isinstance(v, float) for v in vals):
            try:
                feature.float_list.value.extend(float(v) for v in vals)
            except (TypeError, ValueError) as e:
                raise ServiceError(
                    "INVALID_ARGUMENT", f"feature {key!r}: {e}"
                ) from e
        elif all(isinstance(v, bool) is False and isinstance(v, int) for v in vals):
            try:
                feature.int64_list.value.extend(vals)
            except ValueError as e:  # out of int64 range is a client error
                raise ServiceError(
                    "INVALID_ARGUMENT", f"feature {key!r}: {e}"
                ) from e
        elif all(isinstance(v, str) for v in vals):
            feature.bytes_list.value.extend(v.encode("utf-8") for v in vals)
        elif all(isinstance(v, dict) and set(v) == {"b64"} for v in vals):
            try:
                feature.bytes_list.value.extend(
                    base64.b64decode(v["b64"]) for v in vals
                )
            except Exception as e:  # noqa: BLE001 — bad base64 is a 400
                raise ServiceError(
                    "INVALID_ARGUMENT", f"feature {key!r}: invalid base64: {e}"
                ) from e
        else:
            raise ServiceError(
                "INVALID_ARGUMENT",
                f"feature {key!r}: values must be all-int, all-float "
                "(ints coerce), all-string, or all-b64 objects",
            )

    def _example_from_json(self, obj, index: int):
        from ..proto import tf_example_pb2 as ex

        if not isinstance(obj, dict):
            raise ServiceError(
                "INVALID_ARGUMENT", f"example {index} is not a JSON object"
            )
        example = ex.Example()
        for key, value in obj.items():
            self._feature_from_json(
                key, value, example.features.feature[key]
            )
        return example

    def _build_example_request(self, request: web.Request, req, body: dict) -> None:
        """Shared :classify/:regress body parsing into a Classification/
        RegressionRequest's model_spec + Input (examples [+ context])."""
        model = request.match_info["model"]
        version = self._parse_version(request.match_info.get("version"))
        self._fill_model_spec(
            req.model_spec, model, version, request.match_info.get("label")
        )
        req.model_spec.signature_name = body.get("signature_name", "")
        examples = body.get("examples")
        if not isinstance(examples, list) or not examples:
            raise ServiceError(
                "INVALID_ARGUMENT", 'body must carry a non-empty "examples" list'
            )
        context = body.get("context")
        if context is not None:
            target = req.input.example_list_with_context
            target.context.CopyFrom(self._example_from_json(context, -1))
            dest = target.examples
        else:
            dest = req.input.example_list.examples
        for i, obj in enumerate(examples):
            dest.append(self._example_from_json(obj, i))

    async def _example_route(self, request: web.Request, kind: str) -> web.Response:
        try:
            try:
                body = await request.json()
            except Exception as e:  # noqa: BLE001 — malformed JSON is a 400
                return _json_error("INVALID_ARGUMENT", f"invalid JSON body: {e}")
            if not isinstance(body, dict):
                return _json_error("INVALID_ARGUMENT", "body must be a JSON object")
            if kind == "classify":
                req = apis.ClassificationRequest()
                self._build_example_request(request, req, body)
                resp = await self.impl.classify_async(
                    req, criticality=_criticality_of(request)
                )
                # TF-Serving REST shape (json_tensor.cc): one
                # [[label, score], ...] list per example, same order.
                results = [
                    [[c.label, c.score] for c in cls.classes]
                    for cls in resp.result.classifications
                ]
            else:
                req = apis.RegressionRequest()
                self._build_example_request(request, req, body)
                resp = await self.impl.regress_async(
                    req, criticality=_criticality_of(request)
                )
                results = [r.value for r in resp.result.regressions]
            return _mark_degraded(web.json_response({"results": results}))
        except ServiceError as e:
            return _json_error(
                e.code, str(e), retry_after_ms=e.retry_after_ms
            )
        except Exception as e:  # noqa: BLE001 — surface as 500, keep serving
            log.exception("internal error serving REST %s", kind)
            return _json_error("INTERNAL", f"internal error: {e}")

    async def classify(self, request: web.Request) -> web.Response:
        return await self._observed(
            "REST.Classify",
            lambda r: self._example_route(r, "classify"),
            request,
        )

    async def regress(self, request: web.Request) -> web.Response:
        return await self._observed(
            "REST.Regress",
            lambda r: self._example_route(r, "regress"),
            request,
        )

    async def prometheus(self, request: web.Request) -> web.Response:
        stats = getattr(self.impl.batcher, "stats", None)
        # Computed once and shared downstream: mesh_stats lifts its
        # per-device attribution from the utilization snapshot, and
        # elastic_stats lifts its block from the mesh snapshot — one
        # snapshot each per scrape, never recomputed.
        utilization = self.impl.utilization_stats()
        mesh = self.impl.mesh_stats(utilization=utilization)
        return web.Response(
            body=self.metrics.prometheus_text(
                stats, cache=self.impl.cache_stats(),
                row_cache=self.impl.row_cache_stats(),
                overload=self.impl.overload_stats(),
                utilization=utilization,
                quality=self.impl.quality_stats(),
                lifecycle=self.impl.lifecycle_stats(),
                pipeline=self.impl.pipeline_stats(),
                recovery=self.impl.recovery_stats(),
                mesh=mesh,
                elastic=self.impl.elastic_stats(mesh=mesh),
                fleet=self.impl.fleet_stats(),
                cascade=self.impl.cascade_stats(),
                integrity=self.impl.integrity_stats(),
            ).encode("utf-8"),
            headers={
                "Content-Type": "text/plain; version=0.0.4; charset=utf-8"
            },
        )

    def _monitoring_builders(self) -> dict:
        """One builder per /monitoring block, so ?section=NAME serves a
        single block WITHOUT serializing — or even computing — the other
        planes' snapshots (the JSON now aggregates 8+ blocks; scrapers
        that want one should not pay for all)."""

        def request_log():
            logger = getattr(self.impl, "request_logger", None)
            return logger.stats() if logger is not None else None

        return {
            "metrics": lambda: self.metrics.snapshot(
                getattr(self.impl.batcher, "stats", None)
            ),
            "phases": request_trace.snapshot,
            # Who is on the CPU, a row a thread and a row a role: one pass
            # over /proc/self/task on this thread, per scrape.
            "threads": tracing.thread_cpu.threads,
            "tracing": lambda: {
                "enabled": tracing.enabled(),
                "recorded": tracing.recorder().recorded,
            },
            "cache": self.impl.cache_stats,
            "row_cache": self.impl.row_cache_stats,
            "overload": self.impl.overload_stats,
            "utilization": self.impl.utilization_stats,
            "quality": self.impl.quality_stats,
            "lifecycle": self.impl.lifecycle_stats,
            "recovery": self.impl.recovery_stats,
            "mesh": self.impl.mesh_stats,
            "elastic": self.impl.elastic_stats,
            "fleet": self.impl.fleet_stats,
            "cascade": self.impl.cascade_stats,
            "integrity": self.impl.integrity_stats,
            "versions": self.impl.versions_stats,
            "pipeline": self.impl.pipeline_stats,
            "runtime": self.impl.runtime_stats,
            "request_log": request_log,
            "draining": lambda: bool(getattr(self.impl, "draining", False)),
        }

    async def monitoring(self, request: web.Request) -> web.Response:
        """GET /monitoring[?section=NAME]: the metrics snapshot as JSON —
        rolling-window qps + windowed percentiles next to the lifetime
        values, per-model blocks, batcher gauges, the aggregate phase
        means, and one block per armed plane (cache / overload /
        utilization / quality / request_log). ?section=NAME returns just
        that block (and skips building the rest server-side); a disabled
        plane's section answers null, an unknown name is a 400."""
        builders = self._monitoring_builders()
        section = request.query.get("section")
        if section is not None:
            builder = builders.get(section)
            if builder is None:
                return _json_error(
                    "INVALID_ARGUMENT",
                    f"unknown section {section!r}; have {sorted(builders)}",
                )
            return web.json_response({section: builder()})
        snap = builders["metrics"]()
        snap["phases"] = builders["phases"]()
        snap["tracing"] = builders["tracing"]()
        # Armed-plane blocks only: a disabled plane is absent, so
        # dashboards can distinguish "off" from "cold". The mesh block
        # reuses the utilization snapshot computed earlier in this same
        # pass (its per-device attribution lifts from it — no second
        # waterfall merge).
        for name in ("cache", "row_cache", "overload", "utilization",
                     "quality", "lifecycle", "recovery", "mesh", "elastic",
                     "fleet", "cascade", "integrity", "versions", "pipeline",
                     "runtime", "threads"):
            if name == "mesh":
                block = self.impl.mesh_stats(
                    utilization=snap.get("utilization")
                )
            elif name == "elastic":
                # Lifted from the mesh block computed just above in this
                # same pass — never a second executor/history walk.
                block = self.impl.elastic_stats(mesh=snap.get("mesh"))
            else:
                block = builders[name]()
            if block is not None:
                snap[name] = block
        snap["draining"] = builders["draining"]()
        log_block = builders["request_log"]()
        if log_block is not None:
            # Written/dropped accounting for the sampled PredictionLog
            # writer — a silently-shedding log queue must be visible here.
            snap["request_log"] = log_block
        return web.json_response(snap)

    async def tracez(self, request: web.Request) -> web.Response:
        """GET /tracez: recent + slowest retained span trees as JSON;
        ?format=chrome returns Chrome-trace-event JSON (Perfetto /
        chrome://tracing loadable); ?limit=N bounds the trace list."""
        rec = tracing.recorder()
        dumps = lambda obj: json.dumps(obj, default=str)  # noqa: E731
        if request.query.get("format") == "chrome":
            return web.json_response(rec.chrome_trace(), dumps=dumps)
        try:
            limit = int(request.query.get("limit", "50"))
        except ValueError:
            return _json_error("INVALID_ARGUMENT", "limit must be an integer")
        body = rec.tracez(limit=limit)
        body["enabled"] = tracing.enabled()
        return web.json_response(body, dumps=dumps)

    async def tracez_export(self, request: web.Request) -> web.Response:
        """GET /tracez/export?since=CURSOR: kept span trees after the
        cursor, with this process's clock anchor (the fleet stitcher's
        pull surface). `{"enabled": false}` while tracing is off."""
        if not tracing.enabled():
            return web.json_response(
                {"enabled": False, "cursor": 0, "spans": []}
            )
        try:
            since = int(request.query.get("since", "0") or 0)
        except ValueError:
            return _json_error("INVALID_ARGUMENT", "since must be an integer")
        return web.json_response(
            tracing.recorder().export_since(since),
            dumps=lambda obj: json.dumps(obj, default=str),
        )

    async def utilz(self, request: web.Request) -> web.Response:
        """GET /utilz[?window=S]: the utilization-attribution surface —
        occupancy ledger counters, idle-gap histogram by blocking cause,
        the windowed gap waterfall (components sum to wall), and the live
        achieved_fraction_of_device_limit estimate. `{"enabled": false}`
        when no ledger is armed ([utilization] enabled=false), so probes
        need no config knowledge."""
        window = request.query.get("window")
        if window is not None:
            try:
                window = float(window)
            except ValueError:
                return _json_error("INVALID_ARGUMENT", "window must be a number")
        stats = self.impl.utilization_stats(window)
        return web.json_response(
            stats if stats is not None else {"enabled": False}
        )

    async def profilez_status(self, request: web.Request) -> web.Response:
        """GET /profilez: is a deep capture running, and where will its
        artifacts land."""
        from .utilization import profiler_capture

        return web.json_response(profiler_capture().status())

    async def profilez_start(self, request: web.Request) -> web.Response:
        """POST /profilez/start?seconds=N: one-shot deep capture —
        jax.profiler device trace + host-thread stack sampling over the
        same window (utilization.HostStackSampler). Returns the
        artifact paths immediately; the capture stops itself after N
        seconds. A concurrent capture is refused with 409 (the jax
        profiler is process-global)."""
        from .utilization import CaptureInProgressError, profiler_capture

        try:
            seconds = float(request.query.get("seconds", "3"))
        except ValueError:
            return _json_error("INVALID_ARGUMENT", "seconds must be a number")
        try:
            info = profiler_capture().start(seconds)
        except CaptureInProgressError as e:
            return web.json_response({"error": str(e)}, status=409)
        return web.json_response({"started": True, **info})

    async def qualityz(self, request: web.Request) -> web.Response:
        """GET /qualityz[?model=NAME][&version=V]: the model-quality
        surface — per-(model, version) score sketches (lifetime + rolling
        window, per-lane counts), PSI/JS drift vs the pinned reference
        and between live versions, label-join AUC/calibration, and the
        exemplar counters. `{"enabled": false}` when no monitor is armed
        ([quality] enabled=false), so probes need no config knowledge."""
        version = request.query.get("version")
        if version is not None:
            try:
                version = int(version)
            except ValueError:
                return _json_error(
                    "INVALID_ARGUMENT", "version must be an integer"
                )
        stats = self.impl.quality_stats(
            model=request.query.get("model") or None, version=version
        )
        return web.json_response(
            stats if stats is not None else {"enabled": False}
        )

    async def qualityz_snapshot(self, request: web.Request) -> web.Response:
        """POST /qualityz/snapshot: pin the current windowed score
        distributions as the drift reference (and persist the artifact —
        [quality] reference_file, default artifacts/quality_reference
        .json). Future windows drift AGAINST this pin until the next."""
        try:
            pinned = self.impl.quality_pin_reference()
        except ServiceError as e:
            return _json_error(e.code, str(e))
        return web.json_response({"pinned": True, **pinned})

    async def labelz(self, request: web.Request) -> web.Response:
        """POST /labelz: the label-feedback ingest. Body: one label
        object `{"id": ..., "label": 0|1, "ts": ...?}` or
        `{"labels": [...]}`; `id` is a request trace id (optionally
        `#<row>`) or a per-row feature digest (client.label_keys /
        quality.row_label_keys). Answers joined/orphaned counts for this
        call — an orphaned label (unknown or evicted key) is reported,
        never silently dropped."""
        try:
            body = await request.json()
        except Exception as e:  # noqa: BLE001 — malformed JSON is a 400
            return _json_error("INVALID_ARGUMENT", f"invalid JSON body: {e}")
        if isinstance(body, dict) and "labels" in body:
            items = body["labels"]
        elif isinstance(body, dict):
            items = [body]
        else:
            items = body
        if not isinstance(items, list) or not items:
            return _json_error(
                "INVALID_ARGUMENT",
                'body must be a label object, a list, or {"labels": [...]}',
            )
        try:
            result = self.impl.quality_ingest_labels(items)
        except ServiceError as e:
            return _json_error(e.code, str(e))
        return web.json_response(result)

    async def lifecyclez(self, request: web.Request) -> web.Response:
        """GET /lifecyclez: the continuous-freshness surface — the
        IDLE/CANARY/PROMOTING/ROLLED_BACK state machine, stable/canary
        versions and the live routing fraction, publish/promote/rollback
        counters + transition history, the last rollback's evidence
        (pair PSI/JS, AUC deltas), and the version watcher's
        loaded/on-disk/blacklisted/pinned sets. `{"enabled": false}` when
        no controller is armed ([lifecycle] enabled=false), so probes
        need no config knowledge."""
        stats = self.impl.lifecycle_stats()
        return web.json_response(
            stats if stats is not None else {"enabled": False}
        )

    async def lifecyclez_rollback(self, request: web.Request) -> web.Response:
        """POST /lifecyclez/rollback: operator-forced demotion of the
        live canary — the SAME path the drift/AUC gate takes (retire +
        blacklist + restore stable), so the fleet coordinator's next
        tick sees `rolled_back` in this replica's gossip record and
        blacklists the version on EVERY replica. Body (optional JSON):
        {"reason": "..."}. 409 when there is no canary to roll back;
        `{"enabled": false}` + 404 when no controller is armed."""
        lifecycle = getattr(self.impl, "lifecycle", None)
        if lifecycle is None:
            return web.json_response({"enabled": False}, status=404)
        reason = "operator"
        try:
            body = await request.json()
            if isinstance(body, dict) and body.get("reason"):
                reason = str(body["reason"])
        except Exception:  # noqa: BLE001 — empty body is fine
            pass
        rolled = lifecycle.force_rollback(reason)
        return web.json_response(
            {"rolled_back": rolled, "reason": reason,
             "lifecycle": self.impl.lifecycle_stats()},
            status=200 if rolled else 409,
        )

    async def fleetz(self, request: web.Request) -> web.Response:
        """GET /fleetz: this member's fleet view — gossip membership
        (every known replica/router record with state/pressure/versions/
        canary fields), exchange + record-disposition counters, and the
        rollout follower state. `{"enabled": false}` when the replica is
        not fleet-joined ([fleet] enabled=false), so probes need no
        config knowledge."""
        plane = getattr(self.impl, "fleet", None)
        if plane is None:
            return web.json_response({"enabled": False})
        return web.json_response({"enabled": True, **plane.snapshot()})

    async def cascadez(self, request: web.Request) -> web.Response:
        """GET /cascadez: the multi-stage ranking cascade surface —
        config echo (stage-1 model, survivor policy), request/fallback/
        stage-1-failure counters, row dispositions (requested/survivor/
        pruned), per-stage wall time, observed survivor- and rank-
        fractions, and the survivor-bucket histogram (which padded rungs
        the stage-2 submits landed in). `{"enabled": false}` when the
        cascade is not armed ([cascade] enabled=false), so probes need
        no config knowledge."""
        stats = self.impl.cascade_stats()
        return web.json_response(
            stats if stats is not None else {"enabled": False}
        )

    async def integrityz(self, request: web.Request) -> web.Response:
        """GET /integrityz: the data-integrity surface — wire-checksum
        verify/reject counters, readback-screen trips, shadow-
        verification batch/mismatch counters, the replica's suspect
        verdict (what the fleet record gossips), escalations into the
        recovery plane, and the detection-event history. `{"enabled":
        false}` when the plane is not armed ([integrity] enabled=false),
        so probes need no config knowledge."""
        stats = self.impl.integrity_stats()
        return web.json_response(
            stats if stats is not None else {"enabled": False}
        )

    async def integrityz_audit(self, request: web.Request) -> web.Response:
        """POST /integrityz/audit[?batches=N]: operator-forced shadow
        verification — the NEXT N batches (default 1) re-execute through
        the same jitted entry and compare bit-identically, regardless of
        shadow_fraction. The on-demand lever for "is this replica
        corrupting right now?". 404 + `{"enabled": false}` when the
        plane is not armed."""
        integ = getattr(self.impl, "integrity", None)
        if integ is None:
            return web.json_response({"enabled": False}, status=404)
        try:
            batches = int(request.query.get("batches", "1"))
        except ValueError:
            return _json_error("INVALID_ARGUMENT", "batches must be an integer")
        if batches < 1:
            return _json_error("INVALID_ARGUMENT", "batches must be >= 1")
        pending = integ.request_audit(batches)
        return web.json_response(
            {"requested": batches, "pending_audits": pending}
        )

    async def recoveryz(self, request: web.Request) -> web.Response:
        """GET /recoveryz: the device-failure recovery surface — the
        SERVING/QUARANTINED/REINIT/REPLAY state machine, quarantine/
        reinit/replay/bisection counters, the last cycle's trigger +
        duration (MTTR evidence), and the transition-event history.
        `{"enabled": false}` when no controller is armed ([recovery]
        enabled=false), so probes need no config knowledge."""
        stats = self.impl.recovery_stats()
        return web.json_response(
            stats if stats is not None else {"enabled": False}
        )

    async def meshz(self, request: web.Request) -> web.Response:
        """GET /meshz: the mesh serving-mode surface — mesh geometry +
        device list, executor batch/pad counters, the layout source per
        served model, per-device occupancy attribution when the
        utilization ledger rides along, and (elastic mode, ISSUE 15) the
        `elastic` block: current split, ladder, switch history ring,
        per-split serve counters, controller state. `{"enabled": false}`
        when serving is single-chip, so probes need no config
        knowledge."""
        stats = self.impl.mesh_stats()
        return web.json_response(
            stats if stats is not None else {"enabled": False}
        )

    async def cachez(self, request: web.Request) -> web.Response:
        """GET /cachez: the score-cache introspection surface — aggregate +
        per-model hit/miss/coalesced/eviction/expiration counters, hit
        rate, entry/byte occupancy, and the active config, plus a
        `row_cache` block (per-row counters, rows_executed vs
        rows_requested) when the row-granular tier is armed. `{"enabled":
        false}` when no cache is armed (the route always answers, so
        probes need no config knowledge)."""
        stats = self.impl.cache_stats()
        row = self.impl.row_cache_stats()
        if row is not None:
            stats = dict(stats) if stats is not None else {"enabled": False}
            stats["row_cache"] = row
        return web.json_response(stats if stats is not None else {"enabled": False})

    async def cachez_flush(self, request: web.Request) -> web.Response:
        """POST /cachez/flush[?model=NAME]: drop every cached score (or one
        model's). The flush is generation-bumped, so results filled by
        computations already in flight are dropped too."""
        try:
            dropped = self.impl.cache_flush(request.query.get("model") or None)
        except ServiceError as e:
            return _json_error(e.code, str(e))
        return web.json_response({"flushed": True, "entries_dropped": dropped})

    async def status(self, request: web.Request) -> web.Response:
        # ONE status implementation: delegate to the ModelService RPC body
        # (impl.get_model_status) and translate to TF-Serving's REST JSON —
        # the gRPC and REST surfaces cannot drift (and the /versions and
        # /labels pinning arrives for free).
        model = request.match_info["model"]
        try:
            req = apis.GetModelStatusRequest()
            self._fill_model_spec(
                req.model_spec,
                model,
                self._parse_version(request.match_info.get("version")),
                request.match_info.get("label"),
            )
            resp = self.impl.get_model_status(req)
        except ServiceError as e:
            return _json_error(e.code, str(e))
        except ValueError as e:
            # e.g. a /versions/{v} segment past int64: client error, same
            # JSON classification as every other route.
            return _json_error("INVALID_ARGUMENT", str(e))
        except Exception as e:  # noqa: BLE001 — surface as 500, keep serving
            log.exception("internal error serving REST status")
            return _json_error("INTERNAL", f"internal error: {e}")
        state_name = apis.ModelVersionStatus.State.Name
        return web.json_response({
            "model_version_status": [
                {
                    "version": str(s.version),
                    "state": state_name(s.state),
                    # proto3-JSON enum-name convention, like the metadata
                    # route's dtypes: ecosystem parsers match "OK".
                    "status": {
                        "error_code": (
                            "OK" if s.status.error_code == 0
                            else s.status.error_code
                        ),
                        "error_message": s.status.error_message,
                    },
                }
                for s in resp.model_version_status
            ]
        })

    async def metadata(self, request: web.Request) -> web.Response:
        model = request.match_info["model"]
        try:
            # Servable resolution ONLY — no signature lookup: this route
            # enumerates ALL signatures, and a model serving purely by
            # explicit signature names (no serving_default — a supported
            # import shape, interop/savedmodel.py) must still answer.
            from .service import _wrap_lookup

            servable = _wrap_lookup(
                lambda: self.impl.registry.resolve(
                    model,
                    self._parse_version(request.match_info.get("version")),
                    request.match_info.get("label"),
                )
            )
        except ServiceError as e:
            return _json_error(e.code, str(e))
        except Exception as e:  # noqa: BLE001 — surface as 500, keep serving
            log.exception("internal error serving REST metadata")
            return _json_error("INTERNAL", f"internal error: {e}")

        from ..proto import tf_framework_pb2 as fw

        def spec_json(spec):
            shape = (
                {"unknown_rank": True}
                if spec.shape is None
                else {"dim": [{"size": str(-1 if d is None else d)} for d in spec.shape]}
            )
            # Enum by NAME: proto3 JSON (what tensorflow_model_server's
            # REST metadata emits) prints enums as strings, and ecosystem
            # parsers match on "DT_INT64", not 9.
            try:
                dtype = fw.DataType.Name(spec.dtype)
            except ValueError:
                dtype = int(spec.dtype)
            return {"dtype": dtype, "tensor_shape": shape}

        sig_defs = {
            name: {
                "method_name": sig.method_name,
                "inputs": {s.name: spec_json(s) for s in sig.inputs},
                "outputs": {s.name: spec_json(s) for s in sig.outputs},
            }
            for name, sig in servable.signatures.items()
        }
        return web.json_response({
            "model_spec": {
                "name": servable.name,
                "version": str(servable.version),
                "signature_name": "",
            },
            "metadata": {"signature_def": {"signature_def": sig_defs}},
        })


async def start_rest_gateway(
    impl: PredictionServiceImpl,
    host: str = "127.0.0.1",
    port: int = 8501,
    metrics=None,
) -> tuple[web.AppRunner, int]:
    """Start the gateway; returns (runner, bound_port). Stop with
    `await runner.cleanup()`. Pass the gRPC server's ServerMetrics so
    /monitoring/prometheus/metrics aggregates both surfaces."""
    gw = RestGateway(impl, metrics)
    runner = web.AppRunner(gw.app)
    await runner.setup()
    site = web.TCPSite(runner, host, port)
    await site.start()
    bound = runner.addresses[0][1]  # public API (private site._server breaks across aiohttp versions)
    return runner, bound
