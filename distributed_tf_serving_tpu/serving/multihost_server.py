"""Multi-host serving entry point: one logical model over a multi-process
mesh, operable like the reference's deployment.

The reference ran three independently-started backends behind a client
scatter (DCNClient.java:38); this is the equivalent operational surface for
the tier the reference never had — a SINGLE model spanning hosts
(parallel/multihost.py): every process runs

    python -m distributed_tf_serving_tpu.serving.multihost_server \
        --model-base-path /shared/models/DCN \
        --coordinator HOST0:7777 --num-processes K --process-id k [--port 9999]

process 0 serves gRPC and leads; the rest follow. Versions live in the
TF-Serving base-path convention on SHARED storage (every process must see
the same directory): the leader's VersionWatcher drives slice-wide RELOAD
hot-swaps; followers load each version through the same path. A dead
process fails the whole slice fast (heartbeat-bounded) — restart the job,
exactly like any SPMD deployment.

Split from serving/server.py so single-host serving never imports
jax.distributed machinery.
"""

from __future__ import annotations

import logging

import jax
import numpy as np

log = logging.getLogger("dts_tpu.multihost_server")

# Serving deployments want dead-process detection in seconds, not the
# preemption-tolerant 100 s default (parallel/multihost.py init_distributed).
HEARTBEAT_TIMEOUT_S = 10


def build_multihost_stack(
    base_path,
    coordinator: str | None,
    num_processes: int,
    process_id: int,
    model_kind: str = "dcn_v2",
    model_name: str = "DCN",
    buckets: tuple[int, ...] = (1024, 8192),
    model_parallel: int = 1,
    max_wait_us: int = 2000,
    poll_interval_s: float = 5.0,
    max_load_attempts: int = 3,
):
    """Initialize the distributed runtime and build the serving stack.

    Returns (runner, registry, batcher, impl, watcher) on process 0 and
    (runner, None, None, None, None) on followers — the caller runs
    `runner.follow()` there. The initial version is chosen by the LEADER
    and broadcast, so processes scanning shared storage at different
    moments cannot disagree about the starting params.

    Model architecture comes from the CHECKPOINT MANIFEST, never from
    flags: the operator cannot re-specify embed_dim/vocab/mlp_dims wrong,
    and the batch templates are derived from the servable's own signature
    (so DLRM's dense_features input is carried, not silently dropped).
    `model_kind` only parameterizes the watcher's SavedModel-dir handling.
    """
    import dataclasses as dc

    from jax.experimental import multihost_utils

    from ..models import ServableRegistry
    from ..parallel.multihost import MultiHostRunner, global_mesh, init_distributed
    from ..train.checkpoint import load_servable
    from .batcher import DynamicBatcher
    from .service import PredictionServiceImpl
    from .version_watcher import VersionWatcher, VersionWatcherConfig, scan_versions

    init_distributed(
        coordinator, num_processes, process_id,
        heartbeat_timeout_s=HEARTBEAT_TIMEOUT_S,
    )
    mesh = global_mesh(model_parallel=model_parallel)

    # Leader picks the starting version; everyone loads that exact one.
    if num_processes > 1:
        local_latest = max(scan_versions(base_path), default=0) if process_id == 0 else 0
        initial = int(
            multihost_utils.broadcast_one_to_all(np.asarray([local_latest], np.int64))[0]
        )
    else:
        initial = max(scan_versions(base_path), default=0)
    if initial == 0:
        raise FileNotFoundError(f"no version directories under {base_path}")

    def load_version(version: int):
        # Host restore: every process reads the full tree; the runner
        # places it at a protocol-aligned point (construction, or _place
        # after the RELOAD header) — a device restore here would need
        # cross-process shardings orbax cannot infer from a single-process
        # checkpoint, and orbax's own restore barrier would interleave
        # with the runner's collectives.
        return load_servable(f"{base_path}/{version}", host=True)

    def filter_signatures(sv, version):
        # The broadcast protocol gathers ONE output tensor (the scores);
        # the registered signature must promise exactly what the runner
        # serves, or Predict without an output_filter would fail INTERNAL
        # ("model produced [...] but signature declares [..., 'logits']").
        signatures = {
            name: dc.replace(
                sig,
                outputs=tuple(s for s in sig.outputs if s.name == "prediction_node"),
            )
            for name, sig in sv.signatures.items()
        }
        return dc.replace(sv, version=version, name=model_name, signatures=signatures)

    initial_sv = filter_signatures(load_version(initial), initial)
    model = initial_sv.model
    config = model.config

    # Templates from the servable's OWN signature: every declared input is
    # carried across the broadcast (feat_ids as post-fold int32; the rest —
    # feat_wts, DLRM dense_features — as float32 with their trailing dims).
    sig = initial_sv.signature("")
    def template(b: int) -> dict[str, np.ndarray]:
        out = {}
        for spec in sig.inputs:
            trailing = tuple(d or 1 for d in (spec.shape or (None, 1))[1:])
            if spec.name == "feat_ids":
                out[spec.name] = np.zeros((b, *trailing), np.int32)
            else:
                out[spec.name] = np.zeros((b, *trailing), np.float32)
        return out

    runner = MultiHostRunner(
        mesh=mesh,
        params=initial_sv.params,
        score_fn=lambda p, b: model.apply(p, b)["prediction_node"],
        batch_templates=[template(b) for b in sorted(buckets)],
        param_loader=lambda version: load_version(version).params,
    )
    runner.version = initial
    if process_id != 0:
        return runner, None, None, None, None

    registry = ServableRegistry()
    # Pre-seed the initial version: the watcher's first poll must not
    # re-restore and re-broadcast what every process just loaded.
    registry.load(initial_sv)
    batcher = DynamicBatcher(
        buckets=runner.buckets, max_wait_us=max_wait_us, run_fn=runner.as_run_fn()
    ).start()
    impl = PredictionServiceImpl(registry, batcher)
    # Label-only reloads may re-state this source verbatim (deploy tools
    # replay their full config to flip a label); without this entry the
    # single-model reload gate reads the re-statement as a base-path MOVE
    # and rejects it FAILED_PRECONDITION — same wiring as build_stack's
    # --model-base-path mode.
    impl.served_sources[model_name] = (str(base_path), model_kind)

    watcher = VersionWatcher(
        base_path,
        registry,
        VersionWatcherConfig(
            poll_interval_s=poll_interval_s,
            model_name=model_name,
            model_kind=model_kind,
            max_load_attempts=max_load_attempts,
        ),
        loader=runner.watcher_loader(
            lambda version, path: filter_signatures(load_servable(path, host=True), version)
        ),
    ).start()
    return runner, registry, batcher, impl, watcher


def serve(argv=None) -> None:
    import argparse

    from .server import create_server

    parser = argparse.ArgumentParser(description="Multi-host TPU PredictionService")
    parser.add_argument("--model-base-path", required=True)
    parser.add_argument("--coordinator", help="process-0 address host:port (jax.distributed)")
    parser.add_argument("--num-processes", type=int, default=1)
    parser.add_argument("--process-id", type=int, default=0)
    parser.add_argument("--port", type=int, default=9999)
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--model-kind", default="dcn_v2",
                        help="only for SavedModel version dirs; native "
                        "checkpoints carry their architecture in the manifest")
    parser.add_argument("--model-name", default="DCN")
    parser.add_argument("--buckets", default="1024,8192",
                        help="comma-separated multihost bucket ladder")
    parser.add_argument("--model-parallel", type=int, default=1)
    parser.add_argument("--max-workers", type=int, default=32)
    parser.add_argument("--rest-port", type=int, default=0,
                        help="leader also serves the TF-Serving REST API "
                        "(:8501 surface) on this port")
    parser.add_argument("--ssl-config-file", dest="ssl_config_file",
                        help="secure the leader's gRPC port (SSLConfig "
                        "textproto, same format as the single-host CLI)")
    parser.add_argument("--file-system-poll-wait-seconds",
                        dest="file_system_poll_wait_seconds", type=float,
                        default=5.0,
                        help="version-watcher poll interval (upstream flag name)")
    parser.add_argument("--max-num-load-retries", dest="max_num_load_retries",
                        type=int, default=2,
                        help="retries AFTER the first load attempt "
                        "(upstream flag semantics)")
    args = parser.parse_args(argv)
    # Fail-fast like the single-host CLI: validate before slice init.
    credentials = None
    if args.ssl_config_file:
        from .server import load_ssl_credentials

        credentials = load_ssl_credentials(args.ssl_config_file)

    logging.basicConfig(level=logging.INFO)
    from ..utils.runtime import enable_compile_cache

    compile_cache = enable_compile_cache()
    runner, registry, batcher, impl, watcher = build_multihost_stack(
        args.model_base_path,
        args.coordinator,
        args.num_processes,
        args.process_id,
        model_kind=args.model_kind,
        model_name=args.model_name,
        buckets=tuple(int(b) for b in args.buckets.split(",")),
        model_parallel=args.model_parallel,
        poll_interval_s=args.file_system_poll_wait_seconds,
        max_load_attempts=args.max_num_load_retries + 1,  # upstream: retries
    )
    impl.compile_cache = compile_cache
    if args.process_id != 0:
        log.info("follower %d/%d up (mesh %s); serving until leader shutdown",
                 args.process_id, args.num_processes, dict(runner.mesh.shape))
        runner.follow()
        log.info("follower %d released", args.process_id)
        return

    from ..utils.metrics import ServerMetrics

    # ONE metrics instance across gRPC and REST (the monitoring-endpoint
    # aggregation contract, same as the single-host CLI).
    metrics = ServerMetrics()
    # create_server registers grpc.health.v1 alongside Prediction/Model
    # services: the leader answers standard health probes (and the fan-out
    # client's half-open probing) with per-model status — the initial
    # version is pre-seeded above, so "" reports SERVING from first bind.
    server, port = create_server(
        impl, f"{args.host}:{args.port}", args.max_workers, metrics,
        credentials=credentials,
    )
    server.start()
    if args.rest_port:
        from .server import start_rest_in_thread

        try:
            bound = start_rest_in_thread(impl, args.host, args.rest_port, metrics)
        except RuntimeError as exc:
            # Same teardown ORDER as the normal path: watcher first, so no
            # RELOAD broadcast can interleave with the slice shutdown.
            watcher.stop()
            server.stop(0)
            batcher.stop()
            runner.shutdown()
            raise SystemExit(str(exc)) from exc
        log.info("REST gateway on %s:%d (/v1/models/...)", args.host, bound)
    log.info("multihost PredictionService on %s:%d (mesh %s, version %s)",
             args.host, port, dict(runner.mesh.shape), runner.version)
    try:
        server.wait_for_termination()
    finally:
        watcher.stop()
        server.stop(2).wait()
        batcher.stop()
        runner.shutdown()


if __name__ == "__main__":
    serve()
