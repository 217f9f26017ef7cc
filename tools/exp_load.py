#!/usr/bin/env python
"""Load-shape experiment: sweep client concurrency against the real serving
stack and report QPS / p50 / host-CPU utilization per point.

Decides one tuning question: is the stack Little's-law latency-bound (QPS
scales with concurrency) or host-CPU-bound (QPS flat, CPU util ~1.0)? Run
directly; not part of the bench contract.
"""

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CANDIDATES = 1000
NUM_FIELDS = 43


def main() -> None:
    import jax

    from distributed_tf_serving_tpu.client import (
        ShardedPredictClient,
        make_payload,
        run_closed_loop,
    )
    from distributed_tf_serving_tpu.models import (
        ModelConfig,
        Servable,
        ServableRegistry,
        build_model,
        ctr_signatures,
    )
    from distributed_tf_serving_tpu.serving import DynamicBatcher, PredictionServiceImpl
    from distributed_tf_serving_tpu.serving.server import create_server

    platform = jax.devices()[0].platform
    tpu = platform != "cpu"
    print(f"[exp] device={jax.devices()[0]} platform={platform}", file=sys.stderr)

    config = ModelConfig(
        name="DCN", num_fields=NUM_FIELDS, vocab_size=1 << 20, embed_dim=16,
        mlp_dims=(256, 128, 64), num_cross_layers=3, cross_full_matrix=True,
    )
    model = build_model("dcn_v2", config)
    params = model.init(jax.random.PRNGKey(0))
    registry = ServableRegistry()
    ladder = (32, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384, 32768)
    top = int(os.environ.get("EXP_TOP_BUCKET", "8192"))
    batcher = DynamicBatcher(
        buckets=tuple(b for b in ladder if b <= top),
        max_wait_us=int(os.environ.get("EXP_MAX_WAIT_US", "2000")),
        completion_workers=12,
        queue_capacity_candidates=32 * top,
    ).start()
    impl = PredictionServiceImpl(registry, batcher)
    servable = Servable(name="DCN", version=1, model=model, params=params,
                        signatures=ctr_signatures(config.num_fields))
    registry.load(servable)
    for b in (1024, 2048, 4096, 8192, 16384, 32768):
        if b > top:
            continue
        t0 = time.perf_counter()
        batcher.warmup(servable, buckets=(b,))
        print(f"[exp] warm bucket={b} {time.perf_counter()-t0:.1f}s", file=sys.stderr)

    si = float(os.environ.get("EXP_SWITCH_INTERVAL", "0"))
    if si > 0:
        sys.setswitchinterval(si)
    from distributed_tf_serving_tpu.utils.tracing import request_trace
    request_trace.reset()  # warmup compiles out of the phase means
    concs = [int(x) for x in os.environ.get("EXP_CONCS", "48,64,96,128,160").split(",")]
    use_aio = os.environ.get("EXP_AIO", "0") == "1"
    channels = int(os.environ.get("EXP_CHANNELS", "6"))
    payload = make_payload(candidates=CANDIDATES, num_fields=NUM_FIELDS)
    results = []

    # EXP_COMPACT=1: the framework-native wire (client-side fold + bf16,
    # half the bytes, bit-identical scores) — an A/B knob composable with
    # EXP_UNIQUE. DTS_TPU_NO_FUSED=1 (batcher env) isolates
    # the native fused pack in the same sweeps.
    compact = os.environ.get("EXP_COMPACT", "0") == "1"
    if compact:
        from distributed_tf_serving_tpu.client import compact_payload

        payload = compact_payload(payload, config.vocab_size)
    pool = None
    if os.environ.get("EXP_UNIQUE", "0") == "1":
        pool = [
            make_payload(candidates=CANDIDATES, num_fields=NUM_FIELDS, seed=100 + i)
            for i in range(128)
        ]
        if compact:
            pool = [compact_payload(p, config.vocab_size) for p in pool]

    async def sweep(port: int):
        import dataclasses

        for conc in concs:
            # Size each point to ~10 s assuming ~500 qps upper bound.
            rpw = max(2, int((10.0 * 550) / conc)) if tpu else 3
            before = dataclasses.replace(batcher.stats)
            async with ShardedPredictClient(
                [f"127.0.0.1:{port}"], "DCN", channels_per_host=channels
            ) as client:
                cpu0, wall0 = time.process_time(), time.perf_counter()
                report = await run_closed_loop(
                    client, payload, concurrency=conc, requests_per_worker=rpw,
                    sort_scores=True, warmup_requests=5,
                    payload_pool=pool,
                    prepared=(pool is None)
                    and os.environ.get("EXP_PREPARED", "0") == "1",
                )
                cpu1, wall1 = time.process_time(), time.perf_counter()
            s = report.summary()
            stats = batcher.stats
            # Per-point counters (lifetime cumulative would blend the
            # previous concurrency points into every later one).
            d_req = stats.requests - before.requests
            d_batches = stats.batches - before.batches
            d_cand = stats.candidates - before.candidates
            d_padded = stats.padded_candidates - before.padded_candidates
            point = {
                "server": "aio" if use_aio else "threads",
                "compact": compact,
                "fused_off": os.environ.get("DTS_TPU_NO_FUSED") == "1",
                "concurrency": conc,
                "qps": round(s["qps"], 1),
                "p50_ms": round(s["p50_ms"], 1),
                "p99_ms": round(s["p99_ms"], 1),
                "requests": s["requests"],
                "wall_s": round(s["wall_s"], 1),
                "cpu_util": round((cpu1 - cpu0) / (wall1 - wall0), 3),
                "requests_per_batch": round(d_req / d_batches, 2) if d_batches else 0.0,
                "occupancy": round(d_cand / d_padded, 3) if d_padded else 0.0,
            }
            point["phases_us"] = {
                name: snap["mean_us"]
                for name, snap in request_trace.snapshot().items()
            }
            request_trace.reset()
            results.append(point)
            print(f"[exp] {json.dumps(point)}", file=sys.stderr)

    profile = os.environ.get("EXP_PROFILE", "0") == "1"
    if use_aio:
        from distributed_tf_serving_tpu.serving.server import create_server_async

        async def run_all():
            server, port = create_server_async(impl, "127.0.0.1:0")
            await server.start()
            try:
                if profile:
                    import cProfile
                    import pstats

                    prof = cProfile.Profile()
                    prof.enable()
                    await sweep(port)
                    prof.disable()
                    stats = pstats.Stats(prof, stream=sys.stderr)
                    stats.sort_stats("cumulative").print_stats(45)
                    stats.sort_stats("tottime").print_stats(45)
                else:
                    await sweep(port)
            finally:
                await server.stop(0)

        asyncio.run(run_all())
    else:
        server, port = create_server(impl, "127.0.0.1:0", max_workers=max(concs) + 8)
        server.start()
        asyncio.run(sweep(port))
        server.stop(0)
    batcher.stop()
    print(json.dumps(results))


if __name__ == "__main__":
    main()
