#!/usr/bin/env python
"""Deterministic host-CPU profile of the serving data plane.

HOST CPU PER REQUEST is a quantity that does not depend on the device at
all, and it bounds what one host core can serve whatever the chip does.
This harness measures it on the CPU platform, where it is reproducible to
a few percent.

Design choices that make the number honest:
- tiny model (8-dim embed, (16,) mlp) so XLA compute does not swamp the
  host path; the WIRE shape stays the flagship point (1k candidates x 43
  int64+f32 fields) so decode/pad/digest/encode costs are the real ones.
- cProfile wraps the one event loop carrying client+server+grpc-python;
  the batcher thread is profiled separately via its own profiler hook.
- os.times() deltas split Python-attributed CPU from C-core/XLA threads.
- a HostStackSampler (serving/utilization.py — the SAME sampler the
  on-demand POST /profilez/start capture runs) samples every thread's
  Python stack through the run, so the per-THREAD hot stacks ride the
  JSON line next to the cProfile totals. One implementation, two
  surfaces: this offline harness and the live endpoint cannot drift.

Outputs one JSON line: cpu_ms_per_request (the figure of merit), the
per-thread split, the sampled host_stacks block, and top cumulative
Python costs.
"""

import asyncio
import cProfile
import io
import json
import os
import pstats
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
    from distributed_tf_serving_tpu.serving.server import create_server_async
    from distributed_tf_serving_tpu.utils.tracing import request_trace
    from distributed_tf_serving_tpu import native

    native.ensure()  # the serving steady state has the native lib loaded

    requests = int(os.environ.get("PROF_REQUESTS", "1500"))
    concurrency = int(os.environ.get("PROF_CONCURRENCY", "32"))
    unique = os.environ.get("PROF_UNIQUE", "0") == "1"
    compact = os.environ.get("PROF_COMPACT", "0") == "1"
    prepared = not unique

    config = ModelConfig(
        name="DCN", num_fields=NUM_FIELDS, vocab_size=1 << 14, embed_dim=8,
        mlp_dims=(16,), num_cross_layers=1, cross_full_matrix=True,
    )
    model = build_model("dcn_v2", config)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    registry = ServableRegistry()
    # PROF_NULL_DEVICE=1 injects a no-op run_fn: on the CPU platform the
    # XLA forward shares the one core with the data plane and swamps A/B
    # comparisons (readback ~70 ms/batch); nulling it measures the pure
    # host data plane — decode/batch/pack/encode/transport — which is the
    # quantity that transfers to a host with a chip.
    null_device = os.environ.get("PROF_NULL_DEVICE", "0") == "1"
    # PROF_DEVICE_DELAY_MS stalls the batcher thread that long per batch
    # (sleep drops the GIL like a real transfer wait): coalescing then
    # fills batches to device-like requests_per_batch, where per-BATCH host
    # costs (generic pad vs fused pack) become visible. Applied on the
    # REAL dispatch path below — a null-device run_fn would disable the
    # input cache and the fused path entirely (batcher run_fn contract),
    # so the combination is rejected rather than silently measuring the
    # wrong thing.
    delay_s = float(os.environ.get("PROF_DEVICE_DELAY_MS", "0")) / 1e3
    if delay_s and null_device:
        raise SystemExit(
            "PROF_DEVICE_DELAY_MS requires the real dispatch path; "
            "unset PROF_NULL_DEVICE (run_fn disables cache + fused pack)"
        )
    run_fn = None
    if null_device:
        import numpy as _np

        def run_fn(servable, arrays):
            n = next(iter(arrays.values())).shape[0]
            return {"prediction_node": _np.zeros(n, _np.float32)}

    batcher = DynamicBatcher(
        buckets=(1024, 2048, 4096, 8192),
        max_wait_us=2000,
        completion_workers=4,
        run_fn=run_fn,
    ).start()
    if delay_s:
        # Stall both dispatch paths identically so the A/B isolates the
        # host-side assembly cost, not the stall.
        orig_exec = batcher._execute
        orig_fused = batcher._execute_fused

        def slow_exec(sv, arrays, *args, **kwargs):
            time.sleep(delay_s)
            return orig_exec(sv, arrays, *args, **kwargs)

        def slow_fused(ctx, bucket, *args, **kwargs):
            time.sleep(delay_s)
            return orig_fused(ctx, bucket, *args, **kwargs)

        batcher._execute = slow_exec
        batcher._execute_fused = slow_fused
    servable = Servable(
        name="DCN", version=1, model=model, params=params,
        signatures=ctr_signatures(NUM_FIELDS),
    )
    registry.load(servable)
    for b in (1024, 2048, 4096, 8192):
        batcher.warmup(servable, buckets=(b,))
    impl = PredictionServiceImpl(registry, batcher)

    payload = make_payload(candidates=CANDIDATES, num_fields=NUM_FIELDS)
    pool = (
        [make_payload(candidates=CANDIDATES, num_fields=NUM_FIELDS, seed=100 + i)
         for i in range(64)]
        if unique else None
    )
    if compact:
        from distributed_tf_serving_tpu.client import compact_payload

        payload = compact_payload(payload, config.vocab_size)
        if pool:
            pool = [compact_payload(p, config.vocab_size) for p in pool]

    async def drive():
        server, port = create_server_async(impl, "127.0.0.1:0")
        await server.start()
        try:
            async with ShardedPredictClient(
                [f"127.0.0.1:{port}"], "DCN", channels_per_host=3
            ) as client:
                return await run_closed_loop(
                    client, payload,
                    concurrency=concurrency,
                    requests_per_worker=requests // concurrency,
                    sort_scores=True,
                    warmup_requests=5,
                    payload_pool=pool,
                    prepared=prepared,
                )
        finally:
            await server.stop(0)

    from distributed_tf_serving_tpu.serving.utilization import HostStackSampler

    request_trace.reset()
    t0_wall = time.perf_counter()
    t0 = os.times()
    sampler = HostStackSampler(
        interval_s=float(os.environ.get("PROF_SAMPLE_INTERVAL_S", "0.02"))
    ).start()
    prof = cProfile.Profile()
    prof.enable()
    report = asyncio.run(drive())
    prof.disable()
    stacks = sampler.stop()
    t1 = os.times()
    wall = time.perf_counter() - t0_wall

    n = report.requests
    user, system = t1.user - t0.user, t1.system - t0.system
    out = io.StringIO()
    stats = pstats.Stats(prof, stream=out)
    stats.sort_stats("cumulative").print_stats(45)
    top = out.getvalue()

    line = {
        "mode": ("unique" if unique else "repeated_prepared")
                + ("_compact" if compact else "")
                + ("_nulldev" if null_device else ""),
        "requests": n,
        "wall_s": round(wall, 2),
        "qps": round(n / wall, 1),
        "cpu_user_s": round(user, 2),
        "cpu_system_s": round(system, 2),
        "cpu_util": round((user + system) / wall, 3),
        "cpu_ms_per_request": round((user + system) / n * 1e3, 3),
        "phases_us": {
            k: v["mean_us"] for k, v in request_trace.snapshot().items()
        },
        "batcher": {
            "requests_per_batch": round(batcher.stats.mean_requests_per_batch, 2),
            "batches": batcher.stats.batches,
        },
        # Sampled per-thread hot stacks (top 3 per thread, by sample
        # count): where each thread actually SPENDS its time — the
        # attribution cProfile's single-thread view cannot give.
        "host_stacks": {
            "samples": stacks["samples"],
            "interval_s": stacks["interval_s"],
            "threads": {
                name: entries[:3]
                for name, entries in stacks["threads"].items()
            },
        },
    }
    batcher.stop()
    print(json.dumps(line))
    print(top, file=sys.stderr)


if __name__ == "__main__":
    main()
