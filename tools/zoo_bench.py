#!/usr/bin/env python
"""Per-family device benchmark at the reference ecosystem's config points.

BASELINE.json lists five benchmark configs the framework must cover
(Wide&Deep 128-candidate, DeepFM 512, DCN-v2 1k, two-tower 10k retrieval,
DLRM 4k embedding-heavy). The headline bench (bench.py) drives the full
gRPC stack on the flagship DCN-v2 only; this tool measures the pure device
step for EVERY zoo family at its own workload point — the per-family
roofline the serving layer sits on. Timing method shared with bench.py:
steps chained inside one jitted fori_loop so host dispatch jitter cannot
contaminate the number (see bench.device_loop_step_s).

Run on the TPU (or JAX_PLATFORMS=cpu for a smoke):
    python tools/zoo_bench.py [--out ZOO_BENCH.json]
Prints one JSON line per family plus a `summary` line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", help="also write the results to this JSON file")
    parser.add_argument("--iters", type=int, default=0,
                        help="override estimate iters (0 = auto per platform)")
    args = parser.parse_args(argv)

    import jax
    import numpy as np

    from bench import device_loop_step_s, flops_per_example, peak_flops_for

    from distributed_tf_serving_tpu.models import ModelConfig, build_model
    from distributed_tf_serving_tpu.serving.batcher import fold_ids_host
    from distributed_tf_serving_tpu.utils.runtime import enable_compile_cache

    enable_compile_cache()
    device = str(jax.devices()[0])
    device_kind = jax.devices()[0].device_kind
    tpu = jax.devices()[0].platform != "cpu"
    est, tgt = (args.iters or (100 if tpu else 4)), (0.12 if tpu else 0.01)

    # (family, candidates/batch, config) — the BASELINE.json config points.
    POINTS = [
        ("wide_deep", 128, ModelConfig(name="WD", num_fields=43)),
        ("deepfm", 512, ModelConfig(name="DeepFM", num_fields=39)),  # Criteo: 39 cat fields
        ("dcn_v2", 1024, ModelConfig(name="DCN", num_fields=43)),
        ("two_tower", 10240, ModelConfig(name="TT", num_fields=43, num_user_fields=8)),
        ("dlrm", 4096, ModelConfig(name="DLRM", num_fields=26, num_dense_features=13)),
    ]
    if not tpu:  # smoke: shrink the tables, keep the shapes' structure
        import dataclasses as dc

        POINTS = [
            (k, min(n, 512), dc.replace(
                c, vocab_size=1 << 14, embed_dim=4,
                # DLRM requires bottom_mlp_dims[-1] == embed_dim
                bottom_mlp_dims=(16, 4) if k == "dlrm" else c.bottom_mlp_dims,
            ))
            for k, n, c in POINTS
        ]

    results = []
    rng = np.random.RandomState(0)
    for kind, n, config in POINTS:
        t0 = time.perf_counter()
        model = build_model(kind, config)
        params = jax.jit(model.init)(jax.random.PRNGKey(0))
        jax.block_until_ready(params)
        batch = {
            "feat_ids": fold_ids_host(
                rng.randint(0, 1 << 40, size=(n, config.num_fields)), config.vocab_size
            ),
            "feat_wts": rng.rand(n, config.num_fields).astype(np.float32),
        }
        if kind == "dlrm":
            batch["dense_features"] = rng.rand(n, config.num_dense_features).astype(np.float32)
        dev = {k: jax.device_put(v) for k, v in batch.items()}
        jax.block_until_ready(dev)
        apply = jax.jit(model.apply)

        import jax.numpy as jnp

        def step(b, apply=apply, params=params):
            out = apply(params, b)
            eps = jnp.min(out["prediction_node"]) * 1e-30
            return {
                k: (v + eps.astype(v.dtype) if k == "feat_wts" else v)
                for k, v in b.items()
            }

        step_s = device_loop_step_s(step, dev, est, tgt)
        line = {
            "family": kind,
            "batch": n,
            # None = degenerate reading (a stall spanned the min-of-2
            # walls); recorded as null rather than crashing the sweep.
            "device_step_us": None if step_s is None else round(step_s * 1e6, 1),
            "examples_per_s": None if step_s is None else round(n / step_s, 0),
            "qps_1k_equiv": None if step_s is None else round(n / 1000 / step_s, 1),
            "setup_s": round(time.perf_counter() - t0, 1),
        }
        if tpu and kind == "dcn_v2" and step_s:
            # An accelerator metric; a kind the peak table lacks raises.
            peak = peak_flops_for(device_kind)
            line["mfu"] = round(flops_per_example(config) * n / step_s / peak, 4)
        results.append(line)
        print(json.dumps(line), flush=True)

    summary = {
        "summary": True,
        "device": device,
        "families": {r["family"]: r["device_step_us"] for r in results},
    }
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"device": device, "results": results}, f, indent=1)


if __name__ == "__main__":
    main()
