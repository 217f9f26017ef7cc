#!/usr/bin/env python
"""Mesh serving A/B (ISSUE 13): single-chip vs data-parallel vs
data×model serving throughput of ONE process, printed as one JSON line.

Run standalone in one process, or in-process by bench.py's `mesh` block
(DTS_BENCH_MESH=1, real chips only). On a host with >= 2 chips this
measures real hardware (emulated=false). MESH_AB_FORCE_CPU=1 (or an
already-CPU environment) runs it on N emulated CPU devices instead:
a functional check whose rates say nothing about a chip (emulated=true).

Modes (all serving the SAME params through a DynamicBatcher, so the A/B
isolates the execution substrate, not the batching logic):

- ``single``:      the default single-chip jitted path (run_fn=None);
- ``data``:        ShardedExecutor over an {N, 1} mesh (pure candidate
                   sharding — the reference's layout, on-mesh);
- ``data_model``:  ShardedExecutor over an {N/2, 2} mesh (candidate
                   sharding × vocab-sharded embedding tables).

Gate: every mode must score the probe payloads BIT-IDENTICALLY (f32
compute); per-mode closed-loop throughput rides along as the measurement.
"""

import json
import os
import sys
import time

# Backend selection must happen BEFORE importing jax, and it must NOT
# default to CPU: on a host with chips this measures real hardware. Only an
# explicit emulation request (MESH_AB_FORCE_CPU=1) or an already-CPU
# environment gives the emulated N-device mesh.
_need = int(os.environ.get("MESH_AB_DEVICES", "8"))
if os.environ.get("MESH_AB_FORCE_CPU") == "1":
    os.environ["JAX_PLATFORMS"] = "cpu"
if os.environ.get("JAX_PLATFORMS") == "cpu":
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + f" --xla_force_host_platform_device_count={_need}"
        ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from distributed_tf_serving_tpu.client import make_payload  # noqa: E402
from distributed_tf_serving_tpu.models import (  # noqa: E402
    ModelConfig,
    Servable,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.parallel import (  # noqa: E402
    ShardedExecutor,
    make_mesh,
)
from distributed_tf_serving_tpu.serving.batcher import DynamicBatcher  # noqa: E402
from distributed_tf_serving_tpu.utils.runtime import enable_compile_cache  # noqa: E402

NUM_FIELDS = int(os.environ.get("MESH_AB_FIELDS", "16"))
CANDIDATES = int(os.environ.get("MESH_AB_CANDIDATES", "512"))
WINDOW_S = float(os.environ.get("MESH_AB_WINDOW_S", "4"))
BUCKETS = (256, 1024)


def _mode_run(servable, run_fn, payloads, probes):
    """One mode: warm, score the probe payloads, then a closed-loop
    throughput window driven straight at the batcher (4 outstanding
    submits — the substrate A/B wants device-path rate, not RPC plumbing
    that is identical across modes)."""
    batcher = DynamicBatcher(
        buckets=BUCKETS, max_wait_us=200, run_fn=run_fn
    ).start()
    try:
        batcher.warmup(servable)
        scores = [
            np.asarray(
                batcher.submit(servable, p).result(timeout=120)["prediction_node"]
            )
            for p in probes
        ]
        inflight = []
        done = 0
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < WINDOW_S:
            while len(inflight) < 4:
                inflight.append(
                    batcher.submit(servable, payloads[i % len(payloads)])
                )
                i += 1
            inflight.pop(0).result(timeout=120)
            done += 1
        for f in inflight:
            f.result(timeout=120)
            done += 1
        wall = time.perf_counter() - t0
        return scores, {
            "requests": done,
            "qps": round(done / wall, 2),
            "candidates_per_s": round(done * CANDIDATES / wall, 0),
            "window_s": round(wall, 2),
        }
    finally:
        batcher.stop()


def main() -> dict:
    out = {
        "device": str(jax.devices()[0]),
        "devices_visible": len(jax.devices()),
        "emulated": jax.default_backend() == "cpu",
        "modes": {},
        "errors": [],
    }
    n = len(jax.devices())
    if n < 2:
        out["errors"].append(f"need >= 2 devices, have {n}")
        out["ok"] = False
        return out
    cfg = ModelConfig(
        name="DCN", num_fields=NUM_FIELDS, vocab_size=1 << 14, embed_dim=8,
        mlp_dims=(64, 32), num_cross_layers=2, compute_dtype="float32",
    )
    model = build_model("dcn_v2", cfg)
    servable = Servable(
        name="DCN", version=1, model=model,
        params=jax.jit(model.init)(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(NUM_FIELDS),
    )
    payloads = [
        make_payload(candidates=CANDIDATES, num_fields=NUM_FIELDS, seed=s)
        for s in range(4)
    ]
    probes = [
        make_payload(candidates=c, num_fields=NUM_FIELDS, seed=100 + c)
        for c in (37, 200)  # deliberately not mesh-shaped: pad exercised
    ]
    mp = 2 if n % 2 == 0 else 1
    modes = {
        "single": None,
        "data": make_mesh(n, model_parallel=1),
        "data_model": make_mesh(n, model_parallel=mp) if mp > 1 else None,
    }
    reference = None
    for name, mesh in modes.items():
        if name != "single" and mesh is None:
            continue
        run_fn = ShardedExecutor(mesh) if mesh is not None else None
        scores, block = _mode_run(servable, run_fn, payloads, probes)
        if mesh is not None:
            block["mesh"] = {str(k): int(v) for k, v in mesh.shape.items()}
            block["executor"] = run_fn.snapshot()["executor"]
        if reference is None:
            reference = scores
            block["bit_identical_to_single"] = True
        else:
            same = all(np.array_equal(a, b) for a, b in zip(reference, scores))
            block["bit_identical_to_single"] = same
            if not same:
                deltas = [
                    float(np.max(np.abs(a - b)))
                    for a, b in zip(reference, scores)
                ]
                out["errors"].append(
                    f"{name}: scores != single-chip (max deltas {deltas})"
                )
        out["modes"][name] = block
    out["bit_identical"] = all(
        b.get("bit_identical_to_single") for b in out["modes"].values()
    )
    out["ok"] = not out["errors"] and out["bit_identical"]
    return out


if __name__ == "__main__":
    enable_compile_cache()
    result = main()
    print(json.dumps(result))
    sys.exit(0 if result.get("ok") else 1)
