#!/usr/bin/env python
"""Mesh-serving tier-1 smoke (ISSUE 13): a CPU-safe, self-contained gate
asserting the [mesh] serving mode's contract end to end over REAL gRPC on
8 emulated devices —

- the SAME trained model served single-chip and over a {data: 4, model: 2}
  mesh returns BIT-IDENTICAL scores for the same requests;
- arbitrary bucket sizes are accepted (the bucket ladder is deliberately
  NOT mesh-shaped, so the data-axis divisibility pad is exercised and its
  counters move);
- the client's per-shard health/deadline semantics are unchanged over the
  new mode (same fan-out client, a deadline-bounded call still answers);
- the live `mesh` monitoring block and the dts_tpu_mesh_* Prometheus
  series answer over HTTP, with per-device occupancy attribution when the
  utilization ledger rides along.

Prints one JSON line; exit 0 = gate passed. Run in tier-1 by
tests/test_tool_smokes.py.
"""

import asyncio
import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from distributed_tf_serving_tpu.client import (  # noqa: E402
    ShardedPredictClient,
    make_payload,
)
from distributed_tf_serving_tpu.models import (  # noqa: E402
    ModelConfig,
    Servable,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.serving.server import (  # noqa: E402
    build_stack,
    create_server,
    start_rest_in_thread,
)
from distributed_tf_serving_tpu.train import Trainer  # noqa: E402
from distributed_tf_serving_tpu.train.checkpoint import save_servable  # noqa: E402
from distributed_tf_serving_tpu.utils.config import (  # noqa: E402
    MeshConfig,
    ServerConfig,
    UtilizationConfig,
)
from distributed_tf_serving_tpu.utils.metrics import ServerMetrics  # noqa: E402

NUM_FIELDS = 8
MODEL_CFG = ModelConfig(
    name="DCN", num_fields=NUM_FIELDS, vocab_size=1 << 12, embed_dim=4,
    mlp_dims=(16,), num_cross_layers=1, compute_dtype="float32",
)
# Deliberately NOT mesh-shaped (10 and 50 are not multiples of the data
# axis 4): the divisibility pad must absorb them.
BUCKETS = (10, 50)
TRAIN_STEPS = int(os.environ.get("SMOKE_TRAIN_STEPS", "40"))


def _server_cfg() -> ServerConfig:
    return ServerConfig(
        model_kind="dcn_v2", model_name="DCN", num_fields=NUM_FIELDS,
        buckets=BUCKETS, max_wait_us=200, warmup=True,
    )


async def _score_over_grpc(impl, payloads, deadline_s=5.0):
    server, port = create_server(impl, "127.0.0.1:0")
    server.start()
    try:
        async with ShardedPredictClient(
            [f"127.0.0.1:{port}"], "DCN", timeout_s=deadline_s,
        ) as client:
            return [np.asarray(await client.predict(p)) for p in payloads]
    finally:
        server.stop(0).wait()


async def _probe_http(port: int, out: dict) -> None:
    import aiohttp

    async with aiohttp.ClientSession() as sess:
        async with sess.get(
            f"http://127.0.0.1:{port}/monitoring?section=mesh"
        ) as resp:
            body = await resp.json()
            out["mesh_block"] = body.get("mesh")
        async with sess.get(
            f"http://127.0.0.1:{port}/monitoring/prometheus/metrics"
        ) as resp:
            out["prom_text"] = await resp.text()


def _prom_route_probe(impl, metrics, out):
    """Serve the REST gateway briefly and probe the live mesh surfaces."""
    port = start_rest_in_thread(impl, "127.0.0.1", 0, metrics)
    asyncio.run(_probe_http(port, out))


def main() -> dict:
    out = {"errors": [], "bit_identical": None}

    # One trained model, served by both stacks from the same checkpoint.
    trainer = Trainer(build_model("dcn_v2", MODEL_CFG), seed=0)
    train = trainer.fit(steps=TRAIN_STEPS, batch_size=256)
    out["train_loss"] = round(float(train["loss"]), 4)
    servable = Servable(
        name="DCN", version=1, model=trainer.model,
        params=trainer.snapshot_params(),
        signatures=ctr_signatures(NUM_FIELDS),
    )
    ckpt = os.path.join(tempfile.mkdtemp(prefix="mesh_smoke_"), "ckpt")
    save_servable(ckpt, servable, kind="dcn_v2")

    payloads = [
        make_payload(candidates=n, num_fields=NUM_FIELDS, seed=s)
        for n, s in ((7, 1), (33, 2), (50, 3))
    ]

    # Phase A: single-chip serving over real gRPC.
    _r1, batcher1, impl1, _sv1, mesh1, _w1 = build_stack(
        _server_cfg(), checkpoint=ckpt, model_config=MODEL_CFG,
    )
    try:
        single = asyncio.run(_score_over_grpc(impl1, payloads))
    finally:
        batcher1.stop()
    if mesh1 is not None:
        out["errors"].append("single-chip stack unexpectedly built a mesh")

    # Phase B: the {data: 4, model: 2} mesh mode, utilization riding
    # along for the per-device attribution surface.
    _r2, batcher2, impl2, _sv2, mesh2, _w2 = build_stack(
        _server_cfg(), checkpoint=ckpt, model_config=MODEL_CFG,
        mesh_config=MeshConfig(enabled=True, devices=8, model_parallel=2),
        utilization_config=UtilizationConfig(enabled=True),
    )
    metrics = ServerMetrics()
    try:
        if mesh2 is None or dict(mesh2.shape) != {"data": 4, "model": 2}:
            out["errors"].append(f"mesh shape wrong: {mesh2 and dict(mesh2.shape)}")
        meshed = asyncio.run(_score_over_grpc(impl2, payloads))
        out["bit_identical"] = all(
            np.array_equal(a, b) for a, b in zip(single, meshed)
        )
        if not out["bit_identical"]:
            deltas = [
                float(np.max(np.abs(a - b))) for a, b in zip(single, meshed)
            ]
            out["errors"].append(f"mesh scores != single-chip (max deltas {deltas})")

        # Deadline semantics unchanged over the mesh: a tightly-bounded
        # call still answers inside its budget.
        fast = asyncio.run(_score_over_grpc(impl2, payloads[:1], deadline_s=5.0))
        if not np.array_equal(fast[0], single[0]):
            out["errors"].append("deadline-bounded mesh call scored differently")

        snap = impl2.mesh_stats()
        out["mesh_stats"] = {
            "shape": snap["shape"],
            "devices": len(snap["devices"]),
            "executor": snap["executor"],
            "per_device": len(snap.get("per_device") or {}),
        }
        ex = snap["executor"]
        if not ex["pad_batches"] or not ex["data_pad_rows"]:
            out["errors"].append(
                f"divisibility pad never exercised: {ex} (bucket ladder "
                f"{BUCKETS} over data axis 4 must pad)"
            )
        if ex["layout"].get("DCN") != "rules:dcn_v2":
            out["errors"].append(f"named partition rules not used: {ex['layout']}")
        if len(snap.get("per_device") or {}) != 8:
            out["errors"].append("per-device occupancy attribution missing")

        # Live HTTP surfaces: the `mesh` monitoring block + Prometheus.
        _prom_route_probe(impl2, metrics, out)
        blk = (out.get("mesh_block") or {})
        if (blk.get("shape") or {}) != {"data": 4, "model": 2}:
            out["errors"].append(f"/monitoring?section=mesh wrong: {blk}")
        prom = out.pop("prom_text", "")
        needed = (
            "dts_tpu_mesh_devices 8",
            "dts_tpu_mesh_data_parallel 4",
            "dts_tpu_mesh_model_parallel 2",
            "dts_tpu_mesh_pad_batches_total",
            "dts_tpu_mesh_device_busy_fraction{",
        )
        missing = [m for m in needed if m not in prom]
        if missing:
            out["errors"].append(f"Prometheus mesh series missing: {missing}")
        out["prom_mesh_series"] = sum(
            1 for ln in prom.splitlines()
            if ln.startswith("dts_tpu_mesh_") and not ln.startswith("#")
        )
    finally:
        batcher2.stop()

    out["ok"] = not out["errors"] and bool(out["bit_identical"])
    return out


if __name__ == "__main__":
    result = main()
    print(json.dumps(result))
    sys.exit(0 if result["ok"] else 1)
