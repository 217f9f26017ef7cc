#!/usr/bin/env python
"""Elastic mesh serving tier-1 smoke (ISSUE 15): a CPU-safe,
self-contained gate asserting the [elastic] plane's contract end to end
on 8 emulated devices —

- under FORCED pressure (the overload plane's `pressure` fault site pins
  the state machine in BROWNOUT for a bounded number of ticks) the
  serving split switches UP (toward the data-parallel/throughput end),
  and after the fault exhausts and pressure recovers it switches DOWN
  (back toward the configured split): >= 1 switch in each direction;
- EVERY request across the whole stream — including those in flight
  during both switch windows — succeeds, and every score is
  BIT-IDENTICAL to a pinned-split reference stack serving the same
  checkpoint (the hitless contract);
- every ladder rung's executables were warmup-compiled BEFORE the stream
  (params placed per rung at load — the switch-never-compiles contract),
  and the drain barrier closed behind every switch (zero in-flight on
  every rung at the end);
- the `elastic` surfaces answer: mesh_stats()//meshz carries the elastic
  block with a populated switch history, and the dts_tpu_elastic_*
  Prometheus series pass tools/check_prom.py.

Prints one JSON line; exit 0 = gate passed. Run by tools/ci_tier1.sh
under TIER1_ELASTIC_SMOKE=1.
"""

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from distributed_tf_serving_tpu import faults  # noqa: E402
from distributed_tf_serving_tpu.models import (  # noqa: E402
    ModelConfig,
    Servable,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.serving import overload as overload_mod  # noqa: E402
from distributed_tf_serving_tpu.serving.server import build_stack  # noqa: E402
from distributed_tf_serving_tpu.train import Trainer  # noqa: E402
from distributed_tf_serving_tpu.train.checkpoint import save_servable  # noqa: E402
from distributed_tf_serving_tpu.utils.config import (  # noqa: E402
    ElasticConfig,
    MeshConfig,
    OverloadConfig,
    ServerConfig,
)
from distributed_tf_serving_tpu.utils.metrics import ServerMetrics  # noqa: E402

NUM_FIELDS = 8
MODEL_CFG = ModelConfig(
    name="DCN", num_fields=NUM_FIELDS, vocab_size=1 << 12, embed_dim=4,
    mlp_dims=(16,), num_cross_layers=1, compute_dtype="float32",
)
BUCKETS = (10, 50)  # not mesh-shaped: the divisibility pad rides along
TRAIN_STEPS = int(os.environ.get("SMOKE_TRAIN_STEPS", "40"))
STREAM_REQUESTS = int(os.environ.get("ELASTIC_SMOKE_REQUESTS", "400"))
PRESSURE_TICKS = int(os.environ.get("ELASTIC_SMOKE_PRESSURE_TICKS", "40"))


def _server_cfg() -> ServerConfig:
    return ServerConfig(
        model_kind="dcn_v2", model_name="DCN", num_fields=NUM_FIELDS,
        buckets=BUCKETS, max_wait_us=200, warmup=True,
    )


def _payloads():
    out = []
    for n, seed in ((7, 1), (33, 2), (50, 3)):
        rng = np.random.RandomState(seed)
        out.append({
            "feat_ids": rng.randint(
                0, 1 << 40, size=(n, NUM_FIELDS)
            ).astype(np.int64),
            "feat_wts": rng.rand(n, NUM_FIELDS).astype(np.float32),
        })
    return out


def _score(batcher, sv, payload):
    return np.asarray(
        batcher.submit(
            sv, dict(payload), output_keys=("prediction_node",)
        ).result(timeout=60)["prediction_node"]
    )


def main() -> dict:
    out = {"errors": [], "ok": False}

    trainer = Trainer(build_model("dcn_v2", MODEL_CFG), seed=0)
    train = trainer.fit(steps=TRAIN_STEPS, batch_size=256)
    out["train_loss"] = round(float(train["loss"]), 4)
    servable = Servable(
        name="DCN", version=1, model=trainer.model,
        params=trainer.snapshot_params(),
        signatures=ctr_signatures(NUM_FIELDS),
    )
    ckpt = os.path.join(tempfile.mkdtemp(prefix="elastic_smoke_"), "ckpt")
    save_servable(ckpt, servable, kind="dcn_v2")
    payloads = _payloads()

    # Phase A: PINNED-split reference ({data:4, model:2}, no elastic, no
    # overload) — the bit-identity anchor.
    _r1, b1, impl1, sv1, _m1, _w1 = build_stack(
        _server_cfg(), checkpoint=ckpt, model_config=MODEL_CFG,
        mesh_config=MeshConfig(enabled=True, devices=8, model_parallel=2),
    )
    try:
        reference = [_score(b1, sv1, p) for p in payloads]
    finally:
        b1.stop()

    # Phase B: the ELASTIC stack — same checkpoint, [mesh] {4,2} initial,
    # ladder {8,1}/{4,2}, overload plane armed with a fast tick so the
    # pinned pressure escalates (and recovers) inside the smoke window.
    _r2, b2, impl2, sv2, _m2, _w2 = build_stack(
        _server_cfg(), checkpoint=ckpt, model_config=MODEL_CFG,
        mesh_config=MeshConfig(enabled=True, devices=8, model_parallel=2),
        elastic_config=ElasticConfig(
            enabled=True, splits=("8x1", "4x2"),
            tick_interval_s=0.02, dwell_s=0.2,
            up_after_ticks=2, down_after_ticks=3,
            load_up_threshold=0.9, load_down_threshold=0.3,
        ),
        overload_config=OverloadConfig(
            enabled=True, adjust_interval_s=0.02,
            brownout_after_intervals=2, recover_after_intervals=3,
        ),
    )
    ctrl = impl2.elastic
    ex = ctrl.executor
    try:
        # The switch-never-compiles precondition: warmup placed params
        # (and compiled the serve variants) on EVERY rung before any
        # live traffic.
        warm = {
            f"{d}x{m}": len(ex._executors[(d, m)]._placed)
            for d, m in ex.splits
        }
        out["warm_placed_per_split"] = warm
        if any(v < 1 for v in warm.values()):
            out["errors"].append(f"ladder not fully warmed: {warm}")

        # Forced pressure escalation: the `pressure` fault site pins the
        # overload state machine in BROWNOUT for PRESSURE_TICKS ticks,
        # then exhausts — the state machine recovers on its own under
        # the stream's tiny queue waits.
        faults.get().add(
            "pressure", kind="error", code="BROWNOUT",
            count=PRESSURE_TICKS,
        )
        failures = 0
        mismatches = 0

        def settle(pending):
            nonlocal failures, mismatches
            idx, fut = pending.pop(0)
            try:
                got = np.asarray(
                    fut.result(timeout=60)["prediction_node"]
                )
                if not np.array_equal(got, reference[idx]):
                    mismatches += 1
            except Exception:  # noqa: BLE001 — the gate counts failures
                failures += 1

        # A RAMPED stream, one seeded payload cycle throughout: a heavy
        # phase (4 outstanding submits — switches land with real batches
        # in flight on the old split, so the drain barrier does real
        # work) while the pinned pressure escalates, then a light phase
        # (1-deep, spaced) once the up-switch fired, so the recovered
        # state machine + drained queue earn the down-switch.
        pending: list = []
        t0 = time.perf_counter()
        i = 0
        while i < STREAM_REQUESTS or (
            # Keep streaming until both directions fired (bounded).
            (ex.switches_up < 1 or ex.switches_down < 1)
            and time.perf_counter() - t0 < 60
        ):
            heavy = ex.switches_up < 1
            p = i % len(payloads)
            pending.append((p, b2.submit(
                sv2, dict(payloads[p]), output_keys=("prediction_node",)
            )))
            while len(pending) >= (4 if heavy else 1):
                settle(pending)
            i += 1
            if not heavy:
                time.sleep(0.005)  # light phase: idle queue at tick time
            elif i % 25 == 0:
                time.sleep(0.01)  # let the wall clock advance the ticks
        while pending:
            settle(pending)
        out["stream_requests"] = i
        out["stream_seconds"] = round(time.perf_counter() - t0, 2)
        out["failures"] = failures
        out["score_mismatches"] = mismatches
        if failures:
            out["errors"].append(f"{failures} requests failed mid-stream")
        if mismatches:
            out["errors"].append(
                f"{mismatches} responses diverged from the pinned-split "
                "reference"
            )

        snap = ex.elastic_snapshot()
        out["switches_up"] = snap["switches_up"]
        out["switches_down"] = snap["switches_down"]
        out["history"] = snap["history"][-6:]
        out["final_split"] = snap["current_split"]
        out["controller"] = snap["controller"]
        if snap["switches_up"] < 1:
            out["errors"].append("no up-switch under forced pressure")
        if snap["switches_down"] < 1:
            out["errors"].append("no down-switch after pressure recovery")
        stuck = {
            s: blk["in_flight"]
            for s, blk in snap["per_split"].items() if blk["in_flight"]
        }
        if stuck:
            out["errors"].append(f"drain barrier never closed: {stuck}")
        if snap["pending_drain_from"] is not None:
            out["errors"].append(
                f"switch drain still pending from {snap['pending_drain_from']}"
            )

        # Surfaces: the elastic block inside mesh_stats (what /meshz
        # serves) and a lint-clean dts_tpu_elastic_* exposition.
        ms = impl2.mesh_stats()
        if "elastic" not in (ms or {}):
            out["errors"].append("mesh_stats()//meshz lacks the elastic block")
        text = ServerMetrics().prometheus_text(
            b2.stats, mesh=ms, elastic=impl2.elastic_stats(),
        )
        out["prom_elastic_series"] = sum(
            1 for ln in text.splitlines()
            if ln.startswith("dts_tpu_elastic_") and not ln.startswith("#")
        )
        if out["prom_elastic_series"] < 10:
            out["errors"].append(
                f"only {out['prom_elastic_series']} dts_tpu_elastic_* series"
            )
        from check_prom import lint_text

        lint = lint_text(text)
        if lint:
            out["errors"].append(f"prom lint: {lint[:3]}")
    finally:
        faults.reset()
        b2.stop()
        overload_mod.deactivate()

    out["ok"] = not out["errors"]
    return out


if __name__ == "__main__":
    result = main()
    print(json.dumps(result))
    sys.exit(0 if result["ok"] else 1)
