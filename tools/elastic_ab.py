#!/usr/bin/env python
"""Elastic mesh serving A/B (ISSUE 15): pinned-split vs elastic
serving of the SAME seeded ramped stream, printed as one JSON line.

Run standalone in one process, or in-process by bench.py's `elastic`
block (DTS_BENCH_ELASTIC=1, real chips only). On a host with chips this
measures real hardware (emulated=false). ELASTIC_AB_FORCE_CPU=1 (or an
already-CPU environment) runs it on N emulated CPU devices instead: a
functional check, never a throughput claim (emulated=true).

The stream is three pressure phases over one seeded payload cycle, both
runs replaying the SAME schedule:

- ``nominal``   light load (1 outstanding, spaced) — the latency regime;
- ``pressure``  saturating load (8 outstanding, large candidates) with
                the overload plane's queue-wait target set low, so the
                state machine escalates ORGANICALLY (no fault pin);
- ``recovery``  light again — the controller must come back down.

Pinned run: a static ShardedExecutor at {N/2, 2} (the [mesh] default
rung). Elastic run: the {N,1}/{N/2,2} ladder starting at {N/2,2} with an
ElasticController on the same overload signal. Reported per phase:
goodput (completed/s), refusals, p50 latency, the pressure state and the
serving split at phase end — plus the switch history, the first
post-switch request latency next to the steady p50 (the
no-serving-path-compile evidence: every rung was warmup-compiled), and a
bit-identity probe across both runs.
"""

import json
import os
import sys
import time

_need = int(os.environ.get("ELASTIC_AB_DEVICES", "8"))
if os.environ.get("ELASTIC_AB_FORCE_CPU") == "1":
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

from distributed_tf_serving_tpu.models import (  # noqa: E402
    ModelConfig,
    Servable,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.parallel import (  # noqa: E402
    ElasticController,
    ElasticMeshExecutor,
    ShardedExecutor,
    make_mesh,
)
from distributed_tf_serving_tpu.serving import overload as overload_mod  # noqa: E402
from distributed_tf_serving_tpu.serving.batcher import DynamicBatcher  # noqa: E402
from distributed_tf_serving_tpu.utils.config import (  # noqa: E402
    ElasticConfig,
    OverloadConfig,
)
from distributed_tf_serving_tpu.utils.runtime import enable_compile_cache  # noqa: E402

NUM_FIELDS = int(os.environ.get("ELASTIC_AB_FIELDS", "16"))
HEAVY_CANDIDATES = int(os.environ.get("ELASTIC_AB_CANDIDATES", "512"))
LIGHT_CANDIDATES = 64
BUCKETS = (64, 512)
PHASES = (
    ("nominal", float(os.environ.get("ELASTIC_AB_NOMINAL_S", "2")), 1),
    ("pressure", float(os.environ.get("ELASTIC_AB_PRESSURE_S", "4")), 8),
    ("recovery", float(os.environ.get("ELASTIC_AB_RECOVERY_S", "4")), 1),
)


def _payloads(candidates, count=4):
    out = []
    for seed in range(count):
        rng = np.random.RandomState(seed)
        out.append({
            "feat_ids": rng.randint(
                0, 1 << 40, size=(candidates, NUM_FIELDS)
            ).astype(np.int64),
            "feat_wts": rng.rand(candidates, NUM_FIELDS).astype(np.float32),
        })
    return out


def _overload():
    # queue_wait_window_s is deliberately SHORTER than the recovery
    # phase: the default 10 s window would still hold the heavy phase's
    # over-target waits through the whole recovery phase, so the state
    # machine (and with it the down-switch) could never recover inside
    # the bench window.
    return OverloadConfig(
        enabled=True, target_queue_wait_ms=5.0, adjust_interval_s=0.05,
        queue_wait_window_s=2.0,
        brownout_after_intervals=2, recover_after_intervals=3,
    ).build()


def _run(servable, run_fn, make_ctrl=None):
    """One run of the phased stream. make_ctrl(run_fn, overload, batcher)
    attaches the elastic controller (elastic run only)."""
    ov = _overload()
    batcher = DynamicBatcher(
        buckets=BUCKETS, max_wait_us=200, run_fn=run_fn, overload=ov,
    ).start()
    ctrl = make_ctrl(run_fn, ov, batcher) if make_ctrl is not None else None
    light = _payloads(LIGHT_CANDIDATES)
    heavy = _payloads(HEAVY_CANDIDATES)
    phases = {}
    try:
        batcher.warmup(servable)
        prev_switches = 0
        for name, seconds, outstanding in PHASES:
            payloads = heavy if name == "pressure" else light
            done = 0
            refused = 0
            lats = []  # completion order (p50 sorts a copy)
            marks = []  # lats-index right after each observed switch
            pending = []

            def settle():
                nonlocal done, refused
                t_sub, fut = pending.pop(0)
                try:
                    fut.result(timeout=120)
                    lats.append(time.perf_counter() - t_sub)
                    done += 1
                except Exception:  # noqa: BLE001 — refusals counted
                    refused += 1

            t0 = time.perf_counter()
            i = 0
            while time.perf_counter() - t0 < seconds:
                try:
                    fut = batcher.submit(
                        servable, dict(payloads[i % len(payloads)]),
                        output_keys=("prediction_node",),
                    )
                    pending.append((time.perf_counter(), fut))
                except Exception:  # noqa: BLE001 — admission refusal
                    refused += 1
                    time.sleep(0.001)  # honor the pushback, do not spin
                i += 1
                while len(pending) >= outstanding:
                    settle()
                if outstanding == 1:
                    time.sleep(0.002)
                if ctrl is not None and run_fn.switches_up + \
                        run_fn.switches_down > prev_switches:
                    # First completed request AFTER each switch: the
                    # no-compile-on-switch evidence rides its latency.
                    prev_switches = (
                        run_fn.switches_up + run_fn.switches_down
                    )
                    marks.append(len(lats))
            while pending:
                settle()
            wall = time.perf_counter() - t0
            lat_arr = np.asarray(sorted(lats)) if lats else np.asarray([0.0])
            phases[name] = {
                "seconds": round(wall, 2),
                "completed": done,
                "refused": refused,
                "goodput_qps": round(done / wall, 2),
                "candidates_per_s": round(
                    done * payloads[0]["feat_ids"].shape[0] / wall, 0
                ),
                "p50_ms": round(
                    1e3 * float(lat_arr[len(lat_arr) // 2]), 2
                ),
                "pressure_state_end": ov.state(),
            }
            if ctrl is not None:
                phases[name]["split_end"] = (
                    run_fn.elastic_snapshot()["current_split"]
                )
                # Warmup-built executables only: if a switch had paid a
                # compile on the serving path, this first-post-switch
                # latency would sit orders of magnitude over the p50.
                phases[name]["post_switch_first_ms"] = [
                    round(1e3 * lats[m], 2) for m in marks if m < len(lats)
                ]
        result = {"phases": phases}
        if ctrl is not None:
            snap = run_fn.elastic_snapshot()
            result["elastic"] = {
                "switches_up": snap["switches_up"],
                "switches_down": snap["switches_down"],
                "history": snap["history"],
                "per_split": snap["per_split"],
                "controller": snap["controller"],
            }
        # Bit-identity probe payloads (deliberately not mesh-shaped).
        probes = _payloads(37, count=2)
        result["_probe_scores"] = [
            np.asarray(
                batcher.submit(
                    servable, dict(p), output_keys=("prediction_node",)
                ).result(timeout=120)["prediction_node"]
            )
            for p in probes
        ]
        return result
    finally:
        batcher.stop()
        overload_mod.deactivate()


def main() -> dict:
    out = {
        "device": str(jax.devices()[0]),
        "devices_visible": len(jax.devices()),
        "emulated": jax.default_backend() == "cpu",
        "errors": [],
    }
    n = len(jax.devices())
    if n < 2 or n % 2:
        out["errors"].append(f"need an even device count >= 2, have {n}")
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
    pinned_split = (n // 2, 2)

    pinned = _run(
        servable,
        ShardedExecutor(make_mesh(n, model_parallel=2)),
    )
    out["pinned"] = {k: v for k, v in pinned.items() if k != "_probe_scores"}
    out["pinned"]["split"] = f"{pinned_split[0]}x{pinned_split[1]}"

    def make_ctrl(run_fn, ov, batcher):
        return ElasticController(
            ElasticConfig(
                enabled=True, tick_interval_s=0.05, dwell_s=0.3,
                up_after_ticks=2, down_after_ticks=4,
                load_up_threshold=0.9, load_down_threshold=0.3,
            ),
            run_fn, overload=ov, load_fn=batcher.queue_load,
            largest_bucket=max(BUCKETS),
        )

    elastic = _run(
        servable,
        ElasticMeshExecutor(
            splits=[(n, 1), pinned_split], initial=pinned_split,
        ),
        make_ctrl=make_ctrl,
    )
    out["elastic"] = {k: v for k, v in elastic.items() if k != "_probe_scores"}

    same = all(
        np.array_equal(a, b)
        for a, b in zip(pinned["_probe_scores"], elastic["_probe_scores"])
    )
    out["bit_identical"] = same
    if not same:
        out["errors"].append("elastic probe scores != pinned-split probes")
    el = out["elastic"].get("elastic", {})
    out["switch_count"] = el.get("switches_up", 0) + el.get(
        "switches_down", 0
    )
    gain = {}
    for name, _s, _o in PHASES:
        p = out["pinned"]["phases"][name]["goodput_qps"]
        e = out["elastic"]["phases"][name]["goodput_qps"]
        gain[name] = round(e / p, 3) if p else None
    out["goodput_gain_by_phase"] = gain
    out["ok"] = not out["errors"]
    return out


if __name__ == "__main__":
    enable_compile_cache()
    result = main()
    print(json.dumps(result))
    sys.exit(0 if result.get("ok") else 1)
