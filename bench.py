#!/usr/bin/env python
"""Closed-loop CTR serving benchmark on the local device (ROADMAP S1 replaces
its measurement design; until then it may not report what it did not
measure).

Reproduces the reference's measurement methodology (DCNClient.java:205-241:
payload built once, N concurrent workers x M sequential logical requests,
per-request wall-clock including merge+sort) against the in-tree TPU
PredictionService over a real localhost gRPC socket — the full stack the
reference exercised, with tensorflow_model_server replaced by the JAX/XLA
backend and its server-side batching by the padded-bucket pipeline batcher.

Scope, all in the ONE json line:
- headline `value` = the MEDIAN of three sustained windows (8192/16384/
  32768 batch caps; best_window stays a separate field);
- the model served is TRAINED ON THE DEVICE first (train block: 1000-step
  cosine schedule, held-out AUC vs the Bayes ceiling, auc_curve);
- both traffic shapes (qps_repeated / qps_unique) PLUS the framework-
  native compact wire (qps_compact_wire, with a same-window wide control);
- the throughput decomposition: per-bucket device step (chained fori_loop
  differencing), device-limited QPS, MFU against the peak table (an
  accelerator missing from the table is an error), upload_mb_s;
- latency_mode (2048 cap, 4-way concurrency, p50/p99 + phase means) and
  p50_colocated_est (host phases + device step, components listed);
- host_ceiling / wide_wire_ceiling_qps: the same closed loop against a
  null-device batcher on the same host — the measured transport+service
  upper bound for each wire format;
- the cross-only Pallas probe (equality + timing) and an adversarial
  overload phase recording shed behavior (RESOURCE_EXHAUSTED);
- batcher stats incl. fused_batches (native one-pass batch assembly,
  hostops.cc) and the regime-aware input-cache counters.

One process, which touches jax once. Any phase that raises ends the run with
exit code 1 and a `value: 0.0` error line: no number from another run is
ever printed. Progress goes to stderr, staged.
"""

import json
import os
import sys
import time

CANDIDATES = 1000
NUM_FIELDS = 43
TARGET_QPS = 500.0  # north-star-implied: 1 req / 2ms p50, per chip


def log(stage: str, msg: str = "") -> None:
    print(f"[bench] t={time.strftime('%H:%M:%S')} stage={stage} {msg}".rstrip(),
          file=sys.stderr, flush=True)


def fail(stage: str, error: str) -> None:
    """The failure line, then exit 1. `value` is 0.0: this run measured
    nothing it can stand behind."""
    print(json.dumps({
        "metric": "ctr_qps_per_chip_1k",
        "value": 0.0,
        "unit": "qps",
        "vs_baseline": 0.0,
        "error": error[-2000:],
        "stage": stage,
    }), flush=True)
    sys.exit(1)


class Scale:
    """Workload scaling: flagship sizes on the accelerator, a fast smoke
    on the CPU (same code path, smaller everything)."""

    def __init__(self, platform: str):
        self.tpu = platform != "cpu"
        # Env override for load-shape experiments (default is the shipped
        # operating point: the round-3 sweep put the single-core knee at
        # 80-96 in-flight requests — QPS flat above, latency pure queueing).
        self.concurrency = int(
            os.environ.get("DTS_BENCH_CONCURRENCY", 88 if self.tpu else 8)
        )
        self.channels_per_host = 3  # round-3 sweep: beats 2/4/6 on one core
        # Back-to-back sustained windows (>= 8.8k requests each); the
        # headline takes the median. Each window pins (batch cap,
        # concurrency); all windows land in the JSON so the spread stays
        # visible.
        self.requests_per_worker = 100 if self.tpu else 4
        self.windows = (
            ((8192, self.concurrency), (16384, 2 * self.concurrency),
             (32768, 3 * self.concurrency))
            if self.tpu
            else ((1024, self.concurrency),)
        )
        self.unique_requests_per_worker = 60 if self.tpu else 3
        self.unique_pool = 128 if self.tpu else 8
        # The unique loop is upload-bound (every batch misses the content
        # cache), so extra in-flight requests only queue: a third of the
        # repeated concurrency keeps the link saturated at ~1/3 the
        # latency (Little's law), making p50_unique honest about the path
        # rather than about queue depth.
        self.unique_concurrency = max(8, self.concurrency // 3) if self.tpu else 4
        # DTS_BENCH_TOP_BUCKET extends the ladder for batch-size
        # experiments (a taller top bucket amortizes per-batch host cost
        # over more coalesced requests at the price of batch cadence).
        top = int(os.environ.get("DTS_BENCH_TOP_BUCKET", 32768))
        ladder = (32, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384, 32768)
        self.buckets = tuple(b for b in ladder if b <= top) if self.tpu \
            else (32, 64, 128, 256, 512, 1024)
        self.timed_buckets = tuple(
            b for b in (1024, 2048, 4096, 8192, 16384, 32768) if b <= top
        ) if self.tpu else (256, 1024)
        # 1000 steps (x5 round-3's 200): held-out AUC was information-
        # limited, not optimization-limited — ~270 noisy Bernoulli views
        # per embedding row cannot pin the teacher weight. 1000 steps
        # (~1.3k views/row) plus full-horizon cosine decay reached 0.9235
        # vs Bayes 0.9335 in the matched-density CPU study; the recorded
        # auc_curve proves whichever limit remains.
        self.train_steps = 1000 if self.tpu else 8
        self.train_batch = 2048 if self.tpu else 256
        # Bench-scale training must be LEARNABLE, not just runnable: the
        # teacher keys on raw ids, so an id seen a handful of times carries
        # no transferable signal (a 262k-id catalog measured held-out AUC
        # ~0.5 in r3). The 65k catalog — closer to the head of a power-law
        # CTR id distribution — gives each embedding row the ~1.3k views
        # the step count above is sized for.
        self.train_id_space = 1 << 16 if self.tpu else 1 << 12
        self.train_lr = 1.5e-2  # cosine peak (constant 1e-2 plateaued 0.03 lower)
        self.vocab_size = 1 << 20 if self.tpu else 1 << 14
        self.embed_dim = 16 if self.tpu else 8
        self.mlp_dims = (256, 128, 64) if self.tpu else (32, 16)
        self.overload_tasks = 128 if self.tpu else 24
        self.pallas_rows = 4096 if self.tpu else 256
        self.pallas_widths = (NUM_FIELDS * self.embed_dim, 1024) if self.tpu \
            else (NUM_FIELDS * self.embed_dim,)


# Peak dense bf16 FLOP/s by device_kind fragment (Google Cloud TPU
# documentation, per-chip figures); the MFU line's denominator.
_PEAK_BF16 = (("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12),
              ("v4", 275e12), ("v6", 918e12))


def peak_flops_for(device_kind: str) -> float:
    """Peak bf16 FLOP/s of one chip. A device missing from the table is an
    error, never an omitted or assumed peak."""
    kind = device_kind.lower()
    for frag, peak in _PEAK_BF16:
        if frag in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s on record for device kind {device_kind!r}; add it "
        "to _PEAK_BF16 with its source"
    )


def flops_per_example(config) -> float:
    """Dense-FLOPs estimate for one candidate through DCN-v2 (embedding
    gather is bandwidth, not FLOPs; 2 FLOPs per MAC)."""
    d = config.num_fields * config.embed_dim
    cross = config.num_cross_layers * (2 * d * d + 3 * d)
    dims = (d,) + tuple(config.mlp_dims)
    mlp = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    out = 2 * (d + (config.mlp_dims[-1] if config.mlp_dims else 0))
    return float(cross + mlp + out)


def measure_rtt_floor() -> float | None:
    """Round-trip floor of the host<->device link: tiny dispatch + fetch.
    Diagnostic-only, so bounded and guarded — returns None on trouble."""
    import jax
    import numpy as np

    try:
        x = jax.device_put(np.ones((8,), np.float32))
        jax.block_until_ready(x)
        f = jax.jit(lambda v: v * 2.0)
        np.asarray(f(x))  # compile + settle
        samples = []
        deadline = time.perf_counter() + 20.0
        for _ in range(5):
            if time.perf_counter() > deadline:
                break
            t0 = time.perf_counter()
            np.asarray(f(x))
            samples.append((time.perf_counter() - t0) * 1e3)
        return min(samples) if samples else None
    except Exception as exc:  # noqa: BLE001 — diagnostic must not kill the run
        log("rtt_floor", f"unavailable: {type(exc).__name__}: {exc}")
        return None


def device_loop_step_s(
    step_fn, carry, est_iters: int = 200, target_s: float = 0.12
) -> float | None:
    """Pure per-step device time: chain `step_fn` (carry -> carry) INSIDE
    one jitted fori_loop so a single dispatch covers N sequential steps —
    host dispatch rate cannot contaminate the measurement, and the fixed
    cost (one dispatch round-trip per call) cancels in a two-N difference.
    The loop bound is a traced argument, so every N shares one executable.

    N is sized ADAPTIVELY: the long run's total body time must dwarf the
    dispatch jitter (target_s) or the difference is noise. A coarse
    estimate pass picks N; min-of-2 walls reject stragglers."""
    import jax

    @jax.jit
    def many(c, iters):
        return jax.lax.fori_loop(0, iters, lambda i, x: step_fn(x), c)

    def run(iters: int) -> float:
        t0 = time.perf_counter()
        jax.block_until_ready(many(carry, iters))
        return time.perf_counter() - t0

    def measure(iters_short: int, iters_long: int) -> float:
        w_short = min(run(iters_short) for _ in range(2))
        w_long = min(run(iters_long) for _ in range(2))
        return (w_long - w_short) / (iters_long - iters_short)

    run(2)  # compile + settle
    est = max(measure(2, est_iters), 1e-8)
    iters_long = int(min(50_000, max(4 * est_iters, target_s / est)))
    step = measure(max(iters_long // 8, 2), iters_long)
    if step <= 0 or step < est / 50:
        # A straggler round-trip polluted a wall (min-of-2 can't save a
        # stall that spans both); a reading 50x below the coarse estimate
        # is physically implausible for the same op. One deeper retry with
        # a wider N gap.
        step = measure(max(iters_long // 4, 2), min(3 * iters_long, 60_000))
    # Degenerate readings become None, never a fake tiny number — a 0.0
    # here once crashed the whole child via a divide in the MFU line.
    return step if step > 0 and step >= est / 50 else None


def train_on_chip(scale: Scale, config):
    """The served model is trained on this device first.
    Returns (model, trained params, train block for the JSON line)."""
    from distributed_tf_serving_tpu.models import build_model
    from distributed_tf_serving_tpu.train.data import SyntheticCTRConfig
    from distributed_tf_serving_tpu.train.trainer import Trainer

    import optax

    model = build_model("dcn_v2", config)
    t0 = time.perf_counter()
    # Warmup + cosine-to-zero: the constant-LR run plateaued at 0.84 AUC
    # with per-id gradient noise the tail never averaged out.
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=scale.train_lr,
        warmup_steps=max(scale.train_steps // 10, 1),
        decay_steps=scale.train_steps,
    )
    trainer = Trainer(
        model,
        learning_rate=schedule,
        seed=0,
        stream_config=SyntheticCTRConfig(
            num_fields=config.num_fields, id_space=scale.train_id_space, seed=0
        ),
    )
    metrics = trainer.fit(
        scale.train_steps, batch_size=scale.train_batch,
        auc_every=max(scale.train_steps // 4, 1),
    )
    auc_val, bayes = trainer.eval_auc(
        batches=4, batch_size=scale.train_batch, with_bayes=True
    )
    block = {
        "steps": scale.train_steps,
        "batch_size": scale.train_batch,
        "wall_s": round(time.perf_counter() - t0, 1),
        "step_wall_s": round(metrics["wall_s"], 1),
        "examples_per_s": round(metrics["examples_per_s"], 0),
        "loss": round(metrics["loss"], 4),
        "auc": round(auc_val, 4),  # held-out (indices disjoint from training)
        "bayes_auc": round(bayes, 4),  # the synthetic task's ceiling
        "auc_curve": metrics.get("auc_curve"),  # steps-vs-AUC plateau proof
    }
    return model, trainer.state.params, block


def pallas_probe(scale: Scale, config, cross_params) -> dict:
    """Fused Pallas cross-stack capability probe: equality + timing vs the
    per-layer XLA path on the real device (interpret on the CPU smoke).
    The cross-only kernel serves nowhere by default
    (ModelConfig.use_pallas_cross opt-in); the fused serving kernel
    competes through the ops/autotune.py harness (DTS_BENCH_KERNELS=1
    `kernels` block), which enables it per bucket only where it measures a
    live win."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tf_serving_tpu.models.dcn import cross_apply
    from distributed_tf_serving_tpu.ops.cross_kernel import (
        cross_params_to_stacked,
        fused_cross_apply,
    )

    interpret = not scale.tpu
    cd = config.cdtype
    block: dict = {"interpreted": interpret, "rows": scale.pallas_rows}
    for d in scale.pallas_widths:
        entry: dict = {}
        try:
            if d == config.num_fields * config.embed_dim:
                w, b = cross_params_to_stacked(cross_params)
                layers = cross_params
            else:  # aligned-width synthetic point (128-lane multiple)
                keys = jax.random.split(jax.random.PRNGKey(1), 2)
                L = config.num_cross_layers
                w = jax.random.normal(keys[0], (L, d, d), jnp.float32) / d**0.5
                b = jnp.zeros((L, d), jnp.float32)
                layers = [{"w": w[i], "b": b[i]} for i in range(L)]
            x0 = jax.random.normal(
                jax.random.PRNGKey(2), (scale.pallas_rows, d), jnp.float32
            ).astype(cd)

            fused = jax.jit(
                lambda x: fused_cross_apply(x, w, b, compute_dtype=cd, interpret=interpret)
            )
            ref = jax.jit(lambda x: cross_apply(layers, x, cd))
            got = np.asarray(fused(x0), np.float32)
            want = np.asarray(ref(x0), np.float32)
            denom = max(float(np.max(np.abs(want))), 1.0)
            entry["max_rel_err"] = round(float(np.max(np.abs(got - want))) / denom, 6)
            # Both apply x -> x of the same shape/dtype, so they chain on
            # device directly (values may saturate over the loop; TPU
            # arithmetic speed is value-independent). Interpret mode
            # (CPU smoke) gets tiny loops: it is orders slower.
            est, tgt = (200, 0.12) if scale.tpu else (4, 0.005)
            p_s = device_loop_step_s(fused, x0, est, tgt)
            x_s = device_loop_step_s(ref, x0, est, tgt)
            entry["pallas_us"] = None if p_s is None else round(p_s * 1e6, 1)
            entry["xla_us"] = None if x_s is None else round(x_s * 1e6, 1)
            entry["speedup"] = round(x_s / p_s, 2) if (p_s and x_s) else None
        except Exception as exc:  # noqa: BLE001 — record, keep benching on XLA
            entry["error"] = f"{type(exc).__name__}: {exc}"[:500]
        block[f"d{d}"] = entry
    block["enabled_for_serving"] = False  # use_pallas_cross is opt-in
    return block


def kernel_ab_block(batcher, servable, scale: Scale, config) -> dict:
    """Kernels A/B (ISSUE 12, opt-in via DTS_BENCH_KERNELS=1): run the
    ops/autotune.py harness over the timed buckets on the live device —
    per-bucket XLA/Pallas x f32/int8 step times through the SAME jitted
    entries the batcher serves with, the emitted per-bucket decision
    table, the wire-bytes deltas (score bytes per candidate per wire
    dtype; quantized weight-stream shrink), and the accuracy gates: max
    |dScore| vs the f32 baseline and AUC on a held-out labeled synthetic
    block against the train block's number (the 0.84-on-TPU anchor) —
    quantized must land within [kernels] auc_margin (0.005). The
    decision table persists to the [kernels] table_file default, so a
    serving process on this same device adopts these measurements at
    warmup instead of re-tuning. The manager detaches afterward: the
    bench's own windows never serve variant executables, keeping
    headlines comparable across rounds."""
    from distributed_tf_serving_tpu.ops.autotune import KernelManager
    from distributed_tf_serving_tpu.ops.quantize import (
        quantize_params,
        quantized_param_bytes,
    )
    from distributed_tf_serving_tpu.train.data import (
        SyntheticCTRConfig,
        SyntheticCTRStream,
    )
    from distributed_tf_serving_tpu.utils.config import KernelsConfig

    kc = KernelsConfig(
        enabled=True,
        measure_iters=int(os.environ.get("DTS_BENCH_KERNEL_ITERS", "0")),
    )
    manager = KernelManager(kc)
    batcher.kernels = manager
    try:
        # Held-out labeled eval: the train stream's generator at an index
        # far past anything training touched (train/data batch(i) is
        # deterministic per index) — same teacher, fresh rows.
        stream = SyntheticCTRStream(SyntheticCTRConfig(
            num_fields=config.num_fields, id_space=scale.train_id_space,
            seed=0,
        ))
        held_out = stream.batch(1024, 10_000_019)
        eval_data = (
            {"feat_ids": held_out["feat_ids"], "feat_wts": held_out["feat_wts"]},
            held_out["labels"],
        )
        buckets = tuple(b for b in scale.timed_buckets if b <= 4096)
        # force=True: the A/B block's contract is FRESH per-round numbers
        # — a deterministic re-train would otherwise digest-match round
        # 1's persisted entry and replay its timings as this round's.
        table = manager.autotune(
            batcher, servable, buckets=buckets, eval_data=eval_data,
            force=True,
        )
        q, f = quantized_param_bytes(quantize_params(servable.params))
        decisions = {
            b: row.get("decision")
            for b, row in (table.get("buckets") or {}).items()
        }
        return {
            "table": table,
            "decisions": decisions,
            "any_enabled": any(
                d not in (None, "xla_f32") for d in decisions.values()
            ),
            # The readback/wire half of the int8 story: bytes per score
            # crossing D2H (and, with [kernels] int8_score_wire + the
            # client opt-in, the response wire) per wire dtype.
            "wire_bytes_per_score": {"float32": 4, "bfloat16": 2, "int8": 1},
            "quantized_weight_bytes": q,
            "f32_weight_bytes": f,
            "weight_stream_shrink": round(f / q, 2) if q else None,
            "table_file": kc.table_file,
        }
    finally:
        # Detach: headline windows must serve the baseline executables.
        batcher.kernels = None


def _device_ab_block(
    device: str, script_name: str, label: str, devices_env: str,
) -> dict:
    """Run a multi-device A/B tool (mesh_ab.py, elastic_ab.py) IN-PROCESS:
    this process already owns the accelerator backend (one process per
    chip), so a subprocess could never initialize it. The host must have
    the chips the A/B needs — too few is an error that fails the block,
    never an emulated CPU run reported beside a throughput number."""
    import importlib.util

    import jax

    need = int(os.environ.get(devices_env, "8"))
    have = len(jax.devices())
    if jax.default_backend() == "cpu" or have < need:
        raise RuntimeError(
            f"{label} A/B needs {need} accelerator chips; this process has "
            f"{have} {jax.default_backend()} device(s)"
        )
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools", script_name
    )
    spec = importlib.util.spec_from_file_location(
        script_name.removesuffix(".py"), script
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    block = mod.main()
    block["parent_device"] = device
    return block


def mesh_ab_block(device: str) -> dict:
    """Mesh serving A/B (ISSUE 13, opt-in via DTS_BENCH_MESH=1):
    tools/mesh_ab.py — single-chip vs data-parallel ({N,1}) vs
    data×model ({N/2,2}) serving throughput of one process, with a
    bit-identity gate across all three modes."""
    return _device_ab_block(
        device, "mesh_ab.py", "mesh", devices_env="MESH_AB_DEVICES",
    )


def elastic_ab_block(device: str) -> dict:
    """Elastic serving A/B (ISSUE 15, opt-in via DTS_BENCH_ELASTIC=1):
    tools/elastic_ab.py — the SAME seeded ramped stream (nominal ->
    pressure -> recovery phases) served by a pinned {N/2,2} split and by
    the elastic ladder, reporting goodput per pressure phase, switch
    count + history, and the first post-switch request latency (the
    no-serving-path-compile evidence)."""
    return _device_ab_block(
        device, "elastic_ab.py", "elastic", devices_env="ELASTIC_AB_DEVICES",
    )


def device_decomposition(batcher, servable, scale: Scale, rtt_floor_ms,
                         device_kind: str) -> dict:
    """The denominator every tuning argument needs — pure device step time
    per bucket (through the SAME jitted entry the batcher serves with, so
    pack/unpack compression is included), implied device-limited QPS,
    transfer bytes, and on an accelerator MFU against the peak table."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tf_serving_tpu.ops.transfer import (
        combined_layout,
        pack_host,
        pack_host_combined,
    )
    from distributed_tf_serving_tpu.serving.batcher import prepare_inputs

    fn, spec, combined = batcher.jit_entry(servable)
    steps: dict[str, float] = {}
    bytes_per_batch: dict[str, int] = {}
    best_qps = 0.0
    for bucket in scale.timed_buckets:
        arrays = batcher.warmup_arrays(servable, bucket)
        rng = np.random.RandomState(3)
        arrays["feat_ids"] = rng.randint(  # realistic gather addresses
            0, 1 << 40, size=arrays["feat_ids"].shape
        ).astype(np.int64)
        prepped = prepare_inputs(servable.model, arrays)
        if combined:
            layout = combined_layout(prepped, spec)
            buf = pack_host_combined(prepped, spec)
            dev = jax.device_put(buf)
            jax.block_until_ready(dev)
            nbytes = buf.nbytes

            # Chain batches on device with a true sequential data
            # dependence (XLA cannot hoist the forward): XOR the byte
            # buffer with a value-dependent zero — min(score)*1e-30
            # underflows to 0, so the bytes are unchanged but depend on
            # the previous iteration's output.
            def step(b):
                out = fn(servable.params, b, layout)
                score = next(iter(out.values()))
                eps8 = (jnp.min(score) * 1e-30).astype(jnp.uint8)
                return b ^ eps8
        else:
            packed = pack_host(prepped, spec) if spec else prepped
            dev = {k: jax.device_put(v) for k, v in packed.items()}
            jax.block_until_ready(dev)
            nbytes = sum(v.nbytes for v in packed.values())

            # Same chaining trick on the per-key dict: nudge the float
            # input by a value-dependent epsilon (*0 would constant-fold).
            carry_key = next(
                (k for k, v in dev.items() if jnp.issubdtype(v.dtype, jnp.floating)),
                None,
            )

            def step(batch):
                out = fn(servable.params, batch)
                score = next(iter(out.values()))
                eps = jnp.min(score) * 1e-30
                return {
                    k: (v + eps.astype(v.dtype) if k == carry_key else v)
                    for k, v in batch.items()
                }

        est, tgt = (100, 0.12) if scale.tpu else (6, 0.01)
        step_s = device_loop_step_s(step, dev, est, tgt)
        steps[str(bucket)] = None if step_s is None else round(step_s * 1e6, 1)
        bytes_per_batch[str(bucket)] = nbytes
        if step_s:
            best_qps = max(best_qps, (bucket / CANDIDATES) / step_s)
    # Host->device upload bandwidth: the unique-traffic path misses the
    # content cache on every batch, so its ceiling is min(host data plane,
    # this link). Publishing it makes the qps_unique number attributable:
    # at 215 B/candidate a measured U MB/s caps unique QPS at
    # U / 0.215 per 1k-candidate request, whatever the host does.
    upload_mb_s = None
    try:
        import numpy as _np

        buf = _np.random.RandomState(5).randint(
            0, 255, size=4 << 20, dtype=_np.uint8
        )
        jax.block_until_ready(jax.device_put(buf))  # settle
        samples = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready([jax.device_put(buf) for _ in range(4)])
            samples.append((4 * buf.nbytes) / (time.perf_counter() - t0) / 1e6)
        upload_mb_s = round(max(samples), 1)  # max: least-stalled window
    except Exception as exc:  # noqa: BLE001 — diagnostic only
        log("device_decomposition", f"upload probe failed: {exc}")
    block = {
        "device_step_us": steps,
        "transfer_bytes_per_batch": bytes_per_batch,
        "device_limited_qps": round(best_qps, 1) if best_qps else None,
        "rtt_floor_ms": None if rtt_floor_ms is None else round(rtt_floor_ms, 2),
        "upload_mb_s": upload_mb_s,
        "unique_qps_link_cap": (
            round(upload_mb_s / 0.215, 1) if upload_mb_s else None
        ),
    }
    # MFU from the largest bucket with a usable reading — an accelerator
    # metric: a CPU run has none, and an accelerator the peak table does
    # not know raises.
    usable = [b for b in scale.timed_buckets if steps.get(str(b))]
    if scale.tpu and usable:
        peak = peak_flops_for(device_kind)
        top = max(usable)
        flops = flops_per_example(servable.model.config) * top
        block["mfu"] = round(flops / (steps[str(top)] / 1e6) / peak, 4)
        block["peak_flops"] = peak
    return block


def colocated_latency_estimate(
    phases: dict, device_block: dict, stats_rep, headline_cap: int
) -> dict | None:
    """The <=2 ms north-star argument: a 1k-candidate request's p50 as the
    sum of its measured parts, each component listed so the estimate is
    auditable:

    - predict.decode / predict.encode: per-request host codec work.
    - batch.pad + batch.dispatch: per-BATCH host work the request waits out
      (dispatch INCLUDES the cache digest and the jit-call spans). These are
      charged in full, not amortized — latency is not throughput. floor_ms
      leaves the jit-call span out (the async dispatch itself).
    - device_step_us for the headline bucket: the batch's on-chip time.
    - readback: the scores tensor is ~4 KB/request; charged at 50 us.

    Queueing/fill wait is excluded (max_wait_us bounds it at 2 ms at low
    load; under sustained load fill is pipeline-free) — stated in the note.
    """
    try:
        dev_us_map = device_block.get("device_step_us") or {}
        dev_us = dev_us_map.get(str(headline_cap))
        if dev_us is None:
            # Fall back to the largest measured bucket, scaled linearly
            # (the device step is ~linear in rows at these sizes).
            measured = [(int(b), v) for b, v in dev_us_map.items() if v]
            if not measured:
                return None
            b, v = max(measured)
            dev_us = v * headline_cap / b
        decode = phases.get("predict.decode", 0.0)
        encode = phases.get("predict.encode", 0.0)
        pad = phases.get("batch.pad", 0.0)
        dispatch = phases.get("batch.dispatch", 0.0)
        jitcall = phases.get("batch.jitcall", 0.0)
        readback_us = 50.0
        est_us = decode + encode + pad + dispatch + dev_us + readback_us
        floor_us = est_us - jitcall  # async-dispatch span out
        return {
            "est_ms": round(est_us / 1e3, 3),
            "floor_ms": round(floor_us / 1e3, 3),
            "components_us": {
                "predict.decode": round(decode, 1),
                "predict.encode": round(encode, 1),
                "batch.pad": round(pad, 1),
                "batch.dispatch": round(dispatch, 1),
                "of_which_jitcall": round(jitcall, 1),
                "device_step": round(dev_us, 1),
                "readback_assumed": readback_us,
            },
            "requests_per_batch": round(stats_rep.mean_requests_per_batch, 2),
            "note": "host phases + device step for the headline bucket; "
                    "excludes queueing/fill wait; floor_ms drops the "
                    "batch.jitcall span (the async dispatch itself)",
        }
    except Exception as exc:  # noqa: BLE001 — an estimate must not cost the run
        log("colocated_estimate", f"unavailable: {type(exc).__name__}: {exc}")
        return None


async def overload_probe(client_cls, port: str, batcher, scale: Scale, payload) -> dict:
    """Drive past queue capacity on the real stack and
    record shedding. Capacity is squeezed for the probe, then restored."""
    from distributed_tf_serving_tpu.client import PredictClientError

    old_capacity = batcher.queue_capacity_candidates
    # One max-size bucket of queued work: a 128-way burst of 1k-candidate
    # requests must overrun it decisively (a looser squeeze made the shed
    # rate drift with drain-speed variance across runs, 1%-6%). Computed
    # ONCE so the applied and reported values cannot desync.
    probe_capacity = max(batcher.buckets[-1], CANDIDATES)
    batcher.queue_capacity_candidates = probe_capacity
    counts = {"sent": 0, "ok": 0, "shed": 0, "unavailable": 0, "other": 0}
    try:
        async with client_cls([f"127.0.0.1:{port}"], "DCN", channels_per_host=6) as client:
            import asyncio

            async def one():
                counts["sent"] += 1
                try:
                    await client.predict(payload)
                    counts["ok"] += 1
                except PredictClientError as e:
                    code = getattr(e.code, "name", str(e.code))
                    if code == "RESOURCE_EXHAUSTED":
                        counts["shed"] += 1
                    elif code == "UNAVAILABLE":
                        counts["unavailable"] += 1
                    else:
                        counts["other"] += 1

            for _ in range(3):  # three waves so shedding, not warm caches, decides
                await asyncio.gather(*(one() for _ in range(scale.overload_tasks)))
    finally:
        batcher.queue_capacity_candidates = old_capacity
    counts["shed_rate"] = round(counts["shed"] / max(counts["sent"], 1), 3)
    counts["queue_capacity_candidates"] = probe_capacity
    return counts


async def overload_ab_pass(
    client_cls, port: str, pool, sched, deadline_s: float, workers: int,
    duration_s: float, channels_per_host: int,
) -> dict:
    """One pass of the --overload A/B: `workers` continuous closed-loop
    workers replaying the same seeded zipfian schedule for `duration_s`,
    each RPC under a hard `deadline_s` deadline — so `ok` IS the
    in-deadline success count and goodput_qps = ok / duration. One
    failover retry with the scoreboard on: refused requests exercise the
    retry-after pushback path, and the pass records whether refusals
    landed as pushback (busy) or burned the ejection budget."""
    import asyncio

    from distributed_tf_serving_tpu.client import PredictClientError

    counts = {"sent": 0, "ok": 0, "shed": 0, "deadline": 0,
              "unavailable": 0, "other": 0}
    t_end = time.perf_counter() + duration_s
    async with client_cls(
        [f"127.0.0.1:{port}"], "DCN", channels_per_host=channels_per_host,
        timeout_s=deadline_s, scoreboard=True, failover_attempts=1,
    ) as client:

        async def worker(w: int):
            # Staggered ramp: real load is a ramp, and an instantaneous
            # stampede would measure only the cold first moments.
            await asyncio.sleep(min(w, 40) * 0.05)
            i = 0
            while time.perf_counter() < t_end:
                i += 1
                counts["sent"] += 1
                try:
                    await client.predict(
                        pool[sched[(w * 997 + i) % len(sched)]]
                    )
                    counts["ok"] += 1
                except PredictClientError as e:
                    code = getattr(e.code, "name", str(e.code))
                    if code == "RESOURCE_EXHAUSTED":
                        counts["shed"] += 1
                    elif code == "DEADLINE_EXCEEDED":
                        counts["deadline"] += 1
                    elif code == "UNAVAILABLE":
                        counts["unavailable"] += 1
                    else:
                        counts["other"] += 1

        await asyncio.gather(*(worker(w) for w in range(workers)))
        counts["pushbacks"] = client.counters.pushbacks_received
        counts["retry_after_honored"] = client.counters.retry_after_honored
        sb = client.scoreboard.snapshot() if client.scoreboard else {}
        counts["ejections"] = sb.get("ejections", 0)
    counts["duration_s"] = duration_s
    counts["goodput_qps"] = round(counts["ok"] / duration_s, 1)
    return counts


def _overload_flag() -> bool:
    """--overload: run the admission A/B phase (static limit vs adaptive
    controller on the identical overloaded workload). Skipped by default —
    the phase deliberately drives the stack past capacity, which has no
    business inside the headline windows."""
    return "--overload" in sys.argv[1:]


def _cascade_flag() -> bool:
    """--cascade: run the multi-stage cascade A/B phase (the identical
    seeded candidate stream, DCN-only then retrieval->rank through the
    two-executable cascade). Skipped by default — the phase serves
    through its own small-rung batcher, not the headline ladder."""
    return "--cascade" in sys.argv[1:]


def _skew_flag() -> float | None:
    """--skew[=EXPONENT]: run the cache-plane A/B phase on a seeded
    zipfian workload (client/bench.py make_zipfian_payloads +
    zipfian_indices — the same seed replays the identical request stream
    for the cache-off and cache-on passes). Default exponent 1.1; None
    when the flag is absent (the phase is skipped entirely)."""
    for arg in sys.argv[1:]:
        if arg == "--skew":
            return 1.1
        if arg.startswith("--skew="):
            return float(arg.split("=", 1)[1])
    return None


def _flag_value(name: str, argv=None) -> str | None:
    """Value of a `--name PATH` / `--name=PATH` flag, or None (the
    bench's argv handling predates argparse)."""
    argv = sys.argv[1:] if argv is None else argv
    for i, arg in enumerate(argv):
        if arg == name and i + 1 < len(argv):
            return argv[i + 1]
        if arg.startswith(name + "="):
            return arg.split("=", 1)[1]
    return None


def _trace_out_path() -> str | None:
    """--trace-out PATH: enable per-request tracing for the whole bench
    and write the recorder's Chrome-trace-event JSON (Perfetto-loadable)
    there at the end."""
    return _flag_value("--trace-out")


def main() -> None:
    import asyncio
    import dataclasses

    stage = "jax_init"
    try:
        log(stage, "importing jax + framework")
        import jax
        import numpy as np

        from distributed_tf_serving_tpu.utils.runtime import enable_compile_cache

        enable_compile_cache()
        from distributed_tf_serving_tpu.client import (
            ShardedPredictClient,
            make_payload,
            run_closed_loop,
            transfer_counters as _transfer_counters,
        )
        from distributed_tf_serving_tpu.models import (
            ModelConfig,
            Servable,
            ServableRegistry,
            ctr_signatures,
        )
        from distributed_tf_serving_tpu.serving import DynamicBatcher, PredictionServiceImpl
        from distributed_tf_serving_tpu.utils.tracing import request_trace

        device = str(jax.devices()[0])
        device_kind = jax.devices()[0].device_kind
        platform = jax.devices()[0].platform
        scale = Scale(platform)
        log(stage, f"device={device} kind={device_kind} platform={platform} "
                   f"count={len(jax.devices())} tpu_scale={scale.tpu}")

        trace_out = _trace_out_path()
        if trace_out:
            from distributed_tf_serving_tpu.utils import tracing as span_tracing

            # Tail-heavy sampling: at bench QPS a 2% sample plus the
            # always-kept slowest-N/error tails bounds recorder growth
            # while still catching exactly the requests worth explaining.
            span_tracing.enable(buffer_size=512, sample_rate=0.02, slowest_n=64)
            log("tracing", f"per-request tracing on -> {trace_out}")

        stage = "rtt_floor"
        rtt_floor_ms = measure_rtt_floor()
        log(stage, f"rtt_floor={rtt_floor_ms and round(rtt_floor_ms, 2)}ms")

        stage = "train"
        config = ModelConfig(
            name="DCN",
            num_fields=NUM_FIELDS,
            vocab_size=scale.vocab_size,
            embed_dim=scale.embed_dim,
            mlp_dims=scale.mlp_dims,
            num_cross_layers=3,
            cross_full_matrix=True,
        )
        log(stage, f"{scale.train_steps} steps x {scale.train_batch} on-device")
        model, params, train_block = train_on_chip(scale, config)
        log(stage, f"loss={train_block['loss']} auc={train_block['auc']} "
                   f"({train_block['examples_per_s']:.0f} ex/s)")

        stage = "model_build"
        registry = ServableRegistry()
        # Utilization plane (ISSUE 6): the occupancy ledger rides the
        # whole bench (one interval append per batch — noise-level cost).
        # The ledger registers as a Chrome counter-track source, so a
        # --trace-out export carries the per-device occupancy track.
        from distributed_tf_serving_tpu.serving.utilization import (
            OccupancyLedger,
        )
        from distributed_tf_serving_tpu.utils import tracing as span_tracing_mod

        ledger = OccupancyLedger(device=device, ring=8192)
        span_tracing_mod.register_counter_source(ledger)
        # Quality plane (ISSUE 7, opt-in via DTS_BENCH_QUALITY=1): score-
        # distribution sketches ride the bench windows so the report
        # carries a `quality` block next to the perf numbers — the
        # disabled default keeps the headline comparable across rounds
        # (armed, the completer pays the sketch + per-request digest).
        quality_monitor = None
        if os.environ.get("DTS_BENCH_QUALITY", "0") == "1":
            from distributed_tf_serving_tpu.serving.quality import (
                QualityMonitor,
            )

            quality_monitor = QualityMonitor(window_s=600.0)
        batcher = DynamicBatcher(
            buckets=scale.buckets,
            max_wait_us=2000,
            completion_workers=12,
            # Output-transfer pipeline (ISSUE 1): scores cross the D2H
            # link as bf16 (<=1e-2 rel err; the completer widens back to
            # f32 before the response encode) with the readback issued at
            # dispatch and awaited on the completers.
            output_wire_dtype="bfloat16",
            async_readback=True,
            pipelined_dispatch=True,
            utilization=ledger,
            quality=quality_monitor,
        ).start()
        impl = PredictionServiceImpl(registry, batcher)
        servable = Servable(
            name="DCN", version=1, model=model, params=params,
            signatures=ctr_signatures(config.num_fields),
        )
        registry.load(servable)

        stage = "warmup_compile"
        from distributed_tf_serving_tpu.client import compact_payload

        for b in scale.timed_buckets:
            t0 = time.perf_counter()
            batcher.warmup(servable, buckets=(b,))
            # The compact wire (int32 folded ids + bf16 weights) is a
            # distinct combined-buffer layout: warm its executables too so
            # the qps_compact window measures serving, not compilation.
            # Live traffic filters to the score output (the client's
            # output_key), so warm exactly that output-selection variant.
            batcher.submit(
                servable,
                compact_payload(batcher.warmup_arrays(servable, b), config.vocab_size),
                output_keys=("prediction_node",),
                _warmup=True,
            ).result(timeout=600)
            log(stage, f"bucket={b} compiled in {time.perf_counter() - t0:.1f}s "
                       "(wide + compact layouts)")

        stage = "server_start"
        # Coroutine server (serving/server.py create_server_async): the
        # thread-per-RPC model spends a first-order slice of a single core
        # on GIL hand-offs across ~70 handler threads. Client and server
        # share ONE event loop — same core either way, fewer hops.
        from distributed_tf_serving_tpu.serving.server import create_server_async

        payload = make_payload(candidates=CANDIDATES, num_fields=NUM_FIELDS)
        request_trace.reset()  # warmup compiles out of the phase means
        res: dict = {}

        def merge_resilience(counters: dict) -> None:
            """Accumulate per-loop client resilience counters into the
            report (event counts sum across loops; the scoreboard snapshot
            keeps the latest)."""
            agg = res.setdefault("resilience_client", {})
            for k, v in counters.items():
                if isinstance(v, (int, float)):
                    agg[k] = agg.get(k, 0) + v
                else:
                    agg[k] = v

        def make_loop(port):
            async def loop(pool=None, rpw=scale.requests_per_worker,
                           prepared=False, conc=scale.concurrency):
                async with ShardedPredictClient(
                    [f"127.0.0.1:{port}"], "DCN",
                    channels_per_host=scale.channels_per_host,
                    # Scoreboard on: the resilience block reports real EWMA/
                    # event counters for the headline windows (pure
                    # bookkeeping — no hedging/failover unless configured).
                    scoreboard=True,
                ) as client:
                    try:
                        return await run_closed_loop(
                            client,
                            payload,
                            concurrency=conc,
                            requests_per_worker=rpw,
                            sort_scores=True,
                            warmup_requests=5,
                            payload_pool=pool,
                            prepared=prepared,
                        )
                    finally:
                        merge_resilience(client.resilience_counters())

            return loop

        async def serve_windows():
            nonlocal stage
            server, port = create_server_async(impl, "127.0.0.1:0")
            await server.start()
            try:
                loop = make_loop(port)
                stage = "load_loop_repeated"
                # prepared=True: the reference methodology fixes the payload
                # once (DCNClient.java:208-210), so the serialized request is
                # loop-invariant; qps_unique below charges the full per-call
                # build+serialize path.
                def stats_delta(before, after):
                    """Batcher counters for one window (snapshot difference);
                    gauges that are not counters keep the window-end value."""
                    d = dataclasses.replace(after)
                    for f in ("batches", "requests", "candidates",
                              "padded_candidates", "fill_waits",
                              "fused_batches", "topk_batches", "deadline_sheds",
                              "dedup_batches", "dedup_rows_collapsed",
                              "bytes_downloaded", "bytes_download_full_f32",
                              "readback_window_s", "readback_blocked_s"):
                        setattr(d, f, getattr(after, f) - getattr(before, f))
                    return d

                windows = []
                windows_t0 = time.perf_counter()
                for w, (cap, conc) in enumerate(scale.windows):
                    # Clamp: DTS_BENCH_TOP_BUCKET below a window's cap must
                    # shrink the window, not overflow the bucket ladder.
                    batcher.max_batch_candidates = min(cap, batcher.buckets[-1])
                    # Keep each window ~20-30 s regardless of its
                    # concurrency (but always >= 8.8k requests).
                    rpw = max(33, int(scale.requests_per_worker
                                      * scale.concurrency / conc))
                    log(stage, f"window {w + 1}/{len(scale.windows)}: "
                               f"batch_cap={batcher.max_batch_candidates} "
                               f"concurrency={conc} x {rpw} (prepared wire bytes)")
                    before = dataclasses.replace(batcher.stats)
                    request_trace.reset()  # phases are per-window, like stats
                    report_w = await loop(prepared=True, conc=conc, rpw=rpw)
                    phases_w = {
                        name: snap["mean_us"]
                        for name, snap in request_trace.snapshot().items()
                    }
                    windows.append(
                        (cap, report_w, stats_delta(before, batcher.stats), phases_w)
                    )
                    log(stage, f"window {w + 1} qps={report_w.summary()['qps']:.1f}")
                res["windows_qps"] = [
                    {"batch_cap": cap, "concurrency": r.summary()["concurrency"],
                     "qps": round(r.summary()["qps"], 1)}
                    for cap, r, _st, _ph in windows
                ]
                # Headline = the MEDIAN window: best-of-3 inflates
                # systematically. The best window stays visible as a
                # separate field.
                ordered = sorted(windows, key=lambda cr: cr[1].summary()["qps"])
                med_cap, res["report"], res["stats_rep"], res["phases"] = ordered[
                    len(ordered) // 2
                ]
                res["headline_batch_cap"] = med_cap
                best = ordered[-1]
                res["best_window"] = {
                    "batch_cap": best[0],
                    "qps": round(best[1].summary()["qps"], 1),
                }
                # Utilization snapshot over EXACTLY the headline windows
                # (before the latency-mode phase muddies the timeline):
                # the live achieved_fraction_of_device_limit + the gap
                # waterfall whose components sum to the windows' wall.
                res["utilization"] = ledger.snapshot(
                    window_s=time.perf_counter() - windows_t0
                )
                log("utilization", json.dumps(
                    res["utilization"]["waterfall"]))
                if quality_monitor is not None:
                    # Quality plane over the same headline windows: the
                    # served-score sketch the report's `quality` block
                    # carries (DTS_BENCH_QUALITY=1).
                    res["quality"] = quality_monitor.snapshot()

                stage = "latency_mode"
                # MEASURE the latency operating point instead of
                # estimating it. Small bucket cap + near-zero concurrency =
                # no queueing, batches of 1-2 requests: the measured p50 is
                # dispatch floor + host work + device step.
                batcher.max_batch_candidates = min(2048, batcher.buckets[-1])
                request_trace.reset()
                lat_conc = 4 if scale.tpu else 2
                lat_rpw = 100 if scale.tpu else 3
                log(stage, f"batch_cap={batcher.max_batch_candidates} "
                           f"concurrency={lat_conc} x {lat_rpw}")
                report_l = await loop(prepared=True, conc=lat_conc, rpw=lat_rpw)
                s_l = report_l.summary()
                # The dispatch floor probed seconds from the p50 it sits
                # under, reported beside it.
                lat_rtt = measure_rtt_floor()
                res["latency_mode"] = {
                    "batch_cap": batcher.max_batch_candidates,
                    "concurrency": lat_conc,
                    "requests": s_l["requests"],
                    "qps": round(s_l["qps"], 1),
                    "p50_ms": round(s_l["p50_ms"], 3),
                    "p99_ms": round(s_l["p99_ms"], 3),
                    "mean_ms": round(s_l["mean_ms"], 3),
                    "rtt_floor_adjacent_ms": (
                        None if lat_rtt is None else round(lat_rtt, 2)
                    ),
                    "phases_us": {
                        name: snap["mean_us"]
                        for name, snap in request_trace.snapshot().items()
                    },
                }
                log(stage, f"p50={s_l['p50_ms']:.2f}ms p99={s_l['p99_ms']:.2f}ms "
                           f"(adjacent rtt_floor="
                           f"{lat_rtt and round(lat_rtt, 2)}ms)")
            finally:
                await server.stop(0)

        async def serve_unique_and_overload():
            nonlocal stage
            server, port = create_server_async(impl, "127.0.0.1:0")
            await server.start()
            try:
                loop = make_loop(port)
                # Unique-traffic and overload phases run at the 8192 cap.
                batcher.max_batch_candidates = min(8192, batcher.buckets[-1])
                request_trace.reset()  # per-loop phases: unique traffic differs

                stage = "load_loop_unique"
                log(stage, f"pool={scale.unique_pool} x "
                           f"{scale.unique_requests_per_worker}/worker")
                pool = [
                    make_payload(candidates=CANDIDATES, num_fields=NUM_FIELDS, seed=100 + i)
                    for i in range(scale.unique_pool)
                ]
                res["report_u"] = await loop(
                    pool=pool,
                    rpw=scale.unique_requests_per_worker * 3,  # same total
                    conc=scale.unique_concurrency,
                )
                res["phases_unique"] = {
                    name: snap["mean_us"]
                    for name, snap in request_trace.snapshot().items()
                }

                stage = "load_loop_compact"
                # Compact wire (client/client.py compact_payload): the
                # transport is >half the single-core request budget (~1.7
                # ms/MB through grpc-python, round-4 echo floor), so the
                # framework's native wire — int32 folded ids + bf16
                # weights, scores bit-identical, 258 KB vs 516 KB — is the
                # biggest client-side throughput knob. Reported as its own
                # field; the headline stays on the reference-parity int64
                # wire (DCNClient.java:98-108).
                batcher.max_batch_candidates = min(16384, batcher.buckets[-1])
                if batcher.input_cache is not None:
                    # Phase boundary: the unique loop legitimately flipped
                    # the cache to bypass; the compact A/B measures the
                    # repeated-traffic operating point, so re-arm rather
                    # than waiting out the auto re-probe cycle.
                    batcher.input_cache.rearm()
                compact = compact_payload(payload, scale.vocab_size)
                report_c = await loop(
                    pool=None, rpw=scale.requests_per_worker,
                    prepared=True, conc=2 * scale.concurrency,
                )
                res["report_c_wide_ctrl"] = round(report_c.summary()["qps"], 1)

                async def compact_loop():
                    async with ShardedPredictClient(
                        [f"127.0.0.1:{port}"], "DCN",
                        channels_per_host=scale.channels_per_host,
                    ) as client:
                        return await run_closed_loop(
                            client, compact,
                            concurrency=2 * scale.concurrency,
                            requests_per_worker=scale.requests_per_worker,
                            sort_scores=True,
                            warmup_requests=5,
                            prepared=True,
                        )

                report_cc = await compact_loop()
                res["report_compact"] = report_cc.summary()
                log(stage, f"compact {res['report_compact']['qps']:.1f} qps vs "
                           f"wide control {res['report_c_wide_ctrl']} qps "
                           "(same window, adjacent)")
                # Restore the documented overload-probe operating point (the
                # compact A/B ran at the 16384 cap).
                batcher.max_batch_candidates = min(8192, batcher.buckets[-1])

                stage = "overload"
                res["overload"] = await overload_probe(
                    ShardedPredictClient, port, batcher, scale, payload
                )
                log(stage, json.dumps(res["overload"]))
            finally:
                await server.stop(0)

        async def measure_host_ceiling():
            nonlocal stage
            stage = "host_ceiling"
            # The measured transport ceiling, INSIDE the artifact. A second
            # server over the SAME registry but a null-device batcher
            # (run_fn returns canned scores; no jit, no transfer) serves the
            # identical closed loop on the identical host: the measured QPS
            # is everything EXCEPT the device — grpc transport + proto
            # decode/encode + batching + merge/sort — i.e. the hard upper
            # bound any device could reach through this host. headline <
            # ceiling < target means the wire is transport-bound on this
            # host, measured same-session.
            def null_run(sv, arrays):
                n = next(iter(arrays.values())).shape[0]
                return {"prediction_node": np.zeros(n, np.float32)}

            ceil_batcher = DynamicBatcher(
                buckets=scale.buckets,
                max_wait_us=2000,
                completion_workers=12,
                run_fn=null_run,
            ).start()
            try:
                ceil_impl = PredictionServiceImpl(registry, ceil_batcher)
                server, port = create_server_async(ceil_impl, "127.0.0.1:0")
                await server.start()
                try:
                    ceil_batcher.max_batch_candidates = min(
                        16384, ceil_batcher.buckets[-1]
                    )
                    loop = make_loop(port)
                    rpw = 40 if scale.tpu else 3
                    log(stage, f"null-device wide wire: conc={scale.concurrency} x {rpw}")
                    rep_w = await loop(prepared=True, conc=scale.concurrency, rpw=rpw)
                    compact = compact_payload(payload, scale.vocab_size)
                    log(stage, "null-device compact wire (same window)")
                    async with ShardedPredictClient(
                        [f"127.0.0.1:{port}"], "DCN",
                        channels_per_host=scale.channels_per_host,
                    ) as client:
                        rep_c = await run_closed_loop(
                            client, compact,
                            concurrency=scale.concurrency,
                            requests_per_worker=rpw,
                            sort_scores=True,
                            warmup_requests=5,
                            prepared=True,
                        )
                    s_w, s_c = rep_w.summary(), rep_c.summary()
                    res["host_ceiling"] = {
                        "wide_wire_ceiling_qps": round(s_w["qps"], 1),
                        "wide_p50_ms": round(s_w["p50_ms"], 3),
                        "compact_wire_ceiling_qps": round(s_c["qps"], 1),
                        "compact_p50_ms": round(s_c["p50_ms"], 3),
                        "requests_each": s_w["requests"],
                        "note": "same closed loop vs a null-device batcher "
                                "in the same process/core: transport + "
                                "decode/batch/encode with zero device "
                                "time — the measured upper bound of "
                                "this host's data plane per wire format",
                    }
                    log(stage, f"wide ceiling {s_w['qps']:.1f} qps, "
                               f"compact ceiling {s_c['qps']:.1f} qps")
                finally:
                    await server.stop(0)
            finally:
                ceil_batcher.stop()

        async def serve_transport_ab():
            nonlocal stage
            stage = "transport_ab"
            # Transport A/B + continuous-batching window (ISSUE 9): one
            # block measuring (a) the RTT floor DIRECTLY — tiny Predicts
            # over TCP loopback vs a Unix-domain socket on the same
            # server, so the transport share of the floor is measured,
            # not inferred from subtraction; (b) streamed-vs-unary
            # score bit-identity over the wire (the tentpole's
            # correctness gate) plus the client's first-scores latency;
            # (c) the k-deep pipeline at depth 4 / window 8 with the
            # buffer ring armed, reporting the window's readback-overlap
            # fraction. Runs on the LIVE batcher (depth knobs are plain
            # attributes — re-jitting a second batcher would re-compile
            # the ladder) and restores the depth-2 defaults after.
            import tempfile

            from distributed_tf_serving_tpu.serving.batcher import (
                _HostBufferRing,
            )

            uds = os.path.join(
                tempfile.gettempdir(), f"dts_bench_{os.getpid()}.sock"
            )
            server, port = create_server_async(
                impl, "127.0.0.1:0", uds_path=uds
            )
            await server.start()
            # The RTT-floor probe runs against a NULL-DEVICE impl (the
            # host_ceiling trick) on a second server with both ports: a
            # tiny Predict through the real serving path with zero device
            # time IS the transport+service floor, measured directly —
            # probing the live batcher instead would bury the sub-ms
            # transport delta under device compute jitter.
            def null_run(sv, arrays):
                n = next(iter(arrays.values())).shape[0]
                return {"prediction_node": np.zeros(n, np.float32)}

            # max_wait_us=0 + one tiny bucket: the probe's only jitter
            # sources are the transports under test (coalesce-wait and
            # bucket-ladder effects are identical noise on both sides,
            # but removing them tightens the min-floor estimate 3x).
            null_batcher = DynamicBatcher(
                buckets=(32,), max_wait_us=0, run_fn=null_run,
            ).start()
            null_impl = PredictionServiceImpl(registry, null_batcher)
            null_uds = uds + ".null"
            null_server, null_port = create_server_async(
                null_impl, "127.0.0.1:0", uds_path=null_uds
            )
            await null_server.start()
            prev = (
                batcher.pipeline_depth, batcher.inflight_window,
                batcher.buffer_ring,
            )
            batcher.pipeline_depth, batcher.inflight_window = 4, 8
            batcher.buffer_ring = _HostBufferRing()
            impl.stream_chunk_candidates = 0  # explicit chunk per call
            try:
                batcher.max_batch_candidates = min(8192, batcher.buckets[-1])
                tiny = make_payload(candidates=8, num_fields=NUM_FIELDS, seed=55)
                # Null device, so 150 iterations cost ~1 s on any
                # backend; the min over 150 interleaved
                # samples is what makes the sub-ms transport delta
                # resolvable (40 was observed to flip sign under load).
                rtt_iters = 150

                # INTERLEAVED probes: one tiny Predict per transport per
                # iteration, so host-load drift hits both floors
                # identically instead of whichever ran second.
                log(stage, f"RTT floor: {rtt_iters} interleaved tiny "
                           "Predicts, TCP vs UDS (null device)")
                tcp_ms: list = []
                uds_ms: list = []
                async with ShardedPredictClient(
                    [f"127.0.0.1:{null_port}"], "DCN", channels_per_host=1,
                ) as c_tcp, ShardedPredictClient(
                    [f"unix:{null_uds}"], "DCN", channels_per_host=1,
                ) as c_uds:
                    for c in (c_tcp, c_uds):
                        for _ in range(5):  # settle the channel + path
                            await c.predict(tiny)
                    for _ in range(rtt_iters):
                        t0 = time.perf_counter()
                        await c_tcp.predict(tiny)
                        tcp_ms.append((time.perf_counter() - t0) * 1e3)
                        t0 = time.perf_counter()
                        await c_uds.predict(tiny)
                        uds_ms.append((time.perf_counter() - t0) * 1e3)
                tcp_min, uds_min = min(tcp_ms), min(uds_ms)
                log(stage, f"rtt floor tcp={tcp_min:.3f}ms uds={uds_min:.3f}ms")

                # Streamed vs unary: same payload, same (UDS) channel —
                # scores must be bit-identical; first-scores latency is
                # the decoupling streaming buys.
                big = make_payload(
                    candidates=CANDIDATES, num_fields=NUM_FIELDS, seed=56
                )
                async with ShardedPredictClient(
                    [f"unix:{uds}"], "DCN",
                    stream_chunk_candidates=256,
                ) as c:
                    await c.predict_streamed(big)  # compile the 256 bucket
                    t0 = time.perf_counter()
                    unary = await c.predict(big, sort_scores=True)
                    unary_ms = (time.perf_counter() - t0) * 1e3
                    t0 = time.perf_counter()
                    streamed = await c.predict_streamed(big, sort_scores=True)
                    streamed_ms = (time.perf_counter() - t0) * 1e3
                    stream_stats = c.stream_stats()
                bit_identical = bool(np.array_equal(unary, streamed))
                log(stage, f"streamed bit-identical={bit_identical} "
                           f"first_scores_p50={stream_stats['first_score_p50_ms']}ms")

                # Depth-4 window: a short closed loop with the deep
                # pipeline armed; the overlap fraction is THIS window's
                # delta, not the run's lifetime average. On the CPU
                # fallback there is no physical D2H link — np.asarray
                # waits on COMPUTE, so the overlap an accelerator earns
                # by hiding its transfer behind pipelined batches is
                # structurally unreachable. The CPU block therefore
                # EMULATES the link: a deterministic 80 ms readback stall
                # (the injector's `readback` site) that the k-deep window
                # must hide —
                # overlap >= 0.9 then means the pipeline genuinely kept
                # issuing while 8 emulated transfers sat in flight.
                # Fused assembly is disabled for the window so the padded
                # batches exercise the buffer ring (the fused packer
                # builds its device buffer natively and never pads).
                from distributed_tf_serving_tpu import faults as faults_mod
                from distributed_tf_serving_tpu.client import (
                    run_closed_loop as run_loop,
                )

                small = make_payload(
                    candidates=200, num_fields=NUM_FIELDS, seed=57
                )
                prev_cap = batcher.max_batch_candidates
                batcher.max_batch_candidates = min(256, batcher.buckets[-1])
                conc = 16
                rpw = 12 if scale.tpu else 6
                emulated = not scale.tpu
                os.environ["DTS_TPU_NO_FUSED"] = "1"
                try:
                    async with ShardedPredictClient(
                        [f"127.0.0.1:{port}"], "DCN",
                        channels_per_host=scale.channels_per_host,
                    ) as c:
                        for _ in range(3):  # compile/settle the 256 bucket
                            await c.predict(small)
                        if emulated:
                            faults_mod.get().add(
                                "readback", "delay", rate=1.0, delay_s=0.08
                            )
                        log(stage, f"depth-4 window: {conc} x {rpw} "
                                   f"(emulated_d2h={emulated})")
                        before = dataclasses.replace(batcher.stats)
                        # The peak is a lifetime high-water mark (a max
                        # cannot be delta'd like the counters): reset it
                        # so the reported value is THIS window's peak —
                        # the earlier unbounded-window phases may have
                        # driven more batches in flight than the
                        # window-8 gate under test here ever allows.
                        batcher.stats.inflight_peak = 0
                        rep = await run_loop(
                            c, small, concurrency=conc,
                            requests_per_worker=rpw, sort_scores=True,
                            warmup_requests=0,
                        )
                finally:
                    if emulated:
                        faults_mod.reset()
                    os.environ.pop("DTS_TPU_NO_FUSED", None)
                    batcher.max_batch_candidates = prev_cap
                after = batcher.stats
                d_window = after.readback_window_s - before.readback_window_s
                d_blocked = after.readback_blocked_s - before.readback_blocked_s
                overlap = (
                    max(0.0, 1.0 - d_blocked / d_window) if d_window > 0 else 0.0
                )
                res["transport"] = {
                    "rtt_floor_tcp_ms": round(tcp_min, 3),
                    "rtt_floor_uds_ms": round(uds_min, 3),
                    "rtt_floor_tcp_p50_ms": round(
                        float(np.percentile(tcp_ms, 50)), 3
                    ),
                    "rtt_floor_uds_p50_ms": round(
                        float(np.percentile(uds_ms, 50)), 3
                    ),
                    "uds_gain": round(tcp_min / max(uds_min, 1e-9), 3),
                    "rtt_iters": rtt_iters,
                    "streamed_vs_unary_bit_identical": bit_identical,
                    "stream_chunk": 256,
                    "unary_ms": round(unary_ms, 3),
                    "streamed_ms": round(streamed_ms, 3),
                    "first_scores_p50_ms": stream_stats["first_score_p50_ms"],
                    "stream_chunks": stream_stats["stream_chunks"],
                    "depth4_window": {
                        "pipeline_depth": 4,
                        "inflight_window": 8,
                        "emulated_d2h_ms": 80 if emulated else None,
                        "qps": round(rep.summary()["qps"], 1),
                        "requests": rep.summary()["requests"],
                        "readback_overlap_fraction": round(overlap, 4),
                        "batches": after.batches - before.batches,
                        "inflight_peak": after.inflight_peak,
                        "window_waits": (
                            after.inflight_window_waits
                            - before.inflight_window_waits
                        ),
                        "buffer_ring": batcher.buffer_ring.snapshot(),
                    },
                }
                log(stage, json.dumps(res["transport"]))
            finally:
                (batcher.pipeline_depth, batcher.inflight_window,
                 batcher.buffer_ring) = prev
                await null_server.stop(0)
                null_batcher.stop()
                await server.stop(0)
                for path in (uds, null_uds):
                    try:
                        os.unlink(path)
                    except OSError:
                        pass

        async def serve_cache_ab(skew: float):
            nonlocal stage
            stage = "cache_skew"
            # Cache-plane A/B (ISSUE 4 acceptance): the IDENTICAL seeded
            # zipfian request stream, cache off then cache on, against the
            # live stack. Reports hit/miss/coalesced/dedup counters and a
            # bit-identity check (uncached-miss scores vs cached-hit
            # scores). Off unless --skew is passed — the headline windows
            # stay reference-methodology.
            from distributed_tf_serving_tpu.cache import ScoreCache
            from distributed_tf_serving_tpu.client import (
                make_zipfian_payloads,
                zipfian_indices,
            )

            server, port = create_server_async(impl, "127.0.0.1:0")
            await server.start()
            try:
                batcher.max_batch_candidates = min(8192, batcher.buckets[-1])
                pool_n = 64 if scale.tpu else 8
                # Enough requests per pass that the qps comparisons (and
                # the row-granular phase's goodput-vs-baseline) measure
                # steady state, not connection warmup noise.
                rpw = 40 if scale.tpu else 12
                conc = scale.unique_concurrency
                pool = make_zipfian_payloads(
                    pool_n, CANDIDATES, NUM_FIELDS, skew=skew, seed=11
                )
                sched = zipfian_indices(conc * rpw, pool_n, skew=skew, seed=12)

                async def skew_loop():
                    async with ShardedPredictClient(
                        [f"127.0.0.1:{port}"], "DCN",
                        channels_per_host=scale.channels_per_host,
                    ) as client:
                        return await run_closed_loop(
                            client, pool[0], concurrency=conc,
                            requests_per_worker=rpw, sort_scores=True,
                            warmup_requests=2, payload_pool=pool,
                            schedule=sched,
                        )

                log(stage, f"skew={skew} pool={pool_n} x {conc}x{rpw}: cache OFF pass")
                d_batches = batcher.stats.dedup_batches
                d_rows = batcher.stats.dedup_rows_collapsed
                rep_off = await skew_loop()
                cache = ScoreCache(ttl_s=600.0)
                batcher.score_cache, batcher.dedup = cache, True
                try:
                    log(stage, "cache ON pass (identical stream)")
                    rep_on = await skew_loop()
                    # Bit-identity probe against a DISARMED reference: the
                    # same payload scored with the whole plane off, then
                    # armed as a filling miss (the dedup path) and a cached
                    # hit — all three vectors must be byte-equal, or the
                    # plane is changing answers. (Comparing the hit only to
                    # its own filling miss would be tautological.)
                    probe = pool[int(sched[0])]
                    async with ShardedPredictClient(
                        [f"127.0.0.1:{port}"], "DCN", channels_per_host=1,
                    ) as client:
                        batcher.score_cache, batcher.dedup = None, False
                        ref = await client.predict(probe, sort_scores=True)
                        batcher.score_cache, batcher.dedup = cache, True
                        cache.flush()
                        miss = await client.predict(probe, sort_scores=True)
                        hit = await client.predict(probe, sort_scores=True)
                    snap = cache.snapshot()
                finally:
                    batcher.score_cache, batcher.dedup = None, False
                res["cache"] = {
                    "skew": skew,
                    "pool": pool_n,
                    "requests_each_pass": conc * rpw,
                    "qps_cache_off": round(rep_off.summary()["qps"], 1),
                    "qps_cache_on": round(rep_on.summary()["qps"], 1),
                    "p50_ms_cache_off": round(rep_off.summary()["p50_ms"], 3),
                    "p50_ms_cache_on": round(rep_on.summary()["p50_ms"], 3),
                    "hits": snap["hits"],
                    "misses": snap["misses"],
                    "coalesced": snap["coalesced"],
                    "hit_rate": snap["hit_rate"],
                    "dedup_batches": batcher.stats.dedup_batches - d_batches,
                    "dedup_rows_collapsed": (
                        batcher.stats.dedup_rows_collapsed - d_rows
                    ),
                    "scores_bit_identical": bool(
                        np.array_equal(ref, miss) and np.array_equal(ref, hit)
                    ),
                }
                # Row-granular phase (ISSUE 14): the IDENTICAL stream once
                # more with the row cache armed BEHIND the whole-request
                # cache (the deployment shape: a full hit never reaches
                # the row path; distinct payloads sharing hot rows execute
                # only their cold rows). Reports rows_executed vs
                # rows_requested, the per-row hit rate, goodput vs the
                # PR-4 whole-request baseline measured just above, and a
                # flush->miss->hit bit-identity probe against the DISARMED
                # plane (ROADMAP item 4's stated gate).
                from distributed_tf_serving_tpu.cache import RowScoreCache

                stage = "rowcache_skew"
                rowc = RowScoreCache(ttl_s=600.0)
                r_req0 = batcher.stats.rows_requested
                r_exec0 = batcher.stats.rows_executed
                cache.flush()
                batcher.score_cache, batcher.dedup = cache, True
                batcher.row_cache = rowc
                try:
                    log(stage, "row-granular pass (identical stream)")
                    rep_row = await skew_loop()
                    probe = pool[int(sched[0])]
                    async with ShardedPredictClient(
                        [f"127.0.0.1:{port}"], "DCN", channels_per_host=1,
                    ) as client:
                        batcher.score_cache, batcher.dedup = None, False
                        batcher.row_cache = None
                        row_ref = await client.predict(probe, sort_scores=True)
                        batcher.row_cache = rowc
                        rowc.flush()
                        row_miss = await client.predict(probe, sort_scores=True)
                        row_hit = await client.predict(probe, sort_scores=True)
                    rsnap = rowc.snapshot()
                finally:
                    batcher.score_cache, batcher.dedup = None, False
                    batcher.row_cache = None
                rows_req = batcher.stats.rows_requested - r_req0
                rows_exec = batcher.stats.rows_executed - r_exec0
                qps_row = rep_row.summary()["qps"]
                qps_request_baseline = rep_on.summary()["qps"]
                res["cache"]["row_cache"] = {
                    "qps_row_on": round(qps_row, 1),
                    "p50_ms_row_on": round(rep_row.summary()["p50_ms"], 3),
                    "qps_vs_request_cache": round(
                        qps_row / max(qps_request_baseline, 1e-9), 3
                    ),
                    "rows_requested": int(rows_req),
                    "rows_executed": int(rows_exec),
                    "rows_executed_fraction": round(
                        rows_exec / max(rows_req, 1), 4
                    ),
                    "row_hits": rsnap["hits"],
                    "row_coalesced": rsnap["coalesced"],
                    "row_hit_rate": rsnap["hit_rate"],
                    "row_full_hit_batches": (
                        batcher.stats.row_full_hit_batches
                    ),
                    "scores_bit_identical": bool(
                        np.array_equal(row_ref, row_miss)
                        and np.array_equal(row_ref, row_hit)
                    ),
                }
                log(stage, json.dumps(res["cache"]["row_cache"]))
                log(stage, json.dumps({
                    k: v for k, v in res["cache"].items() if k != "row_cache"
                }))
            finally:
                await server.stop(0)

        async def serve_overload_ab():
            nonlocal stage
            stage = "overload_ab"
            # Admission A/B (ISSUE 5 acceptance): the IDENTICAL seeded
            # zipfian ~3x-capacity workload against the live stack, first
            # under the static queue_capacity_candidates bound, then under
            # the adaptive AdmissionController. Capacity is pinned with a
            # deterministic injected batcher.dispatch delay so both passes
            # overload the same server, not two samples minutes apart; both
            # passes run the SAME short-TTL score cache (the deployment
            # the brownout machinery assumes), flushed between passes.
            # Goodput = in-deadline successes/s: the static bound drops
            # expired hot keys and queues their recomputes past the
            # deadline (dead work, blind retries), the controller sheds
            # early with retry-after pushback and serves hot keys STALE
            # through the brownout window while the device catches up.
            from distributed_tf_serving_tpu import faults
            from distributed_tf_serving_tpu.cache import ScoreCache
            from distributed_tf_serving_tpu.client import (
                make_zipfian_payloads,
                zipfian_indices,
            )
            from distributed_tf_serving_tpu.serving import overload as overload_mod
            from distributed_tf_serving_tpu.utils.config import OverloadConfig

            server, port = create_server_async(impl, "127.0.0.1:0")
            await server.start()
            try:
                batcher.max_batch_candidates = min(8192, batcher.buckets[-1])
                deadline_s = 2.0
                delay_s = 0.03
                workers = scale.overload_tasks
                duration_s = 10.0
                pool_n = 128
                pool = make_zipfian_payloads(
                    pool_n, CANDIDATES, NUM_FIELDS, skew=1.1, seed=901,
                    catalog=max(CANDIDATES * 4, 256),
                )
                sched = zipfian_indices(4096, pool_n, skew=1.1, seed=902)
                cache = ScoreCache(ttl_s=1.5)
                faults.get().add(
                    "batcher.dispatch", "delay", rate=1.0, delay_s=delay_s
                )
                batcher.score_cache = cache
                try:
                    log(stage, f"{workers} workers x {duration_s}s, deadline "
                               f"{deadline_s}s, dispatch delay {delay_s}s, "
                               f"zipf pool {pool_n}: STATIC pass")
                    static = await overload_ab_pass(
                        ShardedPredictClient, port, pool, sched, deadline_s,
                        workers, duration_s, scale.channels_per_host,
                    )
                    ctrl = OverloadConfig(
                        enabled=True, target_queue_wait_ms=50.0,
                        adjust_interval_s=0.25, brownout_after_intervals=3,
                        shed_after_intervals=10, recover_after_intervals=8,
                        stale_while_overloaded_s=60.0,
                        max_limit_candidates=6144, min_limit_candidates=1024,
                    ).build()
                    ctrl.bind(batcher.buckets[-1],
                              batcher.queue_capacity_candidates)
                    cache.flush()  # identical cold start for both passes
                    batcher.overload = ctrl
                    try:
                        log(stage, "ADAPTIVE pass (identical workload)")
                        adaptive = await overload_ab_pass(
                            ShardedPredictClient, port, pool, sched,
                            deadline_s, workers, duration_s,
                            scale.channels_per_host,
                        )
                    finally:
                        batcher.overload = None
                finally:
                    batcher.score_cache = None
                    faults.reset()
                    # Drop the module-level fast-path gate the controller's
                    # construction armed: later phases (host_ceiling) must
                    # not pay overload metadata scans for a detached plane.
                    overload_mod.deactivate()
                res["overload_ab"] = {
                    "deadline_s": deadline_s,
                    "dispatch_delay_s": delay_s,
                    "workers": workers,
                    "duration_s_each_pass": duration_s,
                    "zipf_pool": pool_n,
                    "cache_ttl_s": 1.5,
                    "static": static,
                    "adaptive": adaptive,
                    "controller": ctrl.snapshot(),
                    "stale_serves": cache.snapshot()["stale_serves"],
                    "goodput_gain": round(
                        adaptive["goodput_qps"]
                        / max(static["goodput_qps"], 1e-9),
                        3,
                    ),
                }
                log(stage, json.dumps(res["overload_ab"]))
            finally:
                await server.stop(0)

        async def serve_cascade_ab():
            nonlocal stage
            stage = "cascade_ab"
            # Cascade A/B (ISSUE 19 acceptance): the IDENTICAL seeded
            # candidate stream, full-model-only then retrieval->rank
            # through the in-server two-executable cascade (two_tower
            # stage 1, on-device prune, DCN over the survivors). Serves
            # through its OWN batcher: the cascade's win is survivor
            # traffic landing in a smaller rung, so the ladder must hold
            # a survivor-sized bucket (256 for 25% of 1000) the headline
            # ladder does not carry. Reports rows_ranked/rows_requested,
            # the survivor-bucket histogram, the goodput delta, and a
            # survivor bit-identity probe (cascade survivor scores vs the
            # same rows in a full DCN pass).
            from distributed_tf_serving_tpu.models import build_model
            from distributed_tf_serving_tpu.serving.cascade import (
                STAGE2,
                CascadeOrchestrator,
            )

            s1_config = dataclasses.replace(config, name="stage1")
            s1_model = build_model("two_tower", s1_config)
            s1_params = jax.jit(s1_model.init)(jax.random.PRNGKey(3))
            stage1 = Servable(
                name="stage1", version=1, model=s1_model, params=s1_params,
                signatures=ctr_signatures(config.num_fields),
            )
            registry.load(stage1)
            ab_batcher = DynamicBatcher(
                buckets=(256, 1024),
                max_wait_us=2000,
                completion_workers=12,
                output_wire_dtype="bfloat16",
                async_readback=True,
                pipelined_dispatch=True,
            ).start()
            ab_batcher.max_batch_candidates = 1024
            ab_impl = PredictionServiceImpl(registry, ab_batcher)
            server, port = create_server_async(ab_impl, "127.0.0.1:0")
            await server.start()
            try:
                log(stage, "warmup: DCN on both rungs, stage1 on 1024")
                ab_batcher.warmup(servable)
                ab_batcher.warmup(stage1, buckets=(1024,))
                pool_n = 8
                pool = [
                    make_payload(
                        candidates=CANDIDATES, num_fields=NUM_FIELDS,
                        seed=700 + i,
                    )
                    for i in range(pool_n)
                ]
                conc = scale.unique_concurrency
                rpw = 20 if scale.tpu else 8
                sched = np.arange(conc * rpw) % pool_n

                async def cascade_loop():
                    async with ShardedPredictClient(
                        [f"127.0.0.1:{port}"], "DCN",
                        channels_per_host=scale.channels_per_host,
                    ) as client:
                        return await run_closed_loop(
                            client, pool[0], concurrency=conc,
                            requests_per_worker=rpw, sort_scores=True,
                            warmup_requests=3, payload_pool=pool,
                            schedule=sched,
                        )

                log(stage, f"{conc}x{rpw}: cascade OFF pass (DCN only)")
                rep_off = await cascade_loop()
                casc = CascadeOrchestrator(
                    registry, ab_batcher, stage1_model="stage1",
                    survivor_fraction=0.25,
                )
                ab_impl.cascade = casc
                try:
                    log(stage, "cascade ON pass (identical stream)")
                    rep_on = await cascade_loop()
                    # Survivor bit-identity: the cascade's stage-2 scores
                    # must be byte-equal to the same rows of a cascade-off
                    # full pass, and its pruned rows byte-equal to a
                    # stage-1-only pass — or the cascade is changing
                    # answers, not saving work.
                    probe = pool[0]
                    sk = servable.model.score_output
                    s1k = s1_model.score_output
                    out = casc.run(ab_impl, servable, probe, (sk,), None, None)
                    ab_impl.cascade = None
                    ref = ab_impl._run(servable, probe, output_keys=(sk,))
                    ref1 = ab_impl._run(stage1, probe, output_keys=(s1k,))
                    surv = out["cascade_stage"] == STAGE2
                    bit_identical = bool(
                        np.array_equal(out[sk][surv], ref[sk][surv])
                        and np.array_equal(
                            out[sk][~surv],
                            ref1[s1k].astype(np.float32)[~surv],
                        )
                    )
                    snap = casc.snapshot()
                finally:
                    ab_impl.cascade = None
                qps_off = rep_off.summary()["qps"]
                qps_on = rep_on.summary()["qps"]
                res["cascade"] = {
                    "requests_each_pass": conc * rpw,
                    "survivor_fraction": 0.25,
                    "qps_cascade_off": round(qps_off, 1),
                    "qps_cascade_on": round(qps_on, 1),
                    "goodput_delta": round(qps_on / max(qps_off, 1e-9), 3),
                    "p50_ms_cascade_off": round(rep_off.summary()["p50_ms"], 3),
                    "p50_ms_cascade_on": round(rep_on.summary()["p50_ms"], 3),
                    "rows_requested": snap["rows_requested"],
                    "rows_ranked": snap["rows_ranked"],
                    "rank_fraction": snap["rank_fraction"],
                    "survivor_buckets": {
                        str(b): c for b, c in snap["survivor_buckets"].items()
                    },
                    "fallbacks": snap["fallbacks"],
                    "host_prunes": snap["host_prunes"],
                    "scores_bit_identical": bit_identical,
                }
                log(stage, json.dumps(res["cascade"]))
            finally:
                ab_batcher.stop()
                await server.stop(0)

        async def serve_lifecycle():
            nonlocal stage
            stage = "lifecycle_hot_swap"
            # Hot-swap cost (ISSUE 8, opt-in via DTS_BENCH_LIFECYCLE=1):
            # in-window p99 + error count while a version publish ->
            # watcher hot-load (queue warmup) -> canary -> promote runs
            # MID-WINDOW, vs an adjacent steady-state window of the same
            # closed loop. The controller runs in mechanics mode
            # (quality=None: promote on dwell alone) — this block prices
            # the swap machinery, not the rollout judgment; off by
            # default so headline numbers stay comparable.
            import dataclasses as dc_
            import tempfile

            from distributed_tf_serving_tpu.interop.export import (
                publish_version,
            )
            from distributed_tf_serving_tpu.serving.lifecycle import (
                LifecycleController,
            )
            from distributed_tf_serving_tpu.serving.version_watcher import (
                VersionWatcher,
                VersionWatcherConfig,
            )
            from distributed_tf_serving_tpu.train.checkpoint import (
                save_servable,
            )
            from distributed_tf_serving_tpu.utils.config import LifecycleConfig

            server, port = create_server_async(impl, "127.0.0.1:0")
            await server.start()
            base = tempfile.mkdtemp(prefix="bench_lifecycle_")
            watcher = VersionWatcher(
                base, registry,
                VersionWatcherConfig(
                    poll_interval_s=0.5, model_name="DCN",
                    model_kind="dcn_v2",
                ),
                # Queue warmup: the hot-loaded version compiles on the
                # batching thread BEFORE its registry flip — the compile
                # stall IS part of the swap cost this block measures.
                warmup=batcher.warmup_via_queue,
                model_config=config,
            ).start()
            ctrl = LifecycleController(
                LifecycleConfig(
                    enabled=True, tick_interval_s=0.2,
                    canary_probe_only_s=0.3, canary_initial_fraction=0.5,
                    canary_ramp_step=0.5, canary_step_dwell_s=0.5,
                    canary_max_fraction=1.0, promote_after_s=1.0,
                ),
                registry=registry, model_name="DCN", watcher=watcher,
                quality=None,
            ).start()
            impl.lifecycle = ctrl
            try:
                batcher.max_batch_candidates = min(2048, batcher.buckets[-1])
                lat_payload = make_payload(
                    candidates=CANDIDATES, num_fields=NUM_FIELDS, seed=77
                )
                conc = 4
                steady_s = float(
                    os.environ.get("DTS_BENCH_LIFECYCLE_WINDOW_S", "6")
                )

                async def timed_loop(client, run_s):
                    lat: list = []
                    errs = [0]

                    async def w():
                        end = time.perf_counter() + run_s
                        while time.perf_counter() < end:
                            t0 = time.perf_counter()
                            try:
                                await client.predict(
                                    lat_payload, sort_scores=True
                                )
                                lat.append((time.perf_counter() - t0) * 1e3)
                            except Exception:  # noqa: BLE001 — the error
                                errs[0] += 1    # COUNT is the measurement

                    await asyncio.gather(*(w() for _ in range(conc)))
                    return np.asarray(lat), errs[0]

                async with ShardedPredictClient(
                    [f"127.0.0.1:{port}"], "DCN",
                    channels_per_host=scale.channels_per_host,
                ) as client:
                    for _ in range(5):
                        await client.predict(lat_payload, sort_scores=True)
                    log(stage, f"steady window {steady_s}s x {conc} workers")
                    lat_a, err_a = await timed_loop(client, steady_s)

                    async def publish_mid():
                        await asyncio.sleep(steady_s * 0.25)
                        sv = registry.resolve("DCN")
                        loop_ = asyncio.get_running_loop()

                        def pub():
                            def write(tmp):
                                save_servable(
                                    tmp,
                                    dc_.replace(sv, version=sv.version + 1),
                                    kind="dcn_v2",
                                )
                            return publish_version(
                                base, write, at_least=sv.version + 1
                            )

                        return await loop_.run_in_executor(None, pub)

                    log(stage, f"swap window {steady_s}s (publish at 25%)")
                    (lat_b, err_b), published = await asyncio.gather(
                        timed_loop(client, steady_s), publish_mid()
                    )
                # Let the ramp settle briefly past the window so the
                # reported block shows the promote completing (the p99
                # numbers above are already frozen; this only bounds the
                # `promoted` field's truthfulness, it gates nothing).
                settle_end = time.perf_counter() + 8.0
                while (
                    ctrl.snapshot()["counters"]["promotes"] < 1
                    and time.perf_counter() < settle_end
                ):
                    await asyncio.sleep(0.25)
                snap = ctrl.snapshot()

                def pct(a, q):
                    return round(float(np.percentile(a, q)), 3) if a.size else None

                res["lifecycle"] = {
                    "window_s_each": steady_s,
                    "steady": {
                        "requests": int(lat_a.size),
                        "qps": round(lat_a.size / steady_s, 1),
                        "p50_ms": pct(lat_a, 50), "p99_ms": pct(lat_a, 99),
                        "errors": err_a,
                    },
                    "swap": {
                        "requests": int(lat_b.size),
                        "qps": round(lat_b.size / steady_s, 1),
                        "p50_ms": pct(lat_b, 50), "p99_ms": pct(lat_b, 99),
                        "errors": err_b,
                        "published_version": published[0],
                        "promoted": snap["counters"]["promotes"] >= 1,
                        "stable_version": snap["stable_version"],
                    },
                    "p99_delta_ms": (
                        round(pct(lat_b, 99) - pct(lat_a, 99), 3)
                        if lat_a.size and lat_b.size else None
                    ),
                }
                log(stage, json.dumps(res["lifecycle"]))
            finally:
                impl.lifecycle = None
                ctrl.stop()  # also drops the module criticality-scan gate
                watcher.stop()
                await server.stop(0)

        async def serve_recovery():
            nonlocal stage
            stage = "recovery"
            # Device-failure recovery cost (ISSUE 11, opt-in via
            # DTS_BENCH_RECOVERY=1): MTTR (deterministic device_lost
            # injection -> first post-recovery success) and the added
            # latency of the REPLAYED in-flight requests, vs an adjacent
            # steady window of the same closed loop — rides the PR-6
            # --json-out mirror like every diagnostic block, so a TPU
            # round records it even when stdout truncates. Off by default
            # so headlines stay comparable.
            from distributed_tf_serving_tpu import faults
            from distributed_tf_serving_tpu.serving.recovery import (
                RecoveryController,
            )
            from distributed_tf_serving_tpu.utils.config import RecoveryConfig

            server, port = create_server_async(impl, "127.0.0.1:0")
            await server.start()
            rec = RecoveryController(
                RecoveryConfig(
                    enabled=True, watchdog_interval_s=0.2,
                    wedge_quarantine_s=5.0, replay_drain_s=15.0,
                ),
                batcher, registry=registry, impl=impl,
            ).start()
            impl.recovery = rec
            try:
                batcher.max_batch_candidates = min(2048, batcher.buckets[-1])
                payload = make_payload(
                    candidates=CANDIDATES, num_fields=NUM_FIELDS, seed=88
                )
                conc = 4
                window_s = float(
                    os.environ.get("DTS_BENCH_RECOVERY_WINDOW_S", "6")
                )

                async def timed_loop(client, run_s):
                    samples: list = []  # (t_start, t_end, ms)
                    errs = [0]

                    async def w():
                        end = time.perf_counter() + run_s
                        while time.perf_counter() < end:
                            t0 = time.perf_counter()
                            try:
                                await client.predict(payload, sort_scores=True)
                                t1 = time.perf_counter()
                                samples.append((t0, t1, (t1 - t0) * 1e3))
                            except Exception:  # noqa: BLE001 — the error
                                errs[0] += 1    # COUNT is the measurement
                    await asyncio.gather(*(w() for _ in range(conc)))
                    return samples, errs[0]

                async with ShardedPredictClient(
                    [f"127.0.0.1:{port}"], "DCN",
                    channels_per_host=scale.channels_per_host,
                    scoreboard=True, failover_attempts=8,
                    backoff_initial_s=0.2, backoff_max_s=2.0,
                    timeout_s=30.0, max_attempts_total=16,
                ) as client:
                    for _ in range(5):
                        await client.predict(payload, sort_scores=True)
                    log(stage, f"steady window {window_s}s x {conc} workers")
                    steady, err_a = await timed_loop(client, window_s)
                    inject = {"t": None}

                    async def inject_mid():
                        await asyncio.sleep(window_s * 0.25)
                        inject["t"] = time.perf_counter()
                        faults.get().add(
                            "device_lost", "error", code="UNAVAILABLE",
                            count=1,
                        )

                    log(stage, f"fault window {window_s}s "
                               "(device_lost at 25%)")
                    (faulted, err_b), _ = await asyncio.gather(
                        timed_loop(client, window_s), inject_mid()
                    )
                finally_inj = inject["t"]
                steady_lat = np.asarray([ms for _, _, ms in steady])
                fault_lat = np.asarray([ms for _, _, ms in faulted])
                # Requests IN FLIGHT at injection are exactly the replayed
                # cohort. MTTR here = injection -> the LAST affected
                # request answered (fault to full recovery of the work it
                # stranded) — NOT the first post-injection success, which
                # with concurrent workers is just an unaffected request
                # finishing milliseconds later.
                replayed_done = [
                    (t1, ms) for t0, t1, ms in faulted
                    if finally_inj is not None and t0 < finally_inj < t1
                ]
                replayed = [ms for _, ms in replayed_done]
                p50_steady = (
                    float(np.percentile(steady_lat, 50))
                    if steady_lat.size else None
                )

                def pct(a, q):
                    return (
                        round(float(np.percentile(a, q)), 3) if a.size else None
                    )

                res["recovery"] = {
                    "window_s_each": window_s,
                    "steady": {
                        "requests": int(steady_lat.size),
                        "p50_ms": pct(steady_lat, 50),
                        "p99_ms": pct(steady_lat, 99),
                        "errors": err_a,
                    },
                    "fault_window": {
                        "requests": int(fault_lat.size),
                        "p50_ms": pct(fault_lat, 50),
                        "p99_ms": pct(fault_lat, 99),
                        "errors": err_b,
                    },
                    "mttr_s": (
                        round(max(t1 for t1, _ in replayed_done)
                              - finally_inj, 3)
                        if replayed_done and finally_inj is not None
                        else None
                    ),
                    # The controller's own cycle clock (detection ->
                    # reinit -> replay drained) next to the wall-clock
                    # MTTR above.
                    "cycle_duration_s": (
                        (rec.snapshot()["last_cycle"] or {}).get("duration_s")
                    ),
                    "replayed_requests": len(replayed),
                    "replayed_added_ms": (
                        round(max(replayed) - p50_steady, 3)
                        if replayed and p50_steady is not None else None
                    ),
                    "controller": {
                        k: v for k, v in rec.snapshot()["counters"].items()
                    },
                }
                log(stage, json.dumps(res["recovery"]))
            finally:
                impl.recovery = None
                rec.stop()
                faults.get().clear("device_lost")
                await server.stop(0)

        asyncio.run(serve_windows())
        report = res["report"]
        s = report.summary()
        stats_rep = res["stats_rep"]
        phases = res["phases"]
        qps = s["qps"]

        # The headline fields; the diagnostic blocks measured below join
        # them in the one line printed at the end.
        line = {
            "metric": "ctr_qps_per_chip_1k",
            "value": round(qps, 1),
            "unit": "qps",
            "vs_baseline": round(qps / TARGET_QPS, 3),
            "p50_ms": round(s["p50_ms"], 3),
            "p99_ms": round(s["p99_ms"], 3),
            "requests": s["requests"],
            "concurrency": s["concurrency"],
            "qps_repeated": round(qps, 1),
            "windows_qps": res["windows_qps"],
            "headline_window": "median",
            "headline_batch_cap": res["headline_batch_cap"],
            "best_window": res["best_window"],
            "rtt_floor_ms": None if rtt_floor_ms is None else round(rtt_floor_ms, 2),
            "latency_mode": res.get("latency_mode"),
            "train": train_block,
            "device": device,
            "platform": platform,
            "device_kind": device_kind,
            "device_count": len(jax.devices()),
        }
        log("headline", f"headline windows complete: {qps:.1f} qps")

        # Transport A/B + k-deep pipeline window (ISSUE 9).
        asyncio.run(serve_transport_ab())

        stage = "pallas"
        pallas_block = pallas_probe(scale, config, params["cross"])
        log(stage, json.dumps(pallas_block))

        stage = "device_decomposition"
        device_block = device_decomposition(
            batcher, servable, scale, rtt_floor_ms, device_kind
        )
        log(stage, json.dumps(device_block))

        asyncio.run(serve_unique_and_overload())
        report_u = res["report_u"]
        s_u = report_u.summary()
        phases_unique = res["phases_unique"]
        overload_block = res["overload"]

        skew = _skew_flag()
        if skew is not None:
            asyncio.run(serve_cache_ab(skew))
        if _overload_flag():
            asyncio.run(serve_overload_ab())
        if _cascade_flag():
            asyncio.run(serve_cascade_ab())
        if os.environ.get("DTS_BENCH_LIFECYCLE", "0") == "1":
            asyncio.run(serve_lifecycle())
        if os.environ.get("DTS_BENCH_RECOVERY", "0") == "1":
            asyncio.run(serve_recovery())
        if os.environ.get("DTS_BENCH_KERNELS", "0") == "1":
            stage = "kernels"
            res["kernels"] = kernel_ab_block(batcher, servable, scale, config)
            log(stage, json.dumps({
                "decisions": res["kernels"]["decisions"],
                "any_enabled": res["kernels"]["any_enabled"],
            }))
        if os.environ.get("DTS_BENCH_MESH", "0") == "1":
            stage = "mesh"
            res["mesh"] = mesh_ab_block(device)
            log(stage, json.dumps({
                "emulated": res["mesh"].get("emulated"),
                "bit_identical": res["mesh"].get("bit_identical"),
                "qps": {
                    m: b.get("qps")
                    for m, b in (res["mesh"].get("modes") or {}).items()
                },
            }))
        if os.environ.get("DTS_BENCH_ELASTIC", "0") == "1":
            stage = "elastic"
            res["elastic"] = elastic_ab_block(device)
            log(stage, json.dumps({
                "emulated": res["elastic"].get("emulated"),
                "bit_identical": res["elastic"].get("bit_identical"),
                "switch_count": res["elastic"].get("switch_count"),
                "goodput_gain_by_phase": res["elastic"].get(
                    "goodput_gain_by_phase"
                ),
            }))
        batcher.stop()

        asyncio.run(measure_host_ceiling())

        stage = "report"
        dev_qps = device_block.get("device_limited_qps") or 0.0
        line.update({
            "mean_ms": round(s["mean_ms"], 3),
            "candidates_per_s": round(s["candidates_per_s"], 0),
            "wall_s": round(s["wall_s"], 1),
            "qps_unique": round(s_u["qps"], 1),
            "p50_ms_unique": round(s_u["p50_ms"], 3),
            # Framework-native wire, measured against a same-window wide
            # control (adjacent A/B; headline stays reference wire).
            "qps_compact_wire": round(res["report_compact"]["qps"], 1),
            "p50_ms_compact": round(res["report_compact"]["p50_ms"], 3),
            "qps_wide_control_for_compact": res["report_c_wide_ctrl"],
            "batch_occupancy": round(stats_rep.mean_occupancy, 3),
            "requests_per_batch": round(stats_rep.mean_requests_per_batch, 2),
            "batches": stats_rep.batches,
            "fused_batches": stats_rep.fused_batches,
            "fill_waits": stats_rep.fill_waits,  # best window's, like the rest
            "input_cache": (
                {
                    "hits": batcher.input_cache.hits,
                    "misses": batcher.input_cache.misses,
                    "mb_upload_skipped": round(batcher.input_cache.bytes_skipped / 1e6, 1),
                    "bypassed": batcher.input_cache.bypassed,
                    "bypass_cycles": batcher.input_cache.bypass_cycles,
                }
                if batcher.input_cache is not None
                else None
            ),
            "achieved_fraction_of_device_limit": round(qps / dev_qps, 3) if dev_qps else None,
            # Utilization plane (ISSUE 6): occupancy ledger + gap
            # waterfall over the headline windows — wall time decomposed
            # into device/H2D/D2H + idle-by-cause (components sum to the
            # window's wall by construction) with the LIVE
            # achieved_fraction_of_device_limit estimate next to the
            # offline one above.
            "utilization": res.get("utilization"),
            # Quality plane (ISSUE 7, DTS_BENCH_QUALITY=1): the served-
            # score distribution sketch over the headline windows — per-
            # (model, version) count/mean/percentiles; absent when the
            # plane is off (the default, keeping headlines comparable).
            "quality": res.get("quality"),
            # Lifecycle hot-swap cost (ISSUE 8, DTS_BENCH_LIFECYCLE=1):
            # in-window p99 + errors during a mid-run publish -> hot-load
            # -> canary -> promote vs an adjacent steady window; absent
            # when the block is off (the default).
            "lifecycle": res.get("lifecycle"),
            # Device-failure recovery cost (ISSUE 11, DTS_BENCH_RECOVERY
            # =1): MTTR (fault injection -> first post-recovery success)
            # and the replayed in-flight requests' added latency vs the
            # steady window; absent when the block is off (the default).
            "recovery": res.get("recovery"),
            # Kernel autotune A/B (ISSUE 12, DTS_BENCH_KERNELS=1): per-
            # bucket XLA/Pallas x f32/int8 step times + the emitted
            # decision table + wire-bytes deltas + the max|dScore| / AUC
            # gates; absent when the block is off (the default). The
            # decision table also lands in artifacts/kernel_autotune.json
            # for serving processes on this device to adopt.
            "kernels": res.get("kernels"),
            # Mesh serving A/B (ISSUE 13, DTS_BENCH_MESH=1): single-chip
            # vs {N,1} vs {N/2,2} serving throughput with a cross-mode
            # bit-identity gate; `emulated` records whether the modes
            # ran on forced CPU devices (functional trajectory point) or
            # a live slice (real throughput). Absent when off (default).
            "mesh": res.get("mesh"),
            # Elastic serving A/B (ISSUE 15, DTS_BENCH_ELASTIC=1): the
            # same seeded ramped stream (nominal -> pressure ->
            # recovery) against a pinned {N/2,2} split vs the elastic
            # ladder — per-phase goodput, switch count + history, the
            # first post-switch latency next to the steady p50 (warmup-
            # built executables only: no compile spike), bit-identity
            # across runs, and the emulated-vs-live flag. Absent when
            # off (default).
            "elastic": res.get("elastic"),
            # Multi-stage cascade A/B (ISSUE 19, --cascade): the same
            # seeded candidate stream DCN-only vs retrieval->rank through
            # the two-executable cascade — rows_ranked/rows_requested,
            # the survivor-bucket histogram, the goodput delta, and the
            # survivor bit-identity gate. Absent when off (default).
            "cascade": res.get("cascade"),
            # Output-transfer pipeline attribution (ISSUE 1): wire bytes
            # fetched vs. the full-fp32 all-outputs baseline, and the
            # fraction of the in-flight D2H window the completers never
            # blocked on. Headline window's delta (same provenance as
            # batch_occupancy); the full-run cumulative block rides along
            # for the warmup-inclusive totals.
            "readback": {
                "window": _transfer_counters(stats_rep),
                "run_total": _transfer_counters(batcher.stats),
                "output_wire_dtype": batcher.output_wire_dtype,
                "async_readback": batcher.async_readback,
                "pipelined_dispatch": batcher.pipelined_dispatch,
            },
            # Resilience layer (ISSUE 2): server-side deadline sheds plus
            # the headline client's scoreboard/hedge/partial counters —
            # zero in a healthy closed loop; the chaos soak and the
            # deterministic tests are where they move.
            "resilience": {
                "deadline_sheds": batcher.stats.deadline_sheds,
                "client": res.get("resilience_client"),
            },
            # Measured latency operating point — the number the <=2 ms
            # north star is judged against.
            "p50_latency_mode_ms": (
                res["latency_mode"]["p50_ms"] if res.get("latency_mode") else None
            ),
            # Measured same-session transport ceiling.
            "wide_wire_ceiling_qps": (
                res["host_ceiling"]["wide_wire_ceiling_qps"]
                if res.get("host_ceiling") else None
            ),
            "host_ceiling": res.get("host_ceiling"),
            "p50_colocated_est": colocated_latency_estimate(
                phases, device_block, stats_rep, res["headline_batch_cap"]
            ),
            "pallas": pallas_block,
            "device_decomposition": device_block,
            "overload": overload_block,
            # Transport A/B + continuous-batching window (ISSUE 9): the
            # measured TCP-vs-UDS RTT floor, streamed-vs-unary score
            # bit-identity + first-scores latency, and the depth-4 /
            # window-8 pipeline's readback-overlap fraction — the block
            # ROADMAP item 1's achieved-fraction trajectory reads.
            "transport": res.get("transport"),
            # Cache-plane A/B (--skew): seeded zipfian stream replayed
            # cache-off/cache-on, hit/coalesced/dedup counters + score
            # bit-identity. None when --skew was not passed.
            "cache": res.get("cache"),
            # Admission A/B (--overload): identical overloaded workload,
            # static bound vs adaptive controller — goodput (in-deadline
            # successes/s), shed/deadline breakdown, pushback vs ejection.
            # None when --overload was not passed.
            "overload_ab": res.get("overload_ab"),
            "phases_us": phases,
            "phases_us_unique": phases_unique,
        })
        if trace_out:
            from distributed_tf_serving_tpu.utils import tracing as span_tracing

            rec = span_tracing.recorder()
            events = rec.write_chrome_trace(trace_out)
            line["trace_out"] = {
                "path": trace_out,
                "events": events,
                "recorded": rec.recorded,
                "retained": len(rec.spans()),
            }
            log("tracing", f"chrome trace written: {events} events -> {trace_out}")
        print(json.dumps(line), flush=True)
    except Exception as exc:  # noqa: BLE001 — the JSON line IS the error report
        import traceback

        traceback.print_exc(file=sys.stderr)
        fail(stage, f"{type(exc).__name__}: {exc}")


if __name__ == "__main__":
    main()
