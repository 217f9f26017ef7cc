#!/usr/bin/env python
"""Mixed-surface robustness soak against the real serving stack.

A committed, re-runnable tool for both platforms: the same pressure on
the CPU (CI) and against a real chip's timing behavior.

Traffic mix on ONE event loop (the deployed topology):
- gRPC workers interleaving wide / compact / unique payloads every few
  requests (exercises the widening validator, the content-addressed device
  cache's regime detector, and the fused batch assembler under mixed
  dtypes);
- REST workers alternating :predict (columnar) with :classify Examples
  (exercises the JSON plane and the Example decode path into the same
  batcher);
- a control-plane worker hammering GetModelStatus and flipping a version
  label via HandleReloadConfigRequest every ~200 ms (the registry lock
  under data-plane pressure; labels route no soak traffic, so flips must
  never perturb scores or error counts).

Reports one JSON line: per-surface request/error counts, error classification,
RSS start/end (leak watch), batcher + input-cache counters, wall/QPS, and
(when sampling is enabled) a request_log block with written/dropped/
parsed-back counts.
Env knobs: SOAK_SECONDS (default 300), SOAK_GRPC_WORKERS (8),
SOAK_REST_WORKERS (4), SOAK_CANDIDATES (1000),
SOAK_CACHE=1 (cache plane armed: score cache + single-flight + dedup on
the batcher, gRPC workers on a seeded zipfian workload —
SOAK_CACHE_SKEW/SOAK_CACHE_SEED — plus a pre-flight bit-identity probe;
the JSON line gains a `cache` block with hit/miss/coalesced/dedup
counters and `scores_match`),
SOAK_ROWCACHE=1 (cache mode plus the ROW-GRANULAR cache, ISSUE 14: only
cold rows execute; adds a `row_cache` block with per-row hit/miss
counters, rows_executed vs rows_requested, and a row-path bit-identity
probe — the TIER1_ROWCACHE_SMOKE gate reads it),
SOAK_CASCADE=1 (multi-stage cascade armed, ISSUE 19: a two_tower stage-1
servable joins the registry, every score-filtered gRPC request runs
retrieval->rank through serving/cascade.py — stage-1 full batch,
on-device prune to 25% survivors, DCN over the survivor rung only — with
a pre-flight bit-identity probe (cascade survivor scores vs a full-pass
reference; pruned rows vs stage-1-only) and live /cascadez + Prometheus
+ phase-span probes; the JSON line gains a `cascade` block the
TIER1_CASCADE_SMOKE gate reads),
SOAK_REQUEST_LOG_SAMPLING (default 0 = logging off; >0 stresses the
bounded-queue request logger under the mixed load — note it adds a
SerializeToString per sampled request, so A/Bs against logging-off soaks
are not apples-to-apples).

Overload mode (SOAK_OVERLOAD=1): the adaptive overload plane (ISSUE 5,
serving/overload.py) under ~3x sustainable load. Capacity is made
deterministic with an injected batcher.dispatch delay
(SOAK_OVERLOAD_DISPATCH_DELAY_S, default 0.03 -> ~33 batches/s), the
worker pool is sized ~3x what that drains, and a mid-run BURST
(SOAK_OVERLOAD_BURST_WORKERS, default +grpc_workers/2) runs from 40% to
70% of the soak. The batcher runs an AdmissionController (self-tuning
limit, criticality lanes, doomed-work refusal, brownout stale-serve
through a short-TTL score cache on a zipfian workload) and gRPC workers
carry a short deadline (SOAK_OVERLOAD_DEADLINE_S, default 2.0) so goodput
= in-deadline successes/s. One worker in three sends
criticality=sheddable. The client runs the scoreboard with
failover_attempts=1: RESOURCE_EXHAUSTED sheds must register as PUSHBACK
(busy), never ejection. The JSON line gains an `overload` block —
goodput_qps, the controller snapshot (sheds / doomed_refusals /
brownout_serves / limit / queue_wait_p99_ms), cache stale_serves, and
client pushback counters — gated in CI by tools/check_overload_smoke.py
(nonzero sheds, nonzero brownout serves, zero ejections, goodput floor).

Chaos mode (SOAK_CHAOS=1, seeded by SOAK_CHAOS_SEED): deterministic fault
injection (distributed_tf_serving_tpu/faults.py) rides the same soak —
low-rate injected RPC errors + delays at the client.rpc / batcher.dispatch
/ readback sites while the gRPC client runs with the health scoreboard on.
The JSON line gains `chaos` (per-site fire counts) and `resilience`
(client counters + scoreboard) blocks; injected UNAVAILABLEs land in the
error classification, so a chaos soak PASSES when the classification shows nothing
BUT the injected codes and the stack neither leaks nor wedges.

Utilization mode (SOAK_UTIL=1): the device-utilization attribution plane
(ISSUE 6, serving/utilization.py) rides the soak — the batcher runs an
OccupancyLedger (busy/idle timeline, idle-gap cause attribution,
pipeline-depth gauge), and before shutdown the soak probes the LIVE
`GET /utilz` route and the Prometheus endpoint over HTTP. The JSON line
gains a `utilization` block — the ledger snapshot (gap waterfall whose
components must sum to wall, live achieved_fraction_of_device_limit),
`utilz_enabled` from the live route, and `prometheus_series` (the count
of dts_tpu_utilization_* exposition lines) — gated in CI by
tools/check_util_smoke.py (nonzero busy intervals, components sum to
wall within 2%, Prometheus series present). When SOAK_TRACE_OUT is also
set, the exported Chrome trace carries the per-device occupancy counter
track (tools/check_trace.py --require-counter-track).

Quality mode (SOAK_QUALITY=1): the model-quality observability plane
(ISSUE 7, serving/quality.py) rides a purpose-built workload. The soak
model is first TRAINED briefly on the synthetic CTR stream
(SOAK_QUALITY_TRAIN_STEPS, default 200) so its scores carry real signal
against the stream's known teacher logits; gRPC workers then serve
payload pools generated from that same stream, generate each row's label
from the teacher (Bernoulli of the teacher logit — the data-gen's own
labeling), and report labels to the LIVE `POST /labelz` route keyed by
per-row digests (client.label_keys). Mid-run the reference distribution
is pinned via `POST /qualityz/snapshot` (~40%), and a deliberately
SHIFTED traffic segment (feature weights scaled, labels regenerated from
the teacher on the shifted rows) starts at ~55% — driving windowed PSI
vs the pinned reference above threshold, which must force-keep
`quality.drift` exemplar traces into /tracez. The JSON line gains a
`quality` block — windowed AUC from the live /qualityz route next to the
exact AUC the soak computes offline from its own (score, label) log,
joined/orphaned counts, the drift block, the exemplar-trace count found
in the live /tracez body, and the Prometheus text written to
SOAK_QUALITY_PROM_OUT for the exposition lint — gated in CI by
tools/check_quality_smoke.py (which also runs tools/check_prom.py on
the captured text).

Lifecycle mode (SOAK_LIFECYCLE=1): the continuous-freshness plane
(ISSUE 8, serving/lifecycle.py) end to end against live traffic. The
soak model trains briefly, lands as version 1 of a WATCHED base dir (a
real VersionWatcher with a fast poll), and a LifecycleController with
fast ramp/dwell knobs runs armed on the impl while gRPC workers (one on
the probe criticality lane) serve a steady payload pool. A driver task
then (a) fine-tunes and publishes a GOOD canary through
train/publisher.py::publish_finetuned — the watcher hot-loads it
mid-traffic, probe-lane then ramped default-lane traffic feeds its
quality sketches, and the controller auto-PROMOTES it; (b) publishes a
POISONED canary (params scaled, scores saturate) — version-pair PSI
crosses the rollback threshold and the controller auto-ROLLS-BACK:
the watcher retires + blacklists the version, and the soak lets several
reconcile passes run to prove the blacklist holds while the bad
directory still sits ready on disk. End probes hit the LIVE /lifecyclez,
/monitoring?section=lifecycle, and Prometheus surfaces. The JSON line
gains a `lifecycle` block — promote/rollback counters and waits, final
loaded versions, blacklist persistence, routed-traffic counters, live
route/series probes — gated in CI by tools/check_lifecycle_smoke.py
(promote AND rollback observed, blacklist survived reconcile, ZERO
failed requests attributable to either swap).

Recovery mode (SOAK_RECOVERY=1): the device-failure recovery plane
(ISSUE 11, serving/recovery.py) end to end against live traffic on a
depth-4 continuous-batching pipeline (inflight_window=4, buffer ring).
A RecoveryController with a fast watchdog runs armed while gRPC workers
(scoreboard + deep failover retries whose horizon outlasts the cycle,
plus the new per-request max_attempts_total budget) hammer the replica.
A driver task then (a) WEDGES the device stage (faults.py wedge rule) —
the watchdog must escalate the wedge clock into a quarantine (health
NOT_SERVING), replace the stranded worker pools, reinit + re-warm the
executor, and replay the captured pipeline with zero client-visible
failures; MTTR is measured from injection to the first post-recovery
success; (b) submits a content-keyed POISONED input (device_lost rule
keyed on batcher.poison_fault_key) coalesced with clean companions —
the bisection must fail exactly the poison with PoisonedInputError
(INVALID_ARGUMENT) while the companions replay to success. End probes
hit the LIVE /recoveryz, /monitoring?section=recovery, and Prometheus
surfaces. The JSON line gains a `recovery` block gated in CI by
tools/check_recovery_smoke.py (quarantine + replay observed, MTTR
bounded, zero non-poison failures, bisection isolating the poison).

Integrity mode (SOAK_INTEGRITY=1): the data-integrity plane (ISSUE 20,
serving/integrity.py) under live traffic with all three silent-corruption
fault sites armed mid-run. The plane runs with shadow_fraction=1.0
(SOAK_INTEGRITY_SHADOW) and the recovery controller armed; the gRPC
client verifies response checksums (integrity_checksums=True) with
scoreboard + deep failover. The scenario: pre-traffic CLEAN bit-identity
probe (plane detached vs attached with a forced shadow audit — the plane
must never change answers); phase 1 arms `score_nan` with shadow stood
down (the readback screen must catch NaN rows row-granularly and
escalate past screen_trips_per_window into an output_corrupt recovery
cycle); phase 2 re-arms shadow and injects `readback_bitflip` (the
bit-identical shadow compare must catch every flipped batch before
delivery and escalate) plus `wire_corrupt` both directions (request-side
keyed on feat_ids — the server must fail exactly the damaged request
with a corrupt-wire INVALID_ARGUMENT; response-side keyed "response" —
the client verify must catch the flip and retry, never merging corrupt
scores); detection-to-success MTTR is measured from the first shadow
mismatch; a closing clean bit-identity probe runs after faults clear.
The JSON line gains an `integrity` block — the plane snapshot, both
bit-identity verdicts, per-phase screen counters, MTTR, client
corrupt_responses / nan_scores_merged, recovery escalation counters, and
live /integrityz + ?section=integrity + Prometheus probes — gated in CI
(TIER1_INTEGRITY_SMOKE=1) by tools/check_integrity_smoke.py (detections
on every layer, zero NaN merges, zero corrupt deliveries, bit-identity
both ends, escalation observed).

Fleet mode (SOAK_FLEET=1): the fleet robustness plane (ISSUE 17,
fleet/) as REAL PROCESSES — SOAK_FLEET_REPLICAS (default 3) serving
replicas, each a full `serving.server` subprocess with a version watcher
+ lifecycle controller over ONE shared versioned base dir and an armed
[fleet] gossip agent, behind one `fleet.router` subprocess (embedded
ShardedPredictClient: scoreboard + jump-hash affinity + failover,
gossip-fed steering, grpc.health.v1 Watch subscriptions, rollout
coordinator). Edge traffic dials ONLY the router. The kill/restart
chaos script, all mid-traffic: steady window → bit-identity probe
(router response vs a direct backend call on the same payload) →
SIGKILL one replica (the router must absorb it: zero edge-visible
errors, per-1s goodput ≥ half the steady median) → restart it (it must
rejoin the rotation via gossip, measured) → publish a canary version
into the shared base dir (every replica's watcher hot-loads it, every
lifecycle starts its ramp) → POST /lifecyclez/rollback on ONE replica —
the router's rollout coordinator must blacklist the version FLEET-WIDE
(every replica's rolled_back_version flips) within about one gossip
interval of the router's state change, measured → closing bit-identity
probe. The JSON line gains a `fleet` block — request/error counts,
per-1s goodput windows, rejoin/propagation timings, both bit-identity
probes, router /fleetz counters, dts_tpu_fleet_* series counts from the
router's gossip-port /metrics and a replica's REST exposition — gated
in CI by tools/check_fleet_smoke.py. Knobs: SOAK_FLEET_REPLICAS,
SOAK_FLEET_GOSSIP_INTERVAL_S (0.25), SOAK_FLEET_FIELDS (8),
SOAK_CANDIDATES (24 here), SOAK_GRPC_WORKERS (4 here).

Fleet observability mode (SOAK_FLEET=1 + SOAK_TRACE_OUT=/path, ISSUE
18): the fleet soak additionally arms the fleet observability plane —
[observability] tracing + trace_export on every replica AND the router
(sample rate 1.0 so every request is kept), [slo] on the router with
soak-scale windows, and tracing enabled in THIS edge process. After the
chaos script settles, the edge recorder's span trees are POSTed to the
router's /tracez/ingest (source "client"), the router's /tracez is
polled until it serves >= 1 STITCHED trace spanning client + router +
replica, the multi-pid Chrome export (/tracez?format=chrome) is written
to SOAK_TRACE_OUT, and /fleet/monitoring + /sloz + /monitoring are
probed. The JSON line gains a `fleetobs` block (stitched/3-process
trace counts, the hop waterfall, aggregate-vs-member qps, the SLO
snapshot, Chrome event count + artifact path) — gated in CI
(TIER1_FLEETOBS_SMOKE=1) by tools/check_fleetobs_smoke.py plus
tools/check_trace.py --require-multi-pid on the artifact. The plain
fleet smoke (no SOAK_TRACE_OUT) is unchanged.

Tracing (SOAK_TRACE_OUT=/path/trace.json): per-request span tracing runs
for the whole soak (utils/tracing.py; SOAK_TRACE_SAMPLE sets the tail-
sampling rate, default 0.05 — errors/fault-annotated/slowest-N traces are
always kept), the live `/tracez?format=chrome` endpoint is probed over
HTTP before shutdown, and its Chrome-trace-event JSON (Perfetto-loadable)
is written to the given path. The JSON line gains a `trace` block
(recorded/retained/event counts + the artifact path) — the CI smoke step
(tools/ci_tier1.sh TIER1_TRACE_SMOKE=1) asserts the artifact is schema-
valid and non-empty via tools/check_trace.py.
"""

import asyncio
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NUM_FIELDS = 43


def rss_gb() -> float:
    with open("/proc/self/status") as f:
        for ln in f:
            if ln.startswith("VmRSS:"):
                return round(int(ln.split()[1]) / 1e6, 3)
    return 0.0


def _fleet_soak(seconds: float) -> None:
    """SOAK_FLEET=1: the kill/restart chaos soak against a real
    multi-process fleet (module docstring, "Fleet mode"). Self-contained:
    the in-process soak stack below is the wrong shape for a scenario
    whose whole point is processes dying."""
    import shutil
    import socket
    import subprocess
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    import grpc
    import jax
    import numpy as np

    from distributed_tf_serving_tpu.client import (
        ShardedPredictClient,
        make_payload,
    )
    from distributed_tf_serving_tpu.models import (
        ModelConfig,
        Servable,
        build_model,
        ctr_signatures,
    )
    from distributed_tf_serving_tpu.proto import health as health_proto
    from distributed_tf_serving_tpu.train.checkpoint import save_servable

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fields = int(os.environ.get("SOAK_FLEET_FIELDS", "8"))
    replicas = int(os.environ.get("SOAK_FLEET_REPLICAS", "3"))
    candidates = int(os.environ.get("SOAK_CANDIDATES", "24"))
    workers = int(os.environ.get("SOAK_GRPC_WORKERS", "4"))
    gossip_interval = float(
        os.environ.get("SOAK_FLEET_GOSSIP_INTERVAL_S", "0.25")
    )
    ttl_s = max(gossip_interval * 6, 1.5)
    # Fleet observability mode (ISSUE 18): SOAK_TRACE_OUT in fleet mode
    # arms tracing + trace export fleet-wide and the SLO monitor on the
    # router; the Chrome multi-pid export lands at this path.
    trace_out = os.environ.get("SOAK_TRACE_OUT", "")
    fleetobs = bool(trace_out)
    if fleetobs:
        from distributed_tf_serving_tpu.utils import tracing as edge_tracing
        edge_tracing.enable(buffer_size=512, sample_rate=1.0)
    start_rss = rss_gb()
    t_start = time.time()

    tmp = tempfile.mkdtemp(prefix="soak_fleet_")
    base = os.path.join(tmp, "models")
    os.makedirs(base)

    # Tiny servable: the soak measures the fleet plane, not the forward.
    config = ModelConfig(
        name="DCN", num_fields=fields, vocab_size=1 << 12, embed_dim=8,
        mlp_dims=(16,), num_cross_layers=1, cross_full_matrix=True,
    )
    model = build_model("dcn_v2", config)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    servable = Servable(
        name="DCN", version=1, model=model, params=params,
        signatures=ctr_signatures(fields),
    )
    save_servable(os.path.join(base, "1"), servable, kind="dcn_v2")

    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    grpc_ports = [free_port() for _ in range(replicas)]
    rest_ports = [free_port() for _ in range(replicas)]
    gossip_ports = [free_port() for _ in range(replicas)]
    router_port = free_port()
    router_gossip = free_port()
    backend_addrs = [f"127.0.0.1:{p}" for p in grpc_ports]
    router_addr = f"127.0.0.1:{router_port}"

    # Star topology: every replica gossips with the router only; push-pull
    # through the common peer converges the full membership view.
    for i in range(replicas):
        with open(os.path.join(tmp, f"replica{i}.toml"), "w") as f:
            f.write(
                f'[server]\n'
                f'host = "127.0.0.1"\n'
                f'port = {grpc_ports[i]}\n'
                f'model_kind = "dcn_v2"\n'
                f'model_name = "DCN"\n'
                f'num_fields = {fields}\n'
                f'buckets = [8, 16, 32]\n'
                f'max_workers = 8\n'
                f'file_system_poll_wait_seconds = 0.5\n'
                f'\n'
                f'[lifecycle]\n'
                f'enabled = true\n'
                f'tick_interval_s = 0.2\n'
                f'canary_probe_only_s = 0.5\n'
                f'canary_initial_fraction = 0.25\n'
                f'canary_ramp_step = 0.05\n'
                f'canary_step_dwell_s = 30.0\n'
                f'canary_max_fraction = 0.3\n'
                f'promote_after_s = 3600.0\n'
                f'rollback_hold_s = 60.0\n'
                f'\n'
                f'[fleet]\n'
                f'enabled = true\n'
                f'self_id = "{backend_addrs[i]}"\n'
                f'gossip_port = {gossip_ports[i]}\n'
                f'peers = ["127.0.0.1:{router_gossip}"]\n'
                f'gossip_interval_s = {gossip_interval}\n'
                f'record_ttl_s = {ttl_s}\n'
            )
            if fleetobs:
                f.write(
                    '\n'
                    '[observability]\n'
                    'tracing = true\n'
                    'trace_sample_rate = 1.0\n'
                    'trace_export = true\n'
                )
    router_toml = os.path.join(tmp, "router.toml")
    with open(router_toml, "w") as f:
        f.write(
            f'[server]\n'
            f'host = "127.0.0.1"\n'
            f'port = {router_port}\n'
            f'\n'
            f'[client]\n'
            f'hosts = {json.dumps(backend_addrs)}\n'
            f'model_name = "DCN"\n'
            f'num_fields = {fields}\n'
            f'timeout_s = 5.0\n'
            f'health_scoreboard = true\n'
            f'ejection_failures = 1\n'
            f'ejection_interval_s = 1.0\n'
            f'failover_attempts = 2\n'
            f'backoff_initial_ms = 10\n'
            f'partial_results = false\n'
            f'placement = "affinity"\n'
            f'\n'
            f'[fleet]\n'
            f'enabled = true\n'
            f'self_id = "router"\n'
            f'gossip_port = {router_gossip}\n'
            f'peers = {json.dumps([f"127.0.0.1:{p}" for p in gossip_ports])}\n'
            f'gossip_interval_s = {gossip_interval}\n'
            f'record_ttl_s = {ttl_s}\n'
            f'rollout_writer = true\n'
            f'rollout_state_file = "{os.path.join(tmp, "rollout.json")}"\n'
        )
        if fleetobs:
            # Soak-scale SLO windows: short/long must both fill within
            # the run so the burn rates carry real deltas.
            f.write(
                '\n'
                '[observability]\n'
                'tracing = true\n'
                'trace_sample_rate = 1.0\n'
                'trace_export = true\n'
                'trace_export_interval_s = 0.5\n'
                '\n'
                '[slo]\n'
                'enabled = true\n'
                'latency_target_ms = 100.0\n'
                'short_window_s = 2.0\n'
                'long_window_s = 8.0\n'
            )

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = repo_root + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )

    def _log_tails(note: str) -> None:
        print(f"# fleet soak FAILED: {note}", file=sys.stderr)
        for name in sorted(os.listdir(tmp)):
            if name.endswith(".log"):
                with open(os.path.join(tmp, name), "rb") as f:
                    tail = f.read()[-4000:].decode("utf-8", "replace")
                print(f"# ---- {name} tail ----\n{tail}", file=sys.stderr)

    def spawn_replica(i: int) -> subprocess.Popen:
        lf = open(os.path.join(tmp, f"replica{i}.log"), "ab")
        return subprocess.Popen(
            [sys.executable, "-m",
             "distributed_tf_serving_tpu.serving.server",
             "--config", os.path.join(tmp, f"replica{i}.toml"),
             "--model-base-path", base,
             "--rest-port", str(rest_ports[i])],
            stdout=lf, stderr=lf, env=env, cwd=repo_root,
        )

    def wait_serving(addr: str, proc, timeout: float) -> None:
        deadline = time.time() + timeout
        last = "<no attempt>"
        while time.time() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"server {addr} exited rc={proc.returncode}"
                )
            # Fresh channel per attempt: a channel created before the
            # server listens can sit out a reconnect backoff long after
            # the port is up; boot-time probing wants the connect NOW.
            ch = grpc.insecure_channel(addr)
            stub = health_proto.HealthStub(ch)
            try:
                resp = stub.Check(
                    health_proto.HealthCheckRequest(""), timeout=1.0
                )
                last = f"status={resp.status}"
                if resp.status == health_proto.SERVING:
                    return
            except grpc.RpcError as e:
                last = f"{e.code()} {e.details()!r}"
            finally:
                ch.close()
            time.sleep(0.3)
        raise RuntimeError(
            f"server {addr} not SERVING in {timeout}s (last: {last})"
        )

    def http_json(url: str, payload=None, timeout: float = 3.0):
        data = (
            json.dumps(payload).encode("utf-8")
            if payload is not None else None
        )
        req = urllib.request.Request(
            url, data=data,
            headers={"Content-Type": "application/json"} if data else {},
        )
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read().decode("utf-8"))

    def http_text(url: str, timeout: float = 3.0) -> str:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.read().decode("utf-8")

    def router_fleetz() -> dict:
        return http_json(f"http://127.0.0.1:{router_gossip}/fleetz")

    def poll_until(fn, timeout: float, what: str, poll_s: float = 0.05):
        """fn() -> truthy value | falsy; returns (value, elapsed_s)."""
        t0 = time.time()
        while time.time() - t0 < timeout:
            try:
                v = fn()
            except Exception:  # noqa: BLE001 — surfaces settle async
                v = None
            if v:
                return v, round(time.time() - t0, 3)
            time.sleep(poll_s)
        raise RuntimeError(f"timed out ({timeout}s) waiting for {what}")

    # Traffic runs on its own thread + event loop for the whole scenario;
    # the main thread drives the chaos script.
    events: list = []  # (wall_t, ok, error_repr)
    stop_traffic = threading.Event()
    payloads = [make_payload(candidates, fields, seed=s) for s in range(8)]

    def traffic_thread() -> None:
        async def run() -> None:
            edge = ShardedPredictClient(
                [router_addr], "DCN", timeout_s=5.0, failover_attempts=1,
                backoff_initial_s=0.02,
            )

            async def worker(wid: int) -> None:
                n = 0
                while not stop_traffic.is_set():
                    n += 1
                    try:
                        await edge.predict(payloads[(wid + n) % len(payloads)])
                        events.append((time.time(), True, ""))
                    except Exception as e:  # noqa: BLE001 — counted, gated
                        events.append((time.time(), False, repr(e)[:200]))
                    await asyncio.sleep(0.02)

            await asyncio.gather(*(worker(w) for w in range(workers)))
            await edge.close()

        asyncio.run(run())

    def probe_bit_identity() -> bool:
        """The same payload through the router and direct to one backend
        must score bit-identically (the router re-encodes through the
        same codec; affinity sub-batching must not perturb scores)."""
        async def run():
            probe = make_payload(candidates, fields, seed=99)
            edge = ShardedPredictClient([router_addr], "DCN", timeout_s=10.0)
            direct = ShardedPredictClient(
                [backend_addrs[0]], "DCN", timeout_s=10.0
            )
            try:
                via_router = await edge.predict(probe)
                direct_hit = await direct.predict(probe)
            finally:
                await edge.close()
                await direct.close()
            return via_router, direct_hit

        a, b = asyncio.run(run())
        return bool(
            np.asarray(a).tobytes() == np.asarray(b).tobytes()
        )

    procs: list = []
    router_proc = None
    rfd = None
    traffic = None
    try:
        # ---- boot the fleet -------------------------------------------
        procs = [spawn_replica(i) for i in range(replicas)]
        for i in range(replicas):
            wait_serving(backend_addrs[i], procs[i], 120.0)
        rfd, wfd = os.pipe()
        router_log = open(os.path.join(tmp, "router.log"), "ab")
        router_proc = subprocess.Popen(
            [sys.executable, "-m", "distributed_tf_serving_tpu.fleet.router",
             "--config", router_toml, "--ready-fd", str(wfd)],
            stdout=router_log, stderr=router_log, env=env, cwd=repo_root,
            pass_fds=(wfd,),
        )
        os.close(wfd)
        import select

        ready_raw = b""
        deadline = time.time() + 60.0
        while b"\n" not in ready_raw and time.time() < deadline:
            if router_proc.poll() is not None:
                raise RuntimeError(
                    f"router exited rc={router_proc.returncode}"
                )
            r, _, _ = select.select([rfd], [], [], 0.5)
            if r:
                chunk = os.read(rfd, 4096)
                if not chunk:
                    break
                ready_raw += chunk
        if b"\n" not in ready_raw:
            raise RuntimeError("router never wrote its readiness line")
        ready = json.loads(ready_raw.decode("utf-8").splitlines()[0])
        # Membership converges through the star: router sees everyone.
        _, converge_s = poll_until(
            lambda: router_fleetz()["gossip"]["member_count"]
            >= replicas + 1,
            timeout=30.0, what="gossip membership convergence",
        )

        # ---- steady traffic + reference probe -------------------------
        traffic = threading.Thread(target=traffic_thread, daemon=True)
        traffic_start = time.time()
        traffic.start()
        steady_s = max(seconds * 0.25, 3.0)
        time.sleep(steady_s)
        bit_identical_pre = probe_bit_identity()

        # ---- chaos: SIGKILL one replica mid-traffic -------------------
        victim = 1 % replicas
        procs[victim].kill()
        procs[victim].wait()
        kill_t = time.time()
        time.sleep(max(seconds * 0.15, 2.0))

        # ---- restart it: rejoin is a gossip event, measured -----------
        procs[victim] = spawn_replica(victim)
        restart_t = time.time()
        wait_serving(backend_addrs[victim], procs[victim], 120.0)

        def rejoined():
            fz = router_fleetz()
            members = fz.get("gossip", {}).get("members", {})
            rec = members.get(backend_addrs[victim])
            return (
                fz
                if rec is not None and rec.get("state") == "serving"
                and fz.get("healthy_backends") == replicas
                else None
            )

        fz_rejoin, rejoin_poll_s = poll_until(rejoined, 60.0, "fleet rejoin")
        rejoin_s = round(time.time() - restart_t, 3)

        # ---- canary publish into the SHARED base dir ------------------
        # (After the rejoin on purpose: a replica booting onto a dir that
        # already holds the canary adopts LATEST as stable — the fleet
        # could then never blacklist it out. Same params as v1, so the
        # closing bit-identity probe holds straight through the ramp.)
        servable2 = Servable(
            name="DCN", version=2, model=model, params=params,
            signatures=ctr_signatures(fields),
        )
        save_servable(os.path.join(base, "2"), servable2, kind="dcn_v2")
        publish_t = time.time()
        for i in range(replicas):
            poll_until(
                lambda i=i: http_json(
                    f"http://127.0.0.1:{rest_ports[i]}/lifecyclez"
                ).get("canary_version") == 2,
                timeout=30.0, what=f"replica {i} canary live",
            )
        canary_live_s = round(time.time() - publish_t, 3)

        # ---- fleet-coordinated rollback -------------------------------
        # One replica's operator rollback; the router's coordinator must
        # blacklist v2 for the WHOLE fleet within ~a gossip interval.
        def post_rollback():
            try:
                return http_json(
                    f"http://127.0.0.1:{rest_ports[0]}/lifecyclez/rollback",
                    {"reason": "fleet-soak-chaos"},
                )
            except urllib.error.HTTPError:
                return None  # 409: canary not live yet — retried

        rollback_resp, _ = poll_until(
            post_rollback, 20.0, "operator rollback accepted"
        )
        rollback_post_t = time.time()
        _, router_blacklist_s = poll_until(
            lambda: 2 in (
                router_fleetz().get("rollout", {})
                .get("state", {}).get("blacklist", [])
            ),
            timeout=15.0, what="router fleet blacklist",
        )
        router_blacklist_t = time.time()

        def all_rolled_back():
            states = [
                http_json(f"http://127.0.0.1:{rest_ports[i]}/lifecyclez")
                for i in range(replicas)
            ]
            return (
                states
                if all(s.get("rolled_back_version") == 2 for s in states)
                else None
            )

        lifecycle_states, propagation_s = poll_until(
            all_rolled_back, 15.0, "fleet-wide rollback"
        )
        post_to_all_s = round(time.time() - rollback_post_t, 3)

        # ---- post-chaos traffic + closing probe -----------------------
        time.sleep(max(seconds * 0.2, 3.0))
        stop_traffic.set()
        traffic.join(timeout=15.0)
        traffic_stop = time.time()
        bit_identical_post = probe_bit_identity()

        fz_final = router_fleetz()
        router_prom = http_text(
            f"http://127.0.0.1:{router_gossip}/metrics"
        )
        replica_prom = http_text(
            f"http://127.0.0.1:{rest_ports[0]}"
            f"/monitoring/prometheus/metrics"
        )

        # ---- fleet observability probes (ISSUE 18) --------------------
        fleetobs_block = None
        if fleetobs:
            # Push the edge recorder's span trees — the first hop of
            # every stitched trace. Loop the cursor until drained.
            cursor = 0
            pushed = 0
            while True:
                export = edge_tracing.recorder().export_since(cursor)
                if not export.get("spans"):
                    break
                resp = http_json(
                    f"http://127.0.0.1:{router_gossip}/tracez/ingest",
                    {"source": "client", **export},
                )
                pushed += int(resp.get("accepted") or 0)
                cursor = int(export.get("cursor") or cursor)

            def stitched_three():
                tz = http_json(
                    f"http://127.0.0.1:{router_gossip}/tracez?limit=100"
                )
                three = [
                    t for t in tz.get("traces") or []
                    if t.get("num_processes", 0) >= 3
                    and t.get("stitched_hops", 0) >= 2
                ]
                return (tz, three) if three else None

            (tz, three), _ = poll_until(
                stitched_three, 30.0,
                "a stitched trace spanning client + router + replica",
            )
            chrome = http_json(
                f"http://127.0.0.1:{router_gossip}"
                f"/tracez?format=chrome&limit=100"
            )
            with open(trace_out, "w") as f:
                json.dump(chrome, f)
            fleet_mon = http_json(
                f"http://127.0.0.1:{router_gossip}/fleet/monitoring"
            )
            slo = http_json(f"http://127.0.0.1:{router_gossip}/sloz")
            router_mon = http_json(
                f"http://127.0.0.1:{router_gossip}/monitoring"
            )
            agg = fleet_mon.get("aggregate") or {}
            member_qps_sum = sum(
                float(st.get("qps") or 0.0)
                for st in (fleet_mon.get("members") or {}).values()
            )
            wf = next(
                (t["waterfall"] for t in three if t.get("waterfall")),
                None,
            )
            fleetobs_block = {
                "client_spans_pushed": pushed,
                "stitched_traces": sum(
                    1 for t in tz.get("traces") or []
                    if t.get("num_processes", 0) >= 2
                ),
                "three_proc_traces": len(three),
                "waterfall": wf,
                "waterfall_window": fleet_mon.get("waterfall"),
                "agg_qps": agg.get("qps"),
                "member_qps_sum": round(member_qps_sum, 3),
                "agg": agg,
                "slo": slo,
                "router_monitoring_keys": sorted(router_mon),
                "trace_events": len(chrome.get("traceEvents") or []),
                "trace_out": trace_out,
            }

        # ---- goodput windows ------------------------------------------
        ok_times = sorted(t for t, ok, _ in events if ok)
        errors = [e for _, ok, e in events if not ok]

        from bisect import bisect_left as _bisect_left

        def windows(t0: float, t1: float) -> list:
            out, w = [], t0
            while w + 1.0 <= t1:
                lo = _bisect_left(ok_times, w)
                hi = _bisect_left(ok_times, w + 1.0)
                out.append(hi - lo)
                w += 1.0
            return out

        steady_windows = windows(traffic_start + 1.0, kill_t - 0.2)
        # The goodput gate covers the KILL/RESTART phase only: from the
        # SIGKILL until the canary publish. The rollout phase that follows
        # dips for a different, expected reason — every replica
        # orbax-restores and warmup-compiles v2 at once, and on a CPU host
        # three concurrent compile ladders starve the serving threads.
        # That phase is gated on zero errors + bounded propagation instead;
        # its windows are reported separately for eyeballing.
        chaos_windows = windows(kill_t, publish_t - 0.2)
        rollout_windows = windows(publish_t, traffic_stop - 0.2)
        steady_median = (
            sorted(steady_windows)[len(steady_windows) // 2]
            if steady_windows else 0
        )
        min_ratio = (
            round(min(chaos_windows) / steady_median, 3)
            if chaos_windows and steady_median else None
        )

        kinds: dict = {}
        for e in errors:
            kinds[e] = kinds.get(e, 0) + 1

        line = {
            "mode": "fleet",
            "seconds": seconds,
            "wall_s": round(time.time() - t_start, 1),
            "rss_gb": {"start": start_rss, "end": rss_gb()},
            "fleet": {
                "replicas": replicas,
                "router": ready,
                "gossip_interval_s": gossip_interval,
                "converge_s": converge_s,
                "requests": len(events),
                "ok": len(ok_times),
                "errors": len(errors),
                "error_kinds": dict(list(kinds.items())[:5]),
                "steady_window_median": steady_median,
                "steady_windows": steady_windows,
                "chaos_windows": chaos_windows,
                "rollout_windows": rollout_windows,
                "min_chaos_window_ratio": min_ratio,
                "bit_identical_pre": bit_identical_pre,
                "bit_identical_post": bit_identical_post,
                "kill": {
                    "victim": backend_addrs[victim],
                    "rejoin_s": rejoin_s,
                    "rejoin_poll_s": rejoin_poll_s,
                    "healthy_backends": fz_rejoin.get("healthy_backends"),
                },
                "rollout": {
                    "canary_version": 2,
                    "canary_live_s": canary_live_s,
                    "rollback_origin": backend_addrs[0],
                    "rollback_accepted": bool(
                        rollback_resp.get("rolled_back")
                    ),
                    "router_blacklist_s": router_blacklist_s,
                    "propagation_s": propagation_s,
                    "post_to_all_s": post_to_all_s,
                    "per_replica_rolled_back": [
                        s.get("rolled_back_version")
                        for s in lifecycle_states
                    ],
                },
                "router_counters": fz_final.get("counters", {}),
                "router_healthy_backends": fz_final.get(
                    "healthy_backends"
                ),
                "prom_router_series": sum(
                    1 for ln in router_prom.splitlines()
                    if ln.startswith("dts_tpu_fleet_")
                ),
                "prom_replica_series": sum(
                    1 for ln in replica_prom.splitlines()
                    if ln.startswith("dts_tpu_fleet_")
                ),
            },
        }
        if fleetobs_block is not None:
            line["fleetobs"] = fleetobs_block
        print(json.dumps(line))
    except BaseException as e:
        _log_tails(repr(e))
        raise
    finally:
        stop_traffic.set()
        if traffic is not None and traffic.is_alive():
            traffic.join(timeout=10.0)
        if rfd is not None:
            with contextlib.suppress(OSError):
                os.close(rfd)
        for p in [router_proc, *procs]:
            if p is not None and p.poll() is None:
                with contextlib.suppress(OSError):
                    p.terminate()
        deadline = time.time() + 15.0
        for p in [router_proc, *procs]:
            if p is None:
                continue
            with contextlib.suppress(Exception):
                p.wait(timeout=max(deadline - time.time(), 0.1))
            if p.poll() is None:
                with contextlib.suppress(OSError):
                    p.kill()
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> None:
    if os.environ.get("SOAK_FLEET", "0") == "1":
        _fleet_soak(float(os.environ.get("SOAK_SECONDS", "30")))
        return

    import jax

    import aiohttp
    import numpy as np

    from distributed_tf_serving_tpu.client import (
        PredictClientError,
        ShardedPredictClient,
        compact_payload,
        make_payload,
        make_zipfian_payloads,
        zipfian_indices,
    )
    from distributed_tf_serving_tpu.models import (
        ModelConfig,
        Servable,
        ServableRegistry,
        build_model,
        ctr_signatures,
    )
    from distributed_tf_serving_tpu.serving import DynamicBatcher, PredictionServiceImpl
    from distributed_tf_serving_tpu.serving.rest import start_rest_gateway
    from distributed_tf_serving_tpu.serving.server import create_server

    platform = jax.devices()[0].platform
    tpu = platform != "cpu"
    seconds = float(os.environ.get("SOAK_SECONDS", "300"))
    # Overload mode (SOAK_OVERLOAD=1): adaptive admission under ~3x
    # sustainable load with a mid-run burst; see module docstring.
    overload_mode = os.environ.get("SOAK_OVERLOAD", "0") == "1"
    overload_deadline_s = float(os.environ.get("SOAK_OVERLOAD_DEADLINE_S", "2.0"))
    dispatch_delay_s = float(
        os.environ.get("SOAK_OVERLOAD_DISPATCH_DELAY_S", "0.03")
    )
    grpc_workers = int(
        os.environ.get("SOAK_GRPC_WORKERS", "24" if overload_mode else "8")
    )
    rest_workers = int(os.environ.get("SOAK_REST_WORKERS", "4"))
    candidates = int(os.environ.get("SOAK_CANDIDATES", "1000"))
    burst_workers = int(
        os.environ.get("SOAK_OVERLOAD_BURST_WORKERS", str(max(grpc_workers // 2, 4)))
    ) if overload_mode else 0
    chaos = os.environ.get("SOAK_CHAOS", "0") == "1"
    # Cache mode (SOAK_CACHE=1): the batcher runs with the score cache +
    # single-flight + intra-batch dedup armed, and the gRPC workers switch
    # to a seeded zipfian workload (hot payloads AND hot rows recur) so
    # the hit/coalesced/dedup counters actually move. A pre-flight probe
    # pins correctness: the same payload scored uncached (the filling
    # miss) and cached (the hit) must be bit-identical.
    cache_mode = os.environ.get("SOAK_CACHE", "0") == "1"
    # Row-cache mode (SOAK_ROWCACHE=1, ISSUE 14): the cache-mode zipfian
    # workload with the ROW-GRANULAR cache armed next to the request
    # cache + dedup — distinct payloads sharing hot catalog rows execute
    # only their cold rows. The probe additionally pins row-path
    # bit-identity (disarmed reference vs row-filling miss vs
    # row-assembled hit), and the JSON line gains a `row_cache` block
    # (per-row hit/miss counters, rows_executed vs rows_requested) the
    # TIER1_ROWCACHE_SMOKE gate reads.
    rowcache_mode = os.environ.get("SOAK_ROWCACHE", "0") == "1"
    cache_mode = cache_mode or rowcache_mode
    cache_skew = float(os.environ.get("SOAK_CACHE_SKEW", "1.1"))
    util_mode = os.environ.get("SOAK_UTIL", "0") == "1"
    # Quality mode (SOAK_QUALITY=1): trained model, teacher-labeled
    # payload pools, live /labelz feedback, a pinned reference and a
    # shifted segment; see module docstring. Small requests (row digests
    # are the join keys) and no REST mixer (unshifted REST traffic would
    # dilute the drift the gate must observe) unless overridden.
    quality_mode = os.environ.get("SOAK_QUALITY", "0") == "1"
    # Lifecycle mode (SOAK_LIFECYCLE=1): trained model behind a REAL
    # version watcher + lifecycle controller; a driver publishes a good
    # then a poisoned canary and the controller must promote then roll
    # back, mid-traffic, with zero failed requests. Small requests and
    # no REST mixer, like quality mode.
    lifecycle_mode = os.environ.get("SOAK_LIFECYCLE", "0") == "1"
    # Recovery mode (SOAK_RECOVERY=1): the device-failure recovery plane
    # under live traffic on a depth-4 pipeline — a scenario driver
    # injects a WEDGE at the device stage (the watchdog must quarantine,
    # reinit, and replay with zero client-visible failures) and then a
    # content-keyed poisoned input coalesced with clean companions (the
    # bisection must fail exactly the poison with its distinct status
    # while the companions replay to success).
    recovery_mode = os.environ.get("SOAK_RECOVERY", "0") == "1"
    # Integrity mode (SOAK_INTEGRITY=1): the data-integrity plane's
    # chaos scenario — wire flips, readback bitflips, NaN rows — with
    # the recovery controller armed for the escalation path and the
    # client verifying response checksums; see module docstring.
    integrity_mode = os.environ.get("SOAK_INTEGRITY", "0") == "1"
    # Cascade mode (SOAK_CASCADE=1): multi-stage retrieval->rank through
    # serving/cascade.py on every score-filtered gRPC request — stage-1
    # two_tower over the full candidate batch, on-device prune to 25%
    # survivors, DCN over the survivor rung only. A pre-flight probe
    # pins bit-identity (survivor scores vs a full-pass reference,
    # pruned rows vs stage-1-only), and the JSON line gains a `cascade`
    # block with row dispositions + live-route probe results.
    cascade_mode = os.environ.get("SOAK_CASCADE", "0") == "1"
    if quality_mode or lifecycle_mode:
        candidates = int(os.environ.get("SOAK_CANDIDATES", "16"))
        grpc_workers = int(os.environ.get("SOAK_GRPC_WORKERS", "4"))
        rest_workers = int(os.environ.get("SOAK_REST_WORKERS", "0"))
    elif recovery_mode or integrity_mode:
        # Small bucket + modest load: each reinit round re-warms the
        # ladder, so the cycle time (and with it the client retry
        # horizon) must stay in low seconds on a CPU-only CI host.
        # (Integrity mode escalates into the same reinit cycles, and
        # its shadow_fraction=1.0 doubles the forward work besides.)
        candidates = int(os.environ.get("SOAK_CANDIDATES", "200"))
        grpc_workers = int(os.environ.get("SOAK_GRPC_WORKERS", "4"))
        rest_workers = int(os.environ.get("SOAK_REST_WORKERS", "0"))
    trace_out = os.environ.get("SOAK_TRACE_OUT", "")
    if trace_out or quality_mode:
        from distributed_tf_serving_tpu.utils import tracing

        # Quality mode needs the span plane live either way: drift
        # exemplars are span annotations, and annotated spans are what
        # the tail sampler force-keeps into /tracez.
        tracing.enable(
            buffer_size=int(os.environ.get("SOAK_TRACE_BUFFER", "256")),
            sample_rate=float(
                os.environ.get(
                    "SOAK_TRACE_SAMPLE", "0.2" if quality_mode else "0.05"
                )
            ),
            slowest_n=int(os.environ.get("SOAK_TRACE_SLOWEST", "32")),
        )
    if chaos:
        from distributed_tf_serving_tpu import faults

        faults.get().seed = int(os.environ.get("SOAK_CHAOS_SEED", "0"))
        # Low-rate, latency-shaped chaos: enough pressure to exercise the
        # failover/scoreboard/shed paths continuously, low enough that the
        # soak still measures the stack (not the injector).
        faults.get().add("client.rpc", "error", rate=0.02, code="UNAVAILABLE")
        faults.get().add("client.rpc", "delay", rate=0.05, delay_s=0.02)
        faults.get().add("batcher.dispatch", "delay", rate=0.05, delay_s=0.01)
        faults.get().add("readback", "delay", rate=0.05, delay_s=0.005)
    if overload_mode:
        from distributed_tf_serving_tpu import faults

        # Deterministic capacity: EVERY dispatch eats a fixed injected
        # delay, so "sustainable load" is ~1/delay batches/s regardless of
        # how fast this host's CPU runs the tiny soak model — the worker
        # pool above is sized ~3x that, which is the overload.
        faults.get().add(
            "batcher.dispatch", "delay", rate=1.0, delay_s=dispatch_delay_s
        )

    # Bench-scale servable on the accelerator; small on the CPU platform so
    # the one core spends its budget on the serving stack, not the forward.
    config = ModelConfig(
        name="DCN",
        num_fields=NUM_FIELDS,
        vocab_size=(1 << 20) if tpu else (1 << 14),
        embed_dim=16 if tpu else 8,
        mlp_dims=(256, 128, 64) if tpu else (16,),
        num_cross_layers=3 if tpu else 1,
        cross_full_matrix=True,
    )
    model = build_model("dcn_v2", config)
    quality_monitor = None
    q_window_s = max(seconds * 0.35, 3.0)
    if quality_mode or lifecycle_mode:
        # Train briefly on the synthetic stream so the served scores
        # carry REAL signal against the stream's teacher labels — a
        # random-init model would pin the label-feedback AUC at ~0.5 and
        # the gate would measure nothing.
        from distributed_tf_serving_tpu.serving.quality import QualityMonitor
        from distributed_tf_serving_tpu.train import Trainer
        from distributed_tf_serving_tpu.train.data import SyntheticCTRConfig

        # Dense id catalog: each id gets
        # enough noisy Bernoulli views inside a short fit that the model
        # actually generalizes — at the full vocab the same steps leave
        # AUC at coin-flip.
        stream_cfg = SyntheticCTRConfig(
            num_fields=NUM_FIELDS,
            id_space=min(1 << 12, config.vocab_size),
            seed=7,
        )
        trainer = Trainer(model, stream_config=stream_cfg, learning_rate=3e-3)
        fit = trainer.fit(
            steps=int(os.environ.get("SOAK_QUALITY_TRAIN_STEPS", "400")),
            batch_size=256,
        )
        print(
            f"# {'lifecycle' if lifecycle_mode else 'quality'} soak: "
            f"trained {fit['steps']} steps, loss={fit['loss']:.4f}",
            file=sys.stderr,
        )
        params = trainer.snapshot_params()
        if lifecycle_mode:
            # Long window (everything stays in-window for the soak's
            # horizon): the lifecycle controller reads pair_drift /
            # version_auc with ITS OWN evidence floor, so the monitor's
            # drift cadence only feeds the passive surfaces here.
            quality_monitor = QualityMonitor(
                window_s=max(seconds, 10.0),
                slices=4,
                drift_check_interval_s=0.5,
                min_drift_count=60,
            )
        else:
            quality_monitor = QualityMonitor(
                # Short window so the post-shift window is dominated by
                # shifted traffic well before the soak ends; fast drift
                # cadence so short CI smokes (~12 s) get several ticks.
                window_s=q_window_s,
                slices=4,
                drift_check_interval_s=max(seconds / 24, 0.25),
                drift_threshold_psi=float(
                    os.environ.get("SOAK_QUALITY_PSI_THRESHOLD", "0.2")
                ),
                exemplar_traces=8,
            )
    else:
        params = jax.jit(model.init)(jax.random.PRNGKey(0))
    registry = ServableRegistry()
    servable = Servable(
        name="DCN", version=1, model=model, params=params,
        signatures=ctr_signatures(NUM_FIELDS),
    )
    if not lifecycle_mode:
        # Lifecycle mode serves through the WATCHED base dir instead: the
        # trained servable lands as version 1 on disk below, and the real
        # VersionWatcher loads (and queue-warms) it like production.
        registry.load(servable)
    score_cache = None
    row_cache = None
    if cache_mode:
        from distributed_tf_serving_tpu.cache import ScoreCache

        # TTL comfortably past the soak horizon: this mode measures the
        # cache plane's behavior under load, not TTL churn (TTL/eviction
        # correctness is tests/test_cache.py's job).
        score_cache = ScoreCache(ttl_s=max(seconds * 2, 600.0))
        if rowcache_mode:
            from distributed_tf_serving_tpu.cache import RowScoreCache

            row_cache = RowScoreCache(ttl_s=max(seconds * 2, 600.0))
    elif overload_mode:
        from distributed_tf_serving_tpu.cache import ScoreCache

        # SHORT TTL on purpose: hot zipfian entries must actually expire
        # mid-soak so the brownout stale-serve window (entries past TTL
        # still answering while pressure > NOMINAL) gets exercised.
        score_cache = ScoreCache(
            ttl_s=float(os.environ.get("SOAK_OVERLOAD_CACHE_TTL_S", "1.5"))
        )
    overload_ctrl = None
    if overload_mode:
        from distributed_tf_serving_tpu.utils.config import OverloadConfig

        # Faster-than-default control cadence so short CI smokes (8-12s)
        # traverse NOMINAL -> BROWNOUT and shed well inside the run.
        overload_ctrl = OverloadConfig(
            enabled=True,
            target_queue_wait_ms=float(
                os.environ.get("SOAK_OVERLOAD_TARGET_MS", "50")
            ),
            adjust_interval_s=0.25,
            brownout_after_intervals=3,
            shed_after_intervals=10,
            recover_after_intervals=8,
            stale_while_overloaded_s=float(
                os.environ.get("SOAK_OVERLOAD_STALE_S", "60")
            ),
            # Tighter-than-auto ceiling: the limit starts at max and only
            # ratchets DOWN from observed queue wait, so the static-bound
            # default (16x the largest bucket) would let the opening
            # stampede queue several seconds deep — blowing every client
            # deadline before the controller's first shrink tick.
            max_limit_candidates=int(
                os.environ.get("SOAK_OVERLOAD_MAX_LIMIT", "6144")
            ),
            # Let the limit shrink BELOW one largest bucket (the auto min):
            # at 1024 the sheddable lane's ceiling (0.7x) is smaller than
            # one 1000-candidate request, so sustained pressure visibly
            # sheds the sheddable lane — the ordering the smoke gate reads.
            min_limit_candidates=int(
                os.environ.get("SOAK_OVERLOAD_MIN_LIMIT", "1024")
            ),
        ).build()
    ledger = None
    if util_mode:
        from distributed_tf_serving_tpu.serving.utilization import OccupancyLedger
        from distributed_tf_serving_tpu.utils import tracing as tracing_mod

        ledger = OccupancyLedger(device=str(jax.devices()[0]))
        # Counter-track source: a SOAK_TRACE_OUT export then carries the
        # per-device occupancy track next to the request spans.
        tracing_mod.register_counter_source(ledger)
    if lifecycle_mode:
        # One small bucket: three versions each warm the ladder through
        # the queue mid-soak, and the candidates are 16-row requests.
        buckets = (64,)
    elif recovery_mode or integrity_mode:
        # One small bucket: every reinit round re-warms the whole ladder
        # through the queue, and the recovery cycle must finish inside
        # the client retry horizon.
        buckets = (256,)
    elif cascade_mode:
        # A survivor rung BELOW the candidate rung: the cascade's win is
        # stage-2 traffic landing in the smaller bucket (25% of 1000
        # candidates packs into 256), so the ladder must carry one.
        buckets = (256, 1024, 2048) if tpu else (256, 1024)
    else:
        buckets = (1024, 2048, 4096, 8192, 16384) if tpu else (1024, 2048)
    batcher_kw = {}
    if recovery_mode:
        # The acceptance scenario: a wedge at PIPELINE DEPTH 4 — several
        # batches in flight behind the stuck one, all captured + replayed.
        batcher_kw = dict(
            pipeline_depth=4, inflight_window=4, buffer_ring=True
        )
    batcher = DynamicBatcher(
        buckets=buckets, max_wait_us=2000, completion_workers=12,
        score_cache=score_cache, row_cache=row_cache, dedup=cache_mode,
        overload=overload_ctrl,
        utilization=ledger, quality=quality_monitor, **batcher_kw,
    ).start()
    batcher.max_batch_candidates = buckets[-1]
    if not lifecycle_mode:
        for b in buckets:
            batcher.warmup(servable, buckets=(b,))
            batcher.submit(
                servable,
                compact_payload(batcher.warmup_arrays(servable, b), config.vocab_size),
                _warmup=True,
            ).result(timeout=600)

    lifecycle_block: dict = {}
    lifecycle_ctrl = None
    lifecycle_watcher = None
    lc_pool: list = []
    if lifecycle_mode:
        import tempfile

        from distributed_tf_serving_tpu.serving.lifecycle import (
            LifecycleController,
        )
        from distributed_tf_serving_tpu.serving.server import (
            _servable_change_hook,
        )
        from distributed_tf_serving_tpu.serving.version_watcher import (
            VersionWatcher,
            VersionWatcherConfig,
        )
        from distributed_tf_serving_tpu.train.checkpoint import save_servable
        from distributed_tf_serving_tpu.train.data import SyntheticCTRStream
        from distributed_tf_serving_tpu.utils.config import LifecycleConfig

        lc_base = tempfile.mkdtemp(prefix="soak_lifecycle_")
        save_servable(os.path.join(lc_base, "1"), servable, kind="dcn_v2")
        lifecycle_watcher = VersionWatcher(
            lc_base, registry,
            VersionWatcherConfig(
                poll_interval_s=float(
                    os.environ.get("SOAK_LIFECYCLE_POLL_S", "0.5")
                ),
                model_name="DCN", model_kind="dcn_v2",
            ),
            # Queue warmup: each hot-loaded version compiles on the
            # batching thread BEFORE its registry flip, exactly like the
            # production server — a canary's first live request must not
            # pay the jit.
            warmup=batcher.warmup_via_queue,
            model_config=config,
            on_servable_change=_servable_change_hook(None, quality_monitor),
        ).start()
        lifecycle_ctrl = LifecycleController(
            LifecycleConfig(
                enabled=True,
                tick_interval_s=0.2,
                canary_probe_only_s=0.6,
                canary_initial_fraction=0.25,
                canary_ramp_step=0.25,
                canary_step_dwell_s=0.5,
                canary_max_fraction=0.5,
                promote_after_s=float(
                    os.environ.get("SOAK_LIFECYCLE_PROMOTE_AFTER", "2.0")
                ),
                min_canary_scores=int(
                    os.environ.get("SOAK_LIFECYCLE_MIN_SCORES", "120")
                ),
                rollback_psi=float(
                    os.environ.get("SOAK_LIFECYCLE_ROLLBACK_PSI", "0.4")
                ),
                rollback_hold_s=0.5,
            ),
            registry=registry,
            model_name="DCN",
            watcher=lifecycle_watcher,
            quality=quality_monitor,
        ).start()
        # Steady payload pool from the trained distribution: both
        # versions' sketches fill with in-distribution scores, so a
        # healthy canary reads as pair PSI ~ 0 and a poisoned one does
        # not hide behind workload drift.
        lc_stream = SyntheticCTRStream(stream_cfg)
        for i in range(int(os.environ.get("SOAK_LIFECYCLE_POOL", "24"))):
            b = lc_stream.batch(candidates, 5_000 + i)
            lc_pool.append(
                {"feat_ids": b["feat_ids"], "feat_wts": b["feat_wts"]}
            )
    impl = PredictionServiceImpl(registry, batcher)
    if lifecycle_mode:
        impl.lifecycle = lifecycle_ctrl
        impl.version_watcher = lifecycle_watcher

    recovery_block: dict = {}
    recovery_ctrl = None
    if recovery_mode or integrity_mode:
        from distributed_tf_serving_tpu.serving.recovery import (
            RecoveryController,
        )
        from distributed_tf_serving_tpu.utils.config import RecoveryConfig

        # Integrity mode arms the SAME controller: the plane's screen
        # threshold and shadow mismatches escalate through take_group
        # into the output_corrupt quarantine->reinit->replay cycle.
        recovery_ctrl = RecoveryController(
            RecoveryConfig(
                enabled=True,
                watchdog_interval_s=0.2,
                wedge_quarantine_s=float(
                    os.environ.get("SOAK_RECOVERY_WEDGE_S", "1.0")
                ),
                replay_drain_s=15.0,
            ),
            batcher, registry=registry, impl=impl,
        ).start()
        impl.recovery = recovery_ctrl

    quality_block: dict = {}
    q_pools: dict = {}
    if quality_mode:
        # Warmup exclusion is an acceptance criterion: the bucket-ladder
        # warmups above went through the full completer path, and the
        # sketch must have seen NONE of them.
        quality_block["observed_after_warmup"] = quality_monitor.observed_requests
        # Payload pools from the synthetic stream, with each row's label
        # generated from the KNOWN teacher (the data-gen's own Bernoulli)
        # and each row's join key digested client-side over the exact
        # arrays sent. The shifted pool scales feature weights (the
        # teacher is linear in weights, so ranking — and therefore AUC —
        # survives while the score DISTRIBUTION saturates outward), with
        # labels regenerated from the teacher on the shifted rows.
        from distributed_tf_serving_tpu.client import label_keys
        from distributed_tf_serving_tpu.train.data import (
            SyntheticCTRStream,
            _sigmoid,
        )

        q_stream = SyntheticCTRStream(stream_cfg)
        pool_n = int(os.environ.get("SOAK_QUALITY_POOL", "32"))
        shift_scale = float(os.environ.get("SOAK_QUALITY_SHIFT_SCALE", "3.0"))
        for phase, (offset, scale) in enumerate(
            ((0, 1.0), (100_000, shift_scale))
        ):
            payloads, labels, keys = [], [], []
            for i in range(pool_n):
                b = q_stream.batch(candidates, offset + i)
                wts = (b["feat_wts"] * scale).astype(np.float32)
                score = q_stream._teacher_score(b["feat_ids"], wts)
                rng = np.random.RandomState(7_000_003 + offset + i)
                row_labels = (
                    rng.rand(candidates) < _sigmoid(score)
                ).astype(np.float32)
                payload = {"feat_ids": b["feat_ids"], "feat_wts": wts}
                payloads.append(payload)
                labels.append(row_labels)
                keys.append(label_keys(payload))
            q_pools[phase] = (payloads, labels, keys)

    wide = make_payload(candidates=candidates, num_fields=NUM_FIELDS)
    compact = compact_payload(wide, config.vocab_size)
    unique_pool = [
        make_payload(candidates=candidates, num_fields=NUM_FIELDS, seed=500 + i)
        for i in range(32)
    ]
    cache_block: dict = {}
    zipf_pool, zipf_sched = None, None
    use_zipf = cache_mode or overload_mode
    if use_zipf:
        # Zipfian workload: hot payloads repeat (score-cache hits +
        # coalescing) and hot rows recur across distinct payloads
        # (intra-batch dedup). Seeded, so reruns replay the same stream.
        # Overload mode rides the same stream but over a WIDER pool: hot
        # keys + a short cache TTL make brownout stale-serve observable,
        # while the cold tail keeps real misses flowing into admission so
        # the shed path stays exercised (a fully-cached pool would let
        # stale-serve absorb everything and the gate's shed counter idle).
        zipf_pool = make_zipfian_payloads(
            int(os.environ.get("SOAK_OVERLOAD_POOL", "128"))
            if overload_mode and not cache_mode else 32,
            candidates, NUM_FIELDS, skew=cache_skew,
            seed=int(os.environ.get("SOAK_CACHE_SEED", "0")),
            catalog=max(candidates * 4, 256),
        )
        zipf_sched = zipfian_indices(
            4096, len(zipf_pool), skew=cache_skew,
            seed=int(os.environ.get("SOAK_CACHE_SEED", "0")) + 1,
        )
    if cache_mode:
        # Pre-flight bit-identity probe through the real batcher. The
        # reference is computed with the WHOLE cache plane disarmed
        # (score cache detached, dedup off) — comparing a cached copy
        # against its own filling miss would be tautological and blind to
        # a dedup/scatter bug changing answers. Then the same payload runs
        # armed: the miss (dedup path, fills) and the hit (cached copy)
        # must both be bit-identical to the disarmed reference.
        probe = zipf_pool[0]
        batcher.score_cache, batcher.dedup = None, False
        batcher.row_cache = None
        ref = batcher.submit(
            servable, probe, output_keys=("prediction_node",)
        ).result(timeout=600)["prediction_node"]
        batcher.score_cache, batcher.dedup = score_cache, True
        batcher.row_cache = row_cache
        miss = batcher.submit(
            servable, probe, output_keys=("prediction_node",)
        ).result(timeout=600)["prediction_node"]
        hit = batcher.submit(
            servable, probe, output_keys=("prediction_node",)
        ).result(timeout=600)["prediction_node"]
        cache_block["scores_match"] = bool(
            np.array_equal(ref, miss) and np.array_equal(ref, hit)
        )
        if rowcache_mode:
            # Row-path bit-identity: with the REQUEST cache detached, the
            # same payload must answer identically from a flushed row
            # cache (the filling miss — every row cold) and from the
            # fully-warm row cache (zero device work, pure assembly).
            batcher.score_cache, batcher.dedup = None, False
            row_cache.flush()
            row_miss = batcher.submit(
                servable, probe, output_keys=("prediction_node",)
            ).result(timeout=600)["prediction_node"]
            row_hit = batcher.submit(
                servable, probe, output_keys=("prediction_node",)
            ).result(timeout=600)["prediction_node"]
            batcher.score_cache, batcher.dedup = score_cache, True
            cache_block["row_scores_match"] = bool(
                np.array_equal(ref, row_miss)
                and np.array_equal(ref, row_hit)
            )
            cache_block["row_probe_snapshot"] = {
                k: row_cache.snapshot()[k]
                for k in ("hits", "misses", "coalesced",
                          "rows_requested", "rows_executed")
            }
        # Counter baseline AFTER the probe: the reported hit/miss/coalesced
        # workload numbers (and the CI gate) must come from worker traffic,
        # not from the probe's guaranteed hit.
        cache_block["probe_snapshot"] = {
            k: score_cache.snapshot()[k]
            for k in ("hits", "misses", "coalesced")
        }
    cascade_block: dict = {}
    if cascade_mode:
        import dataclasses

        from distributed_tf_serving_tpu.models import build_model
        from distributed_tf_serving_tpu.serving.cascade import (
            STAGE2,
            CascadeOrchestrator,
        )

        # The stage-1 servable is an ordinary registry entry under its
        # own name — exactly how build_stack publishes it — scored over
        # the candidate rung(s) while stage 2 runs the survivor rung.
        s1_config = dataclasses.replace(config, name="stage1")
        s1_model = build_model("two_tower", s1_config)
        s1_params = jax.jit(s1_model.init)(jax.random.PRNGKey(3))
        stage1 = Servable(
            name="stage1", version=1, model=s1_model, params=s1_params,
            signatures=ctr_signatures(NUM_FIELDS),
        )
        registry.load(stage1)
        for b in buckets[1:]:
            batcher.warmup(stage1, buckets=(b,))
        impl.cascade = CascadeOrchestrator(
            registry, batcher, stage1_model="stage1",
            survivor_fraction=0.25,
        )
        # Pre-flight bit-identity probe (the gate's correctness bar):
        # the cascade's survivor rows must be byte-equal to the SAME
        # rows of a cascade-off full pass, and its pruned rows
        # byte-equal to a stage-1-only pass — or the cascade is
        # changing answers, not saving work.
        probe = unique_pool[0]
        sk = servable.model.score_output
        s1k = s1_model.score_output
        out = impl.cascade.run(impl, servable, probe, (sk,), None, None)
        ref = impl._run(servable, probe, output_keys=(sk,))
        ref1 = impl._run(stage1, probe, output_keys=(s1k,))
        surv = out["cascade_stage"] == STAGE2
        cascade_block["scores_match"] = bool(
            np.array_equal(out[sk][surv], ref[sk][surv])
            and np.array_equal(
                out[sk][~surv], ref1[s1k].astype(np.float32)[~surv]
            )
        )
        # Counter baseline AFTER the probe: the gate reads workload
        # deltas, so the probe's guaranteed prune can never green-wash
        # a cascade idle under load.
        cascade_block["probe_snapshot"] = {
            k: impl.cascade.snapshot()[k]
            for k in ("requests", "rows_requested", "rows_ranked",
                      "pruned_rows")
        }

    integrity_block: dict = {}
    integrity_plane = None
    integrity_ref: dict = {}
    if integrity_mode:
        from distributed_tf_serving_tpu.utils.config import IntegrityConfig

        integrity_plane = IntegrityConfig(
            enabled=True,
            shadow_fraction=float(
                os.environ.get("SOAK_INTEGRITY_SHADOW", "1.0")
            ),
            screen_trips_per_window=int(
                os.environ.get("SOAK_INTEGRITY_TRIPS", "3")
            ),
            screen_window_s=5.0,
        ).build()
        batcher.integrity = integrity_plane
        impl.integrity = integrity_plane
        # Pre-flight CLEAN bit-identity probe (the gate's correctness
        # bar): the plane must never change answers. Reference with the
        # plane detached; then the same payload armed — with a FORCED
        # shadow audit so the compare path itself runs — must answer
        # byte-identically.
        probe = unique_pool[0]
        batcher.integrity = None
        ref = batcher.submit(
            servable, probe, output_keys=("prediction_node",)
        ).result(timeout=600)["prediction_node"]
        batcher.integrity = integrity_plane
        integrity_plane.request_audit()
        armed = batcher.submit(
            servable, probe, output_keys=("prediction_node",)
        ).result(timeout=600)["prediction_node"]
        integrity_ref["scores"] = ref
        integrity_block["clean_bit_identical"] = bool(
            np.array_equal(ref, armed)
        )
        integrity_block["probe_audits_run"] = (
            integrity_plane.snapshot()["shadow"]["audits_run"]
        )

    rest_cols = {
        "feat_ids": wide["feat_ids"][:64].tolist(),
        "feat_wts": wide["feat_wts"][:64].tolist(),
    }
    rest_examples = [
        {"feat_ids": wide["feat_ids"][i].tolist(),
         "feat_wts": wide["feat_wts"][i].tolist()}
        for i in range(8)
    ]

    # Sampled request logging under load (SOAK_REQUEST_LOG_SAMPLING > 0,
    # OPT-IN so default soaks stay comparable to prior rounds' baselines):
    # the bounded-queue writer must keep up or shed cleanly while every
    # surface hammers the impl.
    request_logger = None
    log_sampling = float(os.environ.get("SOAK_REQUEST_LOG_SAMPLING", "0"))
    if log_sampling > 0:
        import tempfile

        from distributed_tf_serving_tpu.serving.request_log import RequestLogger

        log_path = os.path.join(tempfile.gettempdir(), f"soak_requests_{os.getpid()}.log")
        request_logger = RequestLogger(log_path, sampling_rate=log_sampling)
        impl.request_logger = request_logger

    counts = {
        "grpc_ok": 0, "grpc_err": 0,
        "rest_ok": 0, "rest_err": 0,
        "control_ok": 0, "control_err": 0,
        "errors": {},
    }
    rss_start = rss_gb()
    deadline = time.perf_counter() + seconds

    def note_error(kind: str, detail: str) -> None:
        counts[f"{kind}_err"] += 1
        key = detail[:120]
        counts["errors"][key] = counts["errors"].get(key, 0) + 1

    async def one_grpc_request(client, wid: int, i: int) -> None:
        if use_zipf:
            # Seeded zipfian stream: worker w walks the schedule from
            # its own offset, so concurrent workers frequently hold
            # the SAME hot payload in flight (single-flight coverage)
            # while the tail keeps misses coming.
            payload = zipf_pool[
                zipf_sched[(wid * 997 + i) % len(zipf_sched)]
            ]
        else:
            # Interleave regimes every 7 requests, like the r4 soak:
            # the cache's regime detector must ride the transitions
            # without false bypass or stale hits.
            phase = (i // 7 + wid) % 3
            payload = (
                wide, compact, unique_pool[(i + wid) % len(unique_pool)]
            )[phase]
        try:
            await client.predict(payload, sort_scores=True)
            counts["grpc_ok"] += 1
        except PredictClientError as e:
            note_error("grpc", f"{getattr(e.code, 'name', e.code)}: {e}")
        except Exception as e:  # noqa: BLE001 — classification, keep soaking
            note_error("grpc", f"{type(e).__name__}: {e}")

    async def grpc_worker(client, wid: int):
        if overload_mode:
            # Staggered ramp: real load arrives as a ramp, not a step.
            # An instantaneous 24-worker stampede onto a cold controller
            # (limit still at max, no service-time EWMA yet) would queue
            # past every deadline before the first shrink tick.
            await asyncio.sleep(min(wid, 40) * 0.05)
        i = 0
        while time.perf_counter() < deadline:
            i += 1
            await one_grpc_request(client, wid, i)

    # Mid-run burst (overload mode): extra workers spike the offered load
    # from 40% to 70% of the soak — the adaptive limit must absorb the
    # step up (shed harder / brown out) and recover after it steps down.
    burst_t0 = deadline - seconds * 0.6
    burst_t1 = deadline - seconds * 0.3

    async def burst_worker(client, wid: int):
        now = time.perf_counter()
        if now < burst_t0:
            await asyncio.sleep(burst_t0 - now)
        i = 0
        while time.perf_counter() < min(burst_t1, deadline):
            i += 1
            await one_grpc_request(client, 1000 + wid, i)

    async def rest_worker(session, wid: int):
        i = 0
        while time.perf_counter() < deadline:
            i += 1
            try:
                if (i + wid) % 5 == 0:
                    async with session.post(
                        "/v1/models/DCN:classify", json={"examples": rest_examples}
                    ) as r:
                        body = await r.json()
                        ok = r.status == 200 and len(body.get("results", ())) == len(rest_examples)
                else:
                    async with session.post(
                        "/v1/models/DCN:predict", json={"inputs": rest_cols}
                    ) as r:
                        body = await r.json()
                        ok = r.status == 200 and "outputs" in body
                if ok:
                    counts["rest_ok"] += 1
                else:
                    note_error("rest", f"http {r.status}: {json.dumps(body)[:80]}")
            except Exception as e:  # noqa: BLE001 — classification, keep soaking
                note_error("rest", f"{type(e).__name__}: {e}")

    # Quality mode: (score, label, t) log the gate's OFFLINE exact-AUC
    # baseline is computed from, and once-per-round labeling bookkeeping.
    quality_log: list[tuple[float, float, float]] = []
    q_labeled: set = set()
    q_shift_t = deadline - seconds * (
        1.0 - float(os.environ.get("SOAK_QUALITY_SHIFT_AT", "0.55"))
    )
    q_round_s = max(q_window_s / 3.0, 1.0)

    async def quality_worker(client, session, wid: int):
        i = 0
        while time.perf_counter() < deadline:
            i += 1
            now = time.perf_counter()
            phase = 0 if now < q_shift_t else 1
            payloads, labels_pool, keys_pool = q_pools[phase]
            idx = (wid * 131 + i) % len(payloads)
            try:
                scores = await client.predict(payloads[idx], sort_scores=False)
                counts["grpc_ok"] += 1
            except PredictClientError as e:
                note_error("grpc", f"{getattr(e.code, 'name', e.code)}: {e}")
                continue
            except Exception as e:  # noqa: BLE001 — classification, keep soaking
                note_error("grpc", f"{type(e).__name__}: {e}")
                continue
            # Label each payload once per labeling round (and afresh per
            # phase): the reservoir keeps refreshing, the windowed AUC
            # always has recent pairs, and the same label is never
            # spammed every request.
            round_id = int((now - (deadline - seconds)) / q_round_s)
            mark = (phase, idx, round_id)
            if mark in q_labeled:
                continue
            q_labeled.add(mark)
            row_labels = labels_pool[idx]
            try:
                async with session.post("/labelz", json={"labels": [
                    {"id": key, "label": float(lb)}
                    for key, lb in zip(keys_pool[idx], row_labels)
                ]}) as r:
                    body = await r.json()
                    if r.status != 200:
                        note_error("rest", f"labelz http {r.status}: {body}")
                        continue
                t = time.monotonic()
                quality_log.extend(
                    (float(s), float(lb), t)
                    for s, lb in zip(np.asarray(scores).ravel(), row_labels)
                )
            except Exception as e:  # noqa: BLE001 — classification, keep soaking
                note_error("rest", f"labelz {type(e).__name__}: {e}")

    async def quality_pin(session):
        """Pin the drift reference over LIVE HTTP at ~40% — steady
        traffic only, so the shifted segment drifts AGAINST it."""
        pin_at = float(os.environ.get("SOAK_QUALITY_PIN_AT", "0.40"))
        await asyncio.sleep(seconds * pin_at)
        try:
            async with session.post("/qualityz/snapshot") as r:
                quality_block["pin"] = await r.json()
        except Exception as e:  # noqa: BLE001 — report, keep line
            quality_block["pin"] = {"error": f"{type(e).__name__}: {e}"}

    async def probe_quality(session) -> None:
        """End-of-run probes against the LIVE surfaces (the bytes an
        operator's curl would get): /qualityz, the ?section= monitoring
        filter, /tracez exemplar annotations, and the Prometheus text
        (written to disk for the exposition lint)."""
        async with session.get("/qualityz") as r:
            qz = await r.json()
        quality_block["qualityz"] = qz
        async with session.get("/monitoring?section=quality") as r:
            sec = await r.json()
            quality_block["section_filter_ok"] = (
                r.status == 200
                and set(sec) == {"quality"}
                and bool(sec["quality"].get("enabled"))
            )
        async with session.get("/tracez?limit=200") as r:
            tz_raw = await r.read()
        quality_block["exemplar_traces"] = tz_raw.count(b'"quality.drift"')
        async with session.get("/monitoring/prometheus/metrics") as r:
            prom_text = await r.text()
        prom_out = os.environ.get(
            "SOAK_QUALITY_PROM_OUT",
            os.path.join(
                __import__("tempfile").gettempdir(),
                f"soak_quality_prom_{os.getpid()}.txt",
            ),
        )
        with open(prom_out, "w") as f:
            f.write(prom_text)
        quality_block["prom_path"] = prom_out
        quality_block["prom_quality_series"] = sum(
            1 for ln in prom_text.splitlines()
            if ln.startswith("dts_tpu_quality_")
        )

    async def lifecycle_worker(client, wid: int):
        """Steady in-distribution gRPC traffic for lifecycle mode; worker
        0 rides the probe criticality lane, so a fresh canary gets its
        first real traffic the moment CANARY is entered."""
        i = 0
        while time.perf_counter() < deadline:
            i += 1
            payload = lc_pool[(wid * 131 + i) % len(lc_pool)]
            try:
                await client.predict(payload, sort_scores=False)
                counts["grpc_ok"] += 1
            except PredictClientError as e:
                note_error("grpc", f"{getattr(e.code, 'name', e.code)}: {e}")
            except Exception as e:  # noqa: BLE001 — classification, keep soaking
                note_error("grpc", f"{type(e).__name__}: {e}")

    async def lifecycle_driver():
        """The scenario script: publish a GOOD fine-tuned canary (must
        auto-promote), then a POISONED one (must auto-rollback +
        blacklist), all against live traffic."""
        import dataclasses as dc

        from distributed_tf_serving_tpu.interop.export import publish_version
        from distributed_tf_serving_tpu.train.checkpoint import (
            save_servable as save_ckpt,
        )
        from distributed_tf_serving_tpu.train.publisher import (
            publish_finetuned,
        )

        loop_ = asyncio.get_running_loop()
        await asyncio.sleep(
            seconds * float(os.environ.get("SOAK_LIFECYCLE_PUBLISH_AT", "0.10"))
        )
        # --- good canary: the REAL fine-tune publisher path -------------
        stable_sv = registry.resolve("DCN")
        good = await loop_.run_in_executor(None, lambda: publish_finetuned(
            lc_base, stable_sv, kind="dcn_v2",
            steps=int(os.environ.get("SOAK_LIFECYCLE_FT_STEPS", "25")),
            batch_size=128, learning_rate=1e-4, seed=1,
            stream_config=stream_cfg,
        ))
        good_v = good["version"]
        lifecycle_block["published_good"] = {
            "version": good_v, "steps": good["steps"],
            "loss": round(good.get("loss", 0.0), 4),
        }
        t0 = time.perf_counter()
        while time.perf_counter() < deadline - seconds * 0.25:
            snap = lifecycle_ctrl.snapshot()
            if snap["counters"]["promotes"] >= 1 and snap["state"] == "idle" \
                    and snap["stable_version"] == good_v:
                break
            await asyncio.sleep(0.15)
        lifecycle_block["promote_wait_s"] = round(time.perf_counter() - t0, 2)
        lifecycle_block["promoted_version"] = (
            lifecycle_ctrl.snapshot()["stable_version"]
        )
        # --- poisoned canary: params scaled -> saturated scores ---------
        import jax as jax_mod

        poisoned_sv = registry.resolve("DCN")
        poisoned_params = jax_mod.tree_util.tree_map(
            lambda a: a * 1.8, poisoned_sv.params
        )

        def publish_poisoned():
            def write(tmp):
                save_ckpt(
                    tmp,
                    dc.replace(
                        poisoned_sv, params=poisoned_params,
                        version=good_v + 1,
                    ),
                    kind="dcn_v2",
                )
            v, p = publish_version(lc_base, write, at_least=good_v + 1)
            return {"version": v, "path": p}

        bad = await loop_.run_in_executor(None, publish_poisoned)
        lifecycle_block["published_poisoned"] = {"version": bad["version"]}
        t0 = time.perf_counter()
        while time.perf_counter() < deadline - 1.5:
            if lifecycle_ctrl.snapshot()["counters"]["rollbacks"] >= 1:
                break
            await asyncio.sleep(0.15)
        lifecycle_block["rollback_wait_s"] = round(time.perf_counter() - t0, 2)
        # Blacklist persistence: the bad version's directory still sits
        # READY on disk — let several watcher reconcile passes run and
        # prove it stays retired.
        await asyncio.sleep(
            3 * float(os.environ.get("SOAK_LIFECYCLE_POLL_S", "0.5")) + 0.2
        )
        post = registry.models().get("DCN", [])
        lifecycle_block["post_rollback_versions"] = post
        lifecycle_block["blacklist_survived_reconcile"] = (
            bad["version"] not in post
        )

    async def probe_lifecycle(session) -> None:
        """End-of-run probes against the LIVE surfaces (the bytes an
        operator's curl would get): /lifecyclez, the ?section= filter,
        and the dts_tpu_lifecycle_* Prometheus series."""
        async with session.get("/lifecyclez") as r:
            lz = await r.json()
        lifecycle_block["lifecyclez_enabled"] = bool(lz.get("enabled"))
        lifecycle_block["state"] = lz.get("state")
        lifecycle_block["stable_version"] = lz.get("stable_version")
        lifecycle_block["counters"] = lz.get("counters")
        lifecycle_block["last_rollback"] = lz.get("last_rollback")
        lifecycle_block["blacklisted"] = (
            (lz.get("watcher") or {}).get("blacklisted", [])
        )
        async with session.get("/monitoring?section=lifecycle") as r:
            sec = await r.json()
            lifecycle_block["section_filter_ok"] = (
                r.status == 200
                and set(sec) == {"lifecycle"}
                and bool(sec["lifecycle"].get("enabled"))
            )
        async with session.get("/monitoring/prometheus/metrics") as r:
            prom_text = await r.text()
        lifecycle_block["prom_lifecycle_series"] = sum(
            1 for ln in prom_text.splitlines()
            if ln.startswith("dts_tpu_lifecycle_")
        )

    async def recovery_driver(client):
        """The scenario script: (1) wedge the device stage mid-run — the
        watchdog must quarantine, reinit, and replay with the in-flight
        depth-4 pipeline's work answered, MTTR measured to the first
        post-recovery success; (2) submit a content-keyed poisoned input
        coalesced with clean companions — the bisection must fail exactly
        the poison (PoisonedInputError) while the companions score."""
        from distributed_tf_serving_tpu import faults as faults_mod
        from distributed_tf_serving_tpu.serving.batcher import (
            PoisonedInputError,
            poison_fault_key,
            prepare_inputs,
        )

        loop_ = asyncio.get_running_loop()
        # --- phase 1: wedge at pipeline depth 4 -------------------------
        await asyncio.sleep(
            seconds * float(os.environ.get("SOAK_RECOVERY_WEDGE_AT", "0.3"))
        )
        t_inject = time.perf_counter()
        # delay_s doubles as the stranded thread's safety release; count=1
        # so the REPLAYED batch does not re-wedge.
        faults_mod.get().add(
            "batcher.dispatch", "wedge", delay_s=10.0, count=1
        )
        recovery_block["wedge_injected"] = True
        while time.perf_counter() < deadline:
            if recovery_ctrl.snapshot()["counters"]["quarantines"] >= 1:
                break
            await asyncio.sleep(0.05)
        recovery_block["quarantine_wait_s"] = round(
            time.perf_counter() - t_inject, 3
        )
        while time.perf_counter() < deadline:
            if (recovery_ctrl.state() == "serving"
                    and not recovery_ctrl.cycle_active()):
                break
            await asyncio.sleep(0.05)
        probe = make_payload(candidates=64, num_fields=NUM_FIELDS, seed=901)
        while time.perf_counter() < deadline:
            try:
                await client.predict(probe)
                break
            except Exception:  # noqa: BLE001 — still recovering
                await asyncio.sleep(0.05)
        recovery_block["mttr_s"] = round(time.perf_counter() - t_inject, 3)
        faults_mod.get().clear("batcher.dispatch")
        # --- phase 2: poisoned input + bisection ------------------------
        poison = make_payload(candidates=32, num_fields=NUM_FIELDS, seed=777)
        companions = [
            make_payload(candidates=32, num_fields=NUM_FIELDS, seed=778 + i)
            for i in range(2)
        ]
        key = poison_fault_key(
            prepare_inputs(model, poison, fold_ids=False)
        )
        faults_mod.get().add(
            "device_lost", "error", code="DATA_LOSS", key=key
        )

        def submit_all():
            # Companions first, poison in the middle, tight sequence: all
            # three land inside one 2ms coalesce window, so the first
            # kill hits a MULTI-request batch and the bisection has
            # something to split.
            f1 = batcher.submit(servable, companions[0])
            fp = batcher.submit(servable, poison)
            f2 = batcher.submit(servable, companions[1])
            return fp, [f1, f2]

        fp, fcs = await loop_.run_in_executor(None, submit_all)

        def harvest():
            out = {"poisoned": False, "companions_ok": 0}
            try:
                fp.result(timeout=90)
                out["poison_error"] = "succeeded (rule did not fire?)"
            except PoisonedInputError:
                out["poisoned"] = True
            except Exception as e:  # noqa: BLE001 — report the classification
                out["poison_error"] = type(e).__name__
            for fc in fcs:
                try:
                    fc.result(timeout=90)
                    out["companions_ok"] += 1
                except Exception as e:  # noqa: BLE001
                    out.setdefault("companion_errors", []).append(
                        type(e).__name__
                    )
            return out

        recovery_block["poison"] = await loop_.run_in_executor(None, harvest)
        faults_mod.get().clear("device_lost")

    async def probe_recovery(session) -> None:
        """End-of-run probes against the LIVE surfaces: /recoveryz, the
        ?section= filter, and the dts_tpu_recovery_* Prometheus series."""
        async with session.get("/recoveryz") as r:
            rz = await r.json()
        recovery_block["recoveryz_enabled"] = bool(rz.get("enabled"))
        recovery_block["final_state"] = rz.get("state")
        recovery_block["counters"] = rz.get("counters")
        recovery_block["last_cycle"] = rz.get("last_cycle")
        async with session.get("/monitoring?section=recovery") as r:
            sec = await r.json()
            recovery_block["section_filter_ok"] = (
                r.status == 200
                and set(sec) == {"recovery"}
                and bool(sec["recovery"].get("enabled"))
            )
        async with session.get("/monitoring/prometheus/metrics") as r:
            prom_text = await r.text()
        recovery_block["prom_recovery_series"] = sum(
            1 for ln in prom_text.splitlines()
            if ln.startswith("dts_tpu_recovery_")
        )

    async def integrity_driver(client):
        """Integrity chaos scenario: NaN rows against the readback screen
        (shadow stood down so the row-granular path is the one proving
        itself), then readback bitflips against shadow verification plus
        wire corruption both directions, then a clean closing window with
        a post-chaos bit-identity probe. Detection latencies and the
        detection->success MTTR land in integrity_block for the gate."""
        import dataclasses as _dc

        from distributed_tf_serving_tpu import faults as faults_mod

        loop_ = asyncio.get_running_loop()
        shadow_cfg = integrity_plane.config
        # --- phase 1: NaN rows -> the readback screen -------------------
        await asyncio.sleep(seconds * 0.15)
        # Shadow stands down for this phase: the compare runs pre-widen
        # and would catch the NaN first, masking the screen under test.
        integrity_plane.config = _dc.replace(
            shadow_cfg, shadow_fraction=0.0
        )
        faults_mod.get().add(
            "score_nan", "error",
            rate=float(os.environ.get("SOAK_INTEGRITY_NAN_RATE", "0.08")),
        )
        integrity_block["nan_injected"] = True
        t_nan = time.perf_counter()
        while time.perf_counter() < deadline:
            snap = integrity_plane.snapshot()
            if snap["screen"]["trips"] >= 1 and snap["escalations"] >= 1:
                break
            await asyncio.sleep(0.05)
        integrity_block["screen_detect_s"] = round(
            time.perf_counter() - t_nan, 3
        )
        faults_mod.get().clear("score_nan")
        integrity_block["screen_after_nan"] = (
            integrity_plane.snapshot()["screen"]
        )
        # --- phase 2: bitflips + wire corruption, shadow re-armed -------
        integrity_plane.config = shadow_cfg
        faults_mod.get().add(
            "readback_bitflip", "error",
            rate=float(os.environ.get("SOAK_INTEGRITY_FLIP_RATE", "0.02")),
        )
        # Request-side wire flip, keyed on the tensor name the client
        # stamps: the server must reject EXACTLY the damaged request
        # (corrupt-wire INVALID_ARGUMENT) while batchmates deliver.
        faults_mod.get().add(
            "wire_corrupt", "error",
            rate=float(os.environ.get("SOAK_INTEGRITY_WIRE_RATE", "0.03")),
            key="feat_ids",
        )
        # Response-side wire flip: the verifying client must catch the
        # checksum mismatch (scoreboard kind="corrupt"), never merge the
        # corrupt scores, and retry the shard.
        faults_mod.get().add(
            "wire_corrupt", "error",
            rate=float(os.environ.get("SOAK_INTEGRITY_RESP_RATE", "0.05")),
            key="response",
        )
        integrity_block["chaos_injected"] = True
        t_flip = time.perf_counter()
        while time.perf_counter() < deadline:
            if integrity_plane.snapshot()["shadow"]["mismatches"] >= 1:
                break
            await asyncio.sleep(0.05)
        integrity_block["shadow_detect_s"] = round(
            time.perf_counter() - t_flip, 3
        )
        # Detection -> next clean answer is the MTTR the gate bounds:
        # the mismatch escalated into a recovery cycle, so a fresh
        # request succeeding means the replica came back serving.
        probe = make_payload(candidates=64, num_fields=NUM_FIELDS, seed=911)
        t_detect = time.perf_counter()
        while time.perf_counter() < deadline:
            try:
                await client.predict(probe)
                break
            except Exception:  # noqa: BLE001 — still recovering
                await asyncio.sleep(0.05)
        integrity_block["detect_to_success_s"] = round(
            time.perf_counter() - t_detect, 3
        )
        # Keep the wire sites firing under steady traffic, then clear
        # everything so the run ends on a clean window.
        await asyncio.sleep(
            max(0.0, (deadline - time.perf_counter()) - seconds * 0.25)
        )
        faults_mod.get().clear("wire_corrupt")
        faults_mod.get().clear("readback_bitflip")
        integrity_block["faults_cleared"] = True
        # --- closing clean bit-identity probe ---------------------------
        # Wait out any in-flight recovery cycle first: the probe measures
        # the steady state after chaos, not mid-reinit unavailability.
        while time.perf_counter() < deadline:
            if (recovery_ctrl.state() == "serving"
                    and not recovery_ctrl.cycle_active()):
                break
            await asyncio.sleep(0.05)

        def closing_probe():
            integrity_plane.request_audit()
            out = batcher.submit(
                servable, unique_pool[0], output_keys=("prediction_node",)
            ).result(timeout=600)["prediction_node"]
            return bool(np.array_equal(integrity_ref["scores"], out))

        try:
            integrity_block["clean_bit_identical_post"] = (
                await loop_.run_in_executor(None, closing_probe)
            )
        except Exception as e:  # noqa: BLE001 — report, keep the line
            integrity_block["closing_probe_error"] = (
                f"{type(e).__name__}: {e}"
            )

    async def probe_integrity(session) -> None:
        """End-of-run probes against the LIVE surfaces: /integrityz, the
        on-demand audit POST, the ?section= filter, and the
        dts_tpu_integrity_* Prometheus series."""
        async with session.get("/integrityz") as r:
            iz = await r.json()
        integrity_block["integrityz_enabled"] = bool(iz.get("enabled"))
        async with session.post("/integrityz/audit?batches=2") as r:
            body = await r.json()
            integrity_block["audit_post_ok"] = (
                r.status == 200 and body.get("pending_audits", 0) >= 1
            )
        async with session.get("/monitoring?section=integrity") as r:
            sec = await r.json()
            integrity_block["section_filter_ok"] = (
                r.status == 200
                and set(sec) == {"integrity"}
                and bool(sec["integrity"].get("enabled"))
            )
        async with session.get("/monitoring/prometheus/metrics") as r:
            prom_text = await r.text()
        integrity_block["prom_integrity_series"] = sum(
            1 for ln in prom_text.splitlines()
            if ln.startswith("dts_tpu_integrity_")
        )

    async def control_worker(gport: int):
        import grpc as grpc_mod

        from distributed_tf_serving_tpu.proto import ModelServiceStub
        from distributed_tf_serving_tpu.proto import serving_apis_pb2 as apis

        async with grpc_mod.aio.insecure_channel(f"127.0.0.1:{gport}") as ch:
            stub = ModelServiceStub(ch)
            i = 0
            while time.perf_counter() < deadline:
                i += 1
                try:
                    sreq = apis.GetModelStatusRequest()
                    sreq.model_spec.name = "DCN"
                    resp = await stub.GetModelStatus(sreq, timeout=30)
                    state = resp.model_version_status[0].state
                    if state != apis.ModelVersionStatus.AVAILABLE:
                        raise RuntimeError(f"unexpected model state {state}")
                    rreq = apis.ReloadConfigRequest()
                    mc = rreq.config.model_config_list.config.add()
                    mc.name = "DCN"
                    if i % 2:  # alternate: label present / declared away
                        mc.version_labels["soak"] = 1
                    await stub.HandleReloadConfigRequest(rreq, timeout=30)
                    counts["control_ok"] += 1
                except Exception as e:  # noqa: BLE001 — classification, keep soaking
                    note_error("control", f"{type(e).__name__}: {e}")
                await asyncio.sleep(0.2)

    resilience: dict = {}
    trace_block: dict = {}
    util_block: dict = {}

    async def probe_utilz(session) -> None:
        """Probe the LIVE utilization surfaces (the same bytes an
        operator's curl would get): /utilz route liveness + the
        dts_tpu_utilization_* Prometheus series count."""
        async with session.get("/utilz") as r:
            body = await r.json()
            util_block["utilz_enabled"] = (
                r.status == 200 and bool(body.get("enabled"))
            )
        async with session.get("/monitoring/prometheus/metrics") as r:
            text = await r.text()
        util_block["prometheus_series"] = sum(
            1 for ln in text.splitlines()
            if ln.startswith("dts_tpu_utilization_")
        )

    async def probe_cascade(session) -> None:
        """Probe the LIVE cascade surfaces (the same bytes an operator's
        curl would get): /cascadez liveness + moving counters, the
        dts_tpu_cascade_* Prometheus series count, and the cascade phase
        spans in /monitoring?section=phases."""
        async with session.get("/cascadez") as r:
            body = await r.json()
            cascade_block["cascadez_live"] = (
                r.status == 200 and body.get("requests", 0) > 0
            )
        async with session.get("/monitoring/prometheus/metrics") as r:
            text = await r.text()
        cascade_block["prometheus_series"] = sum(
            1 for ln in text.splitlines()
            if ln.startswith("dts_tpu_cascade_")
        )
        async with session.get("/monitoring?section=phases") as r:
            phases = (await r.json()).get("phases") or {}
        cascade_block["spans_present"] = all(
            p in phases
            for p in ("cascade.stage1", "cascade.prune", "cascade.stage2")
        )

    async def export_trace(session) -> None:
        """Probe the LIVE /tracez surface (the same bytes an operator's
        curl would get) and persist the Chrome trace artifact."""
        async with session.get("/tracez?format=chrome") as r:
            body = await r.read()
            if r.status != 200:
                trace_block["error"] = f"http {r.status}"
                return
        with open(trace_out, "wb") as f:
            f.write(body)
        doc = json.loads(body)
        from distributed_tf_serving_tpu.utils import tracing

        trace_block.update({
            "path": trace_out,
            "events": len(doc.get("traceEvents", ())),
            "recorded": tracing.recorder().recorded,
            "retained": len(tracing.recorder().spans()),
        })

    client_counters: list[dict] = []

    async def drive():
        server, gport = create_server(impl, "127.0.0.1:0")
        server.start()
        runner, rport = await start_rest_gateway(impl, port=0)
        try:
            client_kwargs = dict(
                channels_per_host=3,
                # Chaos soaks run the resilience layer live: scoreboard on,
                # one failover attempt so injected UNAVAILABLEs reroute
                # (same single host — exercises the backoff path). Overload
                # soaks run it too: sheds must land as PUSHBACK (busy) on
                # the scoreboard and the one retry honors retry-after-ms.
                scoreboard=(
                    chaos or overload_mode or recovery_mode or integrity_mode
                ),
                failover_attempts=(
                    8 if (recovery_mode or integrity_mode)
                    else 1 if (chaos or overload_mode) else 0
                ),
            )
            if integrity_mode:
                # The client half of the wire layer: stamp request CRCs
                # and verify the server's score CRC before merging —
                # corrupt responses must surface as retries, never data.
                client_kwargs["integrity_checksums"] = True
            if recovery_mode or integrity_mode:
                # Retries must OUTLAST the recovery cycles (quarantined
                # submits answer UNAVAILABLE until REPLAY, and the wedge
                # + poison phases can run 2-3 back-to-back cycles of a
                # few seconds each on a CPU host — in production the
                # scoreboard reroutes to another replica instead). The
                # new per-request attempt budget rides along, sized so
                # it never binds here while still exercising the knob
                # end to end.
                client_kwargs.update(
                    backoff_initial_s=0.3, backoff_max_s=2.0,
                    timeout_s=25.0, max_attempts_total=16,
                )
            if overload_mode:
                # The RPC deadline IS the goodput bar: a success under
                # this client is by construction an in-deadline success.
                client_kwargs["timeout_s"] = overload_deadline_s
            async with contextlib.AsyncExitStack() as stack:
                client = await stack.enter_async_context(
                    ShardedPredictClient(
                        [f"127.0.0.1:{gport}"], "DCN", **client_kwargs
                    )
                )
                # One worker in three sends criticality=sheddable — the
                # lane an overloaded server drops first.
                shed_client = (
                    await stack.enter_async_context(
                        ShardedPredictClient(
                            [f"127.0.0.1:{gport}"], "DCN",
                            criticality="sheddable", **client_kwargs,
                        )
                    )
                    if overload_mode else None
                )
                # Lifecycle mode: one worker rides the probe lane — the
                # canary's first traffic (probe-lane-first admission).
                probe_client = (
                    await stack.enter_async_context(
                        ShardedPredictClient(
                            [f"127.0.0.1:{gport}"], "DCN",
                            criticality="probe", **client_kwargs,
                        )
                    )
                    if lifecycle_mode else None
                )
                session = await stack.enter_async_context(
                    aiohttp.ClientSession(f"http://127.0.0.1:{rport}")
                )
                try:
                    # Quality mode swaps the standard gRPC mixers for the
                    # teacher-labeled workload (unshifted mixer traffic
                    # would dilute the drift segment the gate measures)
                    # plus the mid-run reference pin.
                    if quality_mode:
                        data_workers = [
                            quality_worker(client, session, w)
                            for w in range(grpc_workers)
                        ] + [quality_pin(session)]
                    elif lifecycle_mode:
                        # The scenario driver rides next to the workers;
                        # the control-plane label flipper is skipped (it
                        # pins version 1, which retention legitimately
                        # retires mid-scenario).
                        data_workers = [
                            lifecycle_worker(
                                probe_client if w == 0 else client, w
                            )
                            for w in range(grpc_workers)
                        ] + [lifecycle_driver()]
                    else:
                        data_workers = [
                            grpc_worker(
                                shed_client
                                if (shed_client is not None and w % 3 == 2)
                                else client,
                                w,
                            )
                            for w in range(grpc_workers)
                        ]
                    await asyncio.gather(
                        *data_workers,
                        *([recovery_driver(client)] if recovery_mode else []),
                        *([integrity_driver(client)] if integrity_mode else []),
                        *(burst_worker(client, w) for w in range(burst_workers)),
                        *(rest_worker(session, w) for w in range(rest_workers)),
                        *([] if lifecycle_mode else [control_worker(gport)]),
                    )
                finally:
                    resilience.update(client.resilience_counters())
                    client_counters.append(client.resilience_counters())
                    if shed_client is not None:
                        client_counters.append(shed_client.resilience_counters())
                    prom_out = os.environ.get("SOAK_PROM_OUT", "")
                    if prom_out:
                        # Client resilience state in Prometheus text, next
                        # to the soak artifact (the client has no scrape
                        # port of its own).
                        with open(prom_out, "w") as f:
                            f.write(client.resilience_prometheus_text())
                    if util_mode:
                        try:
                            await probe_utilz(session)
                        except Exception as e:  # noqa: BLE001 — report, keep line
                            util_block["error"] = f"{type(e).__name__}: {e}"
                    if quality_mode:
                        try:
                            await probe_quality(session)
                        except Exception as e:  # noqa: BLE001 — report, keep line
                            quality_block["error"] = f"{type(e).__name__}: {e}"
                    if lifecycle_mode:
                        try:
                            await probe_lifecycle(session)
                        except Exception as e:  # noqa: BLE001 — report, keep line
                            lifecycle_block["error"] = f"{type(e).__name__}: {e}"
                    if recovery_mode:
                        try:
                            await probe_recovery(session)
                        except Exception as e:  # noqa: BLE001 — report, keep line
                            recovery_block["error"] = f"{type(e).__name__}: {e}"
                    if cascade_mode:
                        try:
                            await probe_cascade(session)
                        except Exception as e:  # noqa: BLE001 — report, keep line
                            cascade_block["error"] = f"{type(e).__name__}: {e}"
                    if integrity_mode:
                        try:
                            await probe_integrity(session)
                        except Exception as e:  # noqa: BLE001 — report, keep line
                            integrity_block["error"] = f"{type(e).__name__}: {e}"
                    if trace_out:
                        try:
                            await export_trace(session)
                        except Exception as e:  # noqa: BLE001 — report, keep line
                            trace_block["error"] = f"{type(e).__name__}: {e}"
        finally:
            await runner.cleanup()
            server.stop(0).wait()

    t0 = time.perf_counter()
    try:
        asyncio.run(drive())
    finally:
        # Always drain/close (a crashed drive must not leak the writer or
        # leave an append-mode file for a pid-recycled later run).
        if request_logger is not None:
            request_logger.close()
    wall = time.perf_counter() - t0
    total = counts["grpc_ok"] + counts["rest_ok"]
    # Leak-watch RSS BEFORE the parse-back pass below reads the whole log
    # file into memory (malloc arenas rarely shrink; sampling after would
    # report a phantom leak).
    rss_end = rss_gb()
    request_log_block = None
    if request_logger is not None:
        from distributed_tf_serving_tpu.serving.warmup import read_tfrecords

        try:
            parsed = sum(1 for _ in read_tfrecords(log_path))
            parse_err = None
        except Exception as e:  # noqa: BLE001 — report, don't crash the line
            parsed, parse_err = -1, f"{type(e).__name__}: {e}"[:200]
        request_log_block = {
            "sampling": log_sampling,
            "written": request_logger.written,
            "dropped": request_logger.dropped,
            "parsed_back": parsed,
            "parse_error": parse_err,
        }
        if parse_err is None:
            os.remove(log_path)
        else:
            request_log_block["kept_file"] = log_path  # evidence for triage
    if quality_mode:
        # The acceptance comparison: the LIVE windowed AUC (served by
        # /qualityz from the monitor's joined pairs) vs the EXACT AUC the
        # soak computes offline from its own (score, label) log over the
        # same window — train/data.py::auc both times, so a disagreement
        # is a join/reservoir bug, not a metric-definition mismatch.
        from distributed_tf_serving_tpu.train.data import auc as exact_auc

        qz = quality_block.get("qualityz") or {}
        labels_blk = qz.get("labels") or {}
        cutoff = time.monotonic() - q_window_s
        offline_all = offline_window = None
        try:
            if quality_log:
                arr = np.asarray([(s, lb) for s, lb, _t in quality_log])
                offline_all = round(float(exact_auc(arr[:, 1], arr[:, 0])), 6)
            recent = [(s, lb) for s, lb, t in quality_log if t >= cutoff]
            if recent:
                arr = np.asarray(recent)
                offline_window = round(float(exact_auc(arr[:, 1], arr[:, 0])), 6)
        except ValueError:
            pass  # single-class log: AUC undefined, reported as null
        drift_blk = (
            ((qz.get("models") or {}).get("DCN") or {}).get("drift") or {}
        )
        quality_block.update({
            "window_s": q_window_s,
            "windowed_auc": labels_blk.get("auc"),
            "offline_auc_window": offline_window,
            "offline_auc_all": offline_all,
            "offline_pairs": len(quality_log),
            "labels_joined": labels_blk.get("joined", 0),
            "labels_orphaned": labels_blk.get("orphaned", 0),
            "drift": drift_blk,
            "observed_requests": qz.get("observed_requests", 0),
        })
        # The full /qualityz body served its numbers; keep the line lean.
        quality_block.pop("qualityz", None)
    line = {
        "soak_seconds": round(wall, 1),
        "platform": str(jax.devices()[0]),
        "requests_total": total,
        "qps": round(total / wall, 1),
        **{k: v for k, v in counts.items() if k != "errors"},
        "error_kinds": counts["errors"],
        "rss_gb_start": rss_start,
        "rss_gb_end": rss_end,
        "request_log": request_log_block,
        "batcher": {
            "batches": batcher.stats.batches,
            "fused_batches": batcher.stats.fused_batches,
            "requests_per_batch": round(batcher.stats.mean_requests_per_batch, 2),
            "deadline_sheds": batcher.stats.deadline_sheds,
            "dedup_batches": batcher.stats.dedup_batches,
            "dedup_rows_collapsed": batcher.stats.dedup_rows_collapsed,
        },
        "cache": (
            {
                **{k: v for k, v in score_cache.snapshot().items()
                   if k != "models"},
                "skew": cache_skew,
                "dedup_batches": batcher.stats.dedup_batches,
                "dedup_rows_collapsed": batcher.stats.dedup_rows_collapsed,
                **cache_block,
                # Workload-only deltas (probe counts subtracted): what the
                # zipfian WORKER traffic did — the CI gate reads these, so
                # the probe's guaranteed hit can never green-wash a cache
                # that stopped hitting under load.
                **{
                    f"workload_{k}": (
                        score_cache.snapshot()[k]
                        - cache_block.get("probe_snapshot", {}).get(k, 0)
                    )
                    for k in ("hits", "misses", "coalesced")
                },
            }
            if cache_mode else None
        ),
        "row_cache": (
            {
                **{k: v for k, v in row_cache.snapshot().items()
                   if k != "models"},
                "scores_match": cache_block.get("row_scores_match"),
                "row_batches": batcher.stats.row_batches,
                "row_full_hit_batches": batcher.stats.row_full_hit_batches,
                "batcher_rows_requested": batcher.stats.rows_requested,
                "batcher_rows_executed": batcher.stats.rows_executed,
                # Workload-only deltas (probe counts subtracted): the CI
                # gate reads these, so the probe's guaranteed row hits
                # can never green-wash a row cache idle under load.
                **{
                    f"workload_{k}": (
                        row_cache.snapshot()[k]
                        - cache_block.get("row_probe_snapshot", {}).get(k, 0)
                    )
                    for k in ("hits", "misses", "coalesced",
                              "rows_requested", "rows_executed")
                },
            }
            if rowcache_mode else None
        ),
        "resilience": resilience or None,
        "overload": (
            {
                # Goodput: every grpc_ok ran under timeout_s == the
                # deadline, so successes ARE in-deadline successes.
                "goodput_qps": round(counts["grpc_ok"] / wall, 1),
                "deadline_s": overload_deadline_s,
                "dispatch_delay_s": dispatch_delay_s,
                "grpc_workers": grpc_workers,
                "burst_workers": burst_workers,
                "controller": batcher.overload.snapshot(),
                "stale_serves": score_cache.snapshot()["stale_serves"],
                # Aggregated across BOTH clients (default + sheddable):
                # the smoke gate reads these — sheds must register as
                # pushback (busy), never as ejection.
                "client_pushbacks": sum(
                    c.get("pushbacks_received", 0) for c in client_counters
                ),
                "client_retry_after_honored": sum(
                    c.get("retry_after_honored", 0) for c in client_counters
                ),
                "client_ejections": sum(
                    c.get("scoreboard", {}).get("ejections", 0)
                    for c in client_counters
                ),
            }
            if overload_mode else None
        ),
        "trace": trace_block or None,
        # Utilization plane (SOAK_UTIL=1): ledger snapshot (gap waterfall
        # summing to wall + live achieved fraction) plus the live-route
        # probes — the CI gate (tools/check_util_smoke.py) reads this.
        "utilization": (
            {**ledger.snapshot(window_s=wall), **util_block}
            if util_mode else None
        ),
        # Quality plane (SOAK_QUALITY=1): live-route probes + the
        # windowed-vs-offline AUC comparison — the CI gate
        # (tools/check_quality_smoke.py) reads this.
        "quality": quality_block if quality_mode else None,
        # Lifecycle plane (SOAK_LIFECYCLE=1): promote + rollback +
        # blacklist-persistence evidence with live-route probes — the CI
        # gate (tools/check_lifecycle_smoke.py) reads this.
        "lifecycle": lifecycle_block if lifecycle_mode else None,
        # Recovery plane (SOAK_RECOVERY=1): wedge-trip MTTR + poison
        # bisection evidence with live-route probes — the CI gate
        # (tools/check_recovery_smoke.py) reads this.
        "recovery": recovery_block if recovery_mode else None,
        # Cascade plane (SOAK_CASCADE=1): the full snapshot (row
        # dispositions, per-stage seconds, survivor-bucket histogram)
        # plus the bit-identity probe verdict, live-route probe results,
        # and workload-only deltas (probe counts subtracted) — the CI
        # gate (tools/check_cascade_smoke.py) reads this.
        "cascade": (
            {
                **impl.cascade.snapshot(),
                **cascade_block,
                **{
                    f"workload_{k}": (
                        impl.cascade.snapshot()[k]
                        - cascade_block.get("probe_snapshot", {}).get(k, 0)
                    )
                    for k in ("requests", "rows_requested", "rows_ranked",
                              "pruned_rows")
                },
            }
            if cascade_mode else None
        ),
        # Integrity plane (SOAK_INTEGRITY=1): the full plane snapshot,
        # both clean bit-identity verdicts, per-layer detection evidence,
        # the verifying client's corrupt/NaN counters, recovery
        # escalation counters, and live-route probes — the CI gate
        # (tools/check_integrity_smoke.py) reads this.
        "integrity": (
            {
                **integrity_plane.snapshot(),
                **integrity_block,
                "client": {
                    "corrupt_responses": resilience.get(
                        "corrupt_responses", 0
                    ),
                    "nan_scores_merged": resilience.get(
                        "nan_scores_merged", 0
                    ),
                },
                "recovery_counters": (
                    recovery_ctrl.snapshot()["counters"]
                    if recovery_ctrl is not None else None
                ),
            }
            if integrity_mode else None
        ),
        "chaos": None,
        "input_cache": (
            {
                "hits": batcher.input_cache.hits,
                "misses": batcher.input_cache.misses,
                "bypassed": batcher.input_cache.bypassed,
                "bypass_cycles": batcher.input_cache.bypass_cycles,
                "mb_upload_skipped": round(batcher.input_cache.bytes_skipped / 1e6, 1),
            }
            if batcher.input_cache is not None
            else None
        ),
    }
    if chaos or overload_mode or recovery_mode or integrity_mode:
        from distributed_tf_serving_tpu import faults

        if chaos:
            line["chaos"] = faults.get().snapshot()
        faults.reset()
    if recovery_ctrl is not None:
        recovery_ctrl.stop()
    if lifecycle_ctrl is not None:
        lifecycle_ctrl.stop()
    if lifecycle_watcher is not None:
        lifecycle_watcher.stop()
    batcher.stop()
    print(json.dumps(line))


if __name__ == "__main__":
    main()
