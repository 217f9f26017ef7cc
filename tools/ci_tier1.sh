#!/usr/bin/env bash
# Tier-1 verification — the command the driver runs after every PR
# (/root/TESTS_LAST_RUN.json `commands`: six xdist workers, a file to a
# worker, a limit of 1,470 s; ROADMAP.md's older "Tier-1 verify" line has
# 870 s in one process), wrapped so CI (.github/workflows/tier1.yml) and a
# local shell run identically:
#
#     tools/ci_tier1.sh
#
# Runs the non-slow test suite on the CPU platform, tees the log, prints a
# DOTS_PASSED count (the driver's pass-counting convention: the junit
# file's tests less errors, failures and skips, else the dots), and exits
# with pytest's status. The driver also sets ALLOW_MULTIPLE_LIBTPU_LOAD=1
# for its own run; this file does not (tests/test_tpu_compile.py describes
# the topology inside a fixture of one file, so one worker loads libtpu).
# With TIER1_TRACE_SMOKE=1 (CI sets it), a passing test run is followed by
# an observability smoke: a short traced chaos soak (SOAK_CHAOS=1 +
# SOAK_TRACE_OUT) whose /tracez-served Chrome-trace artifact must be
# non-empty and schema-valid (tools/check_trace.py). The artifact lands at
# $TIER1_TRACE_ARTIFACT (default /tmp/tier1_soak_trace.json) so CI can
# upload it for debugging when the step fails.
set -o pipefail
cd "$(dirname "$0")/.."

LOG="${TIER1_LOG:-/tmp/_t1.log}"
XML="${LOG%.log}.xml"
rm -f "$LOG" "$XML"
timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile \
    --junitxml="$XML" -p no:randomly 2>&1 | tee "$LOG"
rc=${PIPESTATUS[0]}
said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' "$XML" 2>/dev/null \
    | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}')
echo "DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$LOG" | tr -cd . | wc -c)}"

if [ "$rc" -eq 0 ] && [ "${TIER1_TRACE_SMOKE:-0}" = "1" ]; then
    ARTIFACT="${TIER1_TRACE_ARTIFACT:-/tmp/tier1_soak_trace.json}"
    echo "tier1: trace smoke (SOAK_CHAOS=1 SOAK_UTIL=1, artifact $ARTIFACT)"
    # SOAK_UTIL=1 rides along so the exported Chrome trace carries the
    # per-device occupancy counter track, which check_trace.py now
    # schema-gates (monotonic counter ts, per-device track names).
    timeout -k 10 300 env JAX_PLATFORMS=cpu \
        SOAK_SECONDS="${TIER1_SMOKE_SECONDS:-8}" SOAK_CHAOS=1 SOAK_UTIL=1 \
        SOAK_GRPC_WORKERS=2 SOAK_REST_WORKERS=1 SOAK_CANDIDATES=64 \
        SOAK_TRACE_OUT="$ARTIFACT" SOAK_TRACE_SAMPLE=0.5 \
        python tools/soak.py || rc=1
    python tools/check_trace.py "$ARTIFACT" --min-events 10 \
        --require-counter-track || rc=1
fi

# Cache smoke (TIER1_CACHE_SMOKE=1): a short SOAK_CACHE=1 skewed soak must
# report a NONZERO hit rate and bit-identical scores with the cache on vs
# off (the soak's pre-flight miss/hit probe) — the cache plane's tier-1
# acceptance gate.
if [ "$rc" -eq 0 ] && [ "${TIER1_CACHE_SMOKE:-0}" = "1" ]; then
    CACHE_LINE="${TIER1_CACHE_LINE:-/tmp/tier1_cache_soak.json}"
    echo "tier1: cache smoke (SOAK_CACHE=1, line $CACHE_LINE)"
    timeout -k 10 300 env JAX_PLATFORMS=cpu \
        SOAK_SECONDS="${TIER1_SMOKE_SECONDS:-8}" SOAK_CACHE=1 \
        SOAK_GRPC_WORKERS=4 SOAK_REST_WORKERS=1 SOAK_CANDIDATES=64 \
        python tools/soak.py | tee "$CACHE_LINE" || rc=1
    python tools/check_cache_smoke.py "$CACHE_LINE" || rc=1
fi

# Row-cache smoke (TIER1_ROWCACHE_SMOKE=1): a short SOAK_ROWCACHE=1 zipfian
# soak — the row-granular cache (ISSUE 14) next to the request cache — must
# report a NONZERO per-row hit rate, rows_executed < rows_requested (only
# cold rows reached the device), bit-identical scores vs the disarmed
# plane, and zero gRPC errors (tools/check_rowcache_smoke.py).
if [ "$rc" -eq 0 ] && [ "${TIER1_ROWCACHE_SMOKE:-0}" = "1" ]; then
    ROWCACHE_LINE="${TIER1_ROWCACHE_LINE:-/tmp/tier1_rowcache_soak.json}"
    echo "tier1: row-cache smoke (SOAK_ROWCACHE=1, line $ROWCACHE_LINE)"
    timeout -k 10 300 env JAX_PLATFORMS=cpu \
        SOAK_SECONDS="${TIER1_SMOKE_SECONDS:-8}" SOAK_ROWCACHE=1 \
        SOAK_GRPC_WORKERS=4 SOAK_REST_WORKERS=1 SOAK_CANDIDATES=64 \
        python tools/soak.py | tee "$ROWCACHE_LINE" || rc=1
    python tools/check_rowcache_smoke.py "$ROWCACHE_LINE" || rc=1
fi

# Overload smoke (TIER1_OVERLOAD_SMOKE=1): a short SOAK_OVERLOAD=1 soak —
# ~3x sustainable load with a mid-run burst against the adaptive admission
# plane — must show nonzero sheds, nonzero brownout stale-serves, client
# pushback with a honored retry-after hint, ZERO scoreboard ejections of
# the overloaded backend, and goodput above a floor
# (tools/check_overload_smoke.py). Runs the soak's own overload defaults
# (24+12 burst workers, 1000-candidate requests): the mode's knobs were
# tuned as a set, and shrinking them piecemeal starves the shed path.
if [ "$rc" -eq 0 ] && [ "${TIER1_OVERLOAD_SMOKE:-0}" = "1" ]; then
    OVERLOAD_LINE="${TIER1_OVERLOAD_LINE:-/tmp/tier1_overload_soak.json}"
    echo "tier1: overload smoke (SOAK_OVERLOAD=1, line $OVERLOAD_LINE)"
    timeout -k 10 300 env JAX_PLATFORMS=cpu \
        SOAK_SECONDS="${TIER1_OVERLOAD_SECONDS:-12}" SOAK_OVERLOAD=1 \
        python tools/soak.py | tee "$OVERLOAD_LINE" || rc=1
    python tools/check_overload_smoke.py "$OVERLOAD_LINE" || rc=1
fi

# Utilization smoke (TIER1_UTIL_SMOKE=1): a short SOAK_UTIL=1 soak with
# the occupancy ledger armed must show nonzero device-busy intervals, a
# gap waterfall whose components sum to wall within 2%, a sane live
# achieved_fraction_of_device_limit, the /utilz route answering, and
# dts_tpu_utilization_* Prometheus series present
# (tools/check_util_smoke.py) — the utilization plane's tier-1 gate.
if [ "$rc" -eq 0 ] && [ "${TIER1_UTIL_SMOKE:-0}" = "1" ]; then
    UTIL_LINE="${TIER1_UTIL_LINE:-/tmp/tier1_util_soak.json}"
    echo "tier1: utilization smoke (SOAK_UTIL=1, line $UTIL_LINE)"
    timeout -k 10 300 env JAX_PLATFORMS=cpu \
        SOAK_SECONDS="${TIER1_SMOKE_SECONDS:-8}" SOAK_UTIL=1 \
        SOAK_GRPC_WORKERS=4 SOAK_REST_WORKERS=1 SOAK_CANDIDATES=64 \
        python tools/soak.py | tee "$UTIL_LINE" || rc=1
    python tools/check_util_smoke.py "$UTIL_LINE" || rc=1
fi

# Quality smoke (TIER1_QUALITY_SMOKE=1): a SOAK_QUALITY=1 soak — model
# trained on the synthetic teacher, labels reported to the live /labelz,
# reference pinned mid-run, shifted segment after it — must sketch scores
# with warmup excluded, join labels with the live windowed AUC within
# 0.05 of the soak's own offline exact AUC (and above coin-flip), drive
# PSI over threshold with >=1 quality.drift exemplar visible in /tracez,
# and serve dts_tpu_quality_* series whose captured exposition text
# passes tools/check_prom.py (tools/check_quality_smoke.py runs both).
# Slightly longer than the other smokes: the run needs a steady phase, a
# pin, and a drifted window inside one soak.
if [ "$rc" -eq 0 ] && [ "${TIER1_QUALITY_SMOKE:-0}" = "1" ]; then
    QUALITY_LINE="${TIER1_QUALITY_LINE:-/tmp/tier1_quality_soak.json}"
    QUALITY_PROM="${TIER1_QUALITY_PROM:-/tmp/tier1_quality_prom.txt}"
    echo "tier1: quality smoke (SOAK_QUALITY=1, line $QUALITY_LINE)"
    timeout -k 10 300 env JAX_PLATFORMS=cpu \
        SOAK_SECONDS="${TIER1_QUALITY_SECONDS:-12}" SOAK_QUALITY=1 \
        SOAK_QUALITY_PROM_OUT="$QUALITY_PROM" \
        python tools/soak.py | tee "$QUALITY_LINE" || rc=1
    python tools/check_quality_smoke.py "$QUALITY_LINE" || rc=1
fi

# The streaming smoke (tools/check_streaming_smoke.py) and the mesh smoke
# (tools/check_mesh_smoke.py) cost about six seconds each and run INSIDE the
# test run above, as tests/test_tool_smokes.py: no knob turns them on. Each
# script's docstring says what it judges.

# Recovery smoke (TIER1_RECOVERY_SMOKE=1): a SOAK_RECOVERY=1 soak — the
# device-failure recovery plane under live traffic on a depth-4
# pipeline: an injected wedge at the device stage must quarantine the
# replica (watchdog escalation), reinit + replay the captured pipeline
# with ZERO client-visible non-poison failures and a bounded MTTR, and
# a content-keyed poisoned input coalesced with clean companions must
# fail ALONE via bisection (PoisonedInputError) while the companions
# replay to success (tools/check_recovery_smoke.py).
if [ "$rc" -eq 0 ] && [ "${TIER1_RECOVERY_SMOKE:-0}" = "1" ]; then
    RECOVERY_LINE="${TIER1_RECOVERY_LINE:-/tmp/tier1_recovery_soak.json}"
    echo "tier1: recovery smoke (SOAK_RECOVERY=1, line $RECOVERY_LINE)"
    timeout -k 10 300 env JAX_PLATFORMS=cpu \
        SOAK_SECONDS="${TIER1_RECOVERY_SECONDS:-14}" SOAK_RECOVERY=1 \
        python tools/soak.py | tee "$RECOVERY_LINE" || rc=1
    python tools/check_recovery_smoke.py "$RECOVERY_LINE" || rc=1
fi

# Elastic smoke (TIER1_ELASTIC_SMOKE=1): the ISSUE-15 serving-mode gate —
# on 8 emulated CPU devices (the script forces the device count itself) a
# pinned `pressure` fault escalates the overload state machine to
# BROWNOUT under a ramped stream: the serving split must switch UP
# (toward data-parallel) under pressure and DOWN after recovery, with
# every response BIT-IDENTICAL to a pinned-split reference stack serving
# the same checkpoint, ZERO failed requests across both switch windows,
# every ladder rung warmup-compiled before the stream, and the
# dts_tpu_elastic_* series lint-clean (tools/check_elastic_smoke.py).
if [ "$rc" -eq 0 ] && [ "${TIER1_ELASTIC_SMOKE:-0}" = "1" ]; then
    ELASTIC_LINE="${TIER1_ELASTIC_LINE:-/tmp/tier1_elastic_smoke.json}"
    echo "tier1: elastic smoke (line $ELASTIC_LINE)"
    timeout -k 10 300 env JAX_PLATFORMS=cpu \
        python tools/check_elastic_smoke.py | tee "$ELASTIC_LINE" || rc=1
fi

# Lifecycle smoke (TIER1_LIFECYCLE_SMOKE=1): a SOAK_LIFECYCLE=1 soak —
# trained model behind a real version watcher + lifecycle controller;
# the driver publishes a fine-tuned GOOD canary (must auto-promote) and
# then a POISONED one (must auto-rollback: watcher retires + blacklists
# it, and the blacklist holds across reconcile passes while the bad dir
# still sits ready on disk) — with zero failed requests attributable to
# either swap and the live /lifecyclez + section filter + Prometheus
# series answering (tools/check_lifecycle_smoke.py). Slightly longer
# than the other smokes: one run holds a fine-tune, a promote ramp, a
# rollback, and post-rollback reconcile passes.
if [ "$rc" -eq 0 ] && [ "${TIER1_LIFECYCLE_SMOKE:-0}" = "1" ]; then
    LIFECYCLE_LINE="${TIER1_LIFECYCLE_LINE:-/tmp/tier1_lifecycle_soak.json}"
    echo "tier1: lifecycle smoke (SOAK_LIFECYCLE=1, line $LIFECYCLE_LINE)"
    timeout -k 10 300 env JAX_PLATFORMS=cpu \
        SOAK_SECONDS="${TIER1_LIFECYCLE_SECONDS:-20}" SOAK_LIFECYCLE=1 \
        python tools/soak.py | tee "$LIFECYCLE_LINE" || rc=1
    python tools/check_lifecycle_smoke.py "$LIFECYCLE_LINE" || rc=1
fi

# Fleet smoke (TIER1_FLEET_SMOKE=1): a SOAK_FLEET=1 chaos soak — 3
# serving-replica subprocesses (shared versioned base dir, lifecycle +
# gossip armed) behind the fleet.router subprocess, edge traffic dialing
# ONLY the router. SIGKILL one replica mid-traffic (zero edge-visible
# errors, per-1s goodput >= half the steady median), restart it (must
# rejoin the rotation via gossip), publish a canary into the shared base
# dir, then one replica's operator rollback must blacklist the version
# FLEET-WIDE within ~one gossip interval of the router's state change —
# with scores through the router bit-identical to a direct backend call
# before and after (tools/check_fleet_smoke.py). Longer budget: the run
# boots four processes and three of them compile a bucket ladder.
if [ "$rc" -eq 0 ] && [ "${TIER1_FLEET_SMOKE:-0}" = "1" ]; then
    FLEET_LINE="${TIER1_FLEET_LINE:-/tmp/tier1_fleet_soak.json}"
    echo "tier1: fleet smoke (SOAK_FLEET=1, line $FLEET_LINE)"
    timeout -k 10 420 env JAX_PLATFORMS=cpu \
        SOAK_SECONDS="${TIER1_FLEET_SECONDS:-20}" SOAK_FLEET=1 \
        python tools/soak.py | tee "$FLEET_LINE" || rc=1
    python tools/check_fleet_smoke.py "$FLEET_LINE" || rc=1
fi

# Fleet observability smoke (TIER1_FLEETOBS_SMOKE=1, ISSUE 18): the
# fleet chaos soak re-run with the observability plane armed fleet-wide
# (SOAK_TRACE_OUT triggers it in fleet mode) — tracing + trace export
# on every replica and the router, [slo] on the router, tracing in the
# edge process. Gated on: >= 1 stitched trace spanning client + router
# + replica, the hop waterfall closing within 2%, aggregate qps within
# 5% of the member sum, sane SLO burn rates
# (tools/check_fleetobs_smoke.py), and the multi-pid Chrome artifact
# passing tools/check_trace.py --require-multi-pid.
if [ "$rc" -eq 0 ] && [ "${TIER1_FLEETOBS_SMOKE:-0}" = "1" ]; then
    FLEETOBS_LINE="${TIER1_FLEETOBS_LINE:-/tmp/tier1_fleetobs_soak.json}"
    FLEETOBS_TRACE="${TIER1_FLEETOBS_TRACE:-/tmp/tier1_fleetobs_trace.json}"
    echo "tier1: fleet observability smoke (SOAK_FLEET=1 +" \
        "SOAK_TRACE_OUT=$FLEETOBS_TRACE, line $FLEETOBS_LINE)"
    timeout -k 10 420 env JAX_PLATFORMS=cpu \
        SOAK_SECONDS="${TIER1_FLEETOBS_SECONDS:-20}" SOAK_FLEET=1 \
        SOAK_TRACE_OUT="$FLEETOBS_TRACE" \
        python tools/soak.py | tee "$FLEETOBS_LINE" || rc=1
    python tools/check_fleetobs_smoke.py "$FLEETOBS_LINE" || rc=1
    python tools/check_trace.py "$FLEETOBS_TRACE" --min-events 10 \
        --require-multi-pid || rc=1
fi

# Cascade smoke (TIER1_CASCADE_SMOKE=1, ISSUE 19): a short SOAK_CASCADE=1
# soak — every score-filtered gRPC request runs retrieval->rank through
# the two-executable cascade (two_tower stage 1, on-device prune to 25%
# survivors, DCN over the survivor rung) — must report nonzero pruned
# rows, rows_ranked/rows_requested < 0.5, survivor scores bit-identical
# to a full-pass reference, zero gRPC errors, zero fallbacks, and the
# /cascadez + dts_tpu_cascade_* + cascade-span surfaces live
# (tools/check_cascade_smoke.py). Default candidates (1000): the prune
# must actually cross rungs (1024 -> 256).
if [ "$rc" -eq 0 ] && [ "${TIER1_CASCADE_SMOKE:-0}" = "1" ]; then
    CASCADE_LINE="${TIER1_CASCADE_LINE:-/tmp/tier1_cascade_soak.json}"
    echo "tier1: cascade smoke (SOAK_CASCADE=1, line $CASCADE_LINE)"
    timeout -k 10 300 env JAX_PLATFORMS=cpu \
        SOAK_SECONDS="${TIER1_SMOKE_SECONDS:-8}" SOAK_CASCADE=1 \
        SOAK_GRPC_WORKERS=4 SOAK_REST_WORKERS=1 \
        python tools/soak.py | tee "$CASCADE_LINE" || rc=1
    python tools/check_cascade_smoke.py "$CASCADE_LINE" || rc=1
fi

# Integrity smoke (TIER1_INTEGRITY_SMOKE=1, ISSUE 20): a SOAK_INTEGRITY=1
# chaos soak — wire flips both directions, readback bitflips, NaN score
# rows injected mid-run against the armed data-integrity plane
# (shadow_fraction=1.0, recovery controller live, verifying client) —
# must report detections on EVERY layer (server wire rejects, client
# corrupt-response catches, readback screen trips, shadow mismatches),
# zero NaN scores merged, every client-visible error an integrity
# rejection/retry, escalations landing in completed recovery cycles,
# bounded detection->success MTTR, clean traffic bit-identical plane-on
# vs off both before and after chaos, and the /integrityz +
# ?section=integrity + dts_tpu_integrity_* surfaces live
# (tools/check_integrity_smoke.py). Longer budget: shadow verification
# doubles the forward work and each escalation re-warms the ladder.
if [ "$rc" -eq 0 ] && [ "${TIER1_INTEGRITY_SMOKE:-0}" = "1" ]; then
    INTEGRITY_LINE="${TIER1_INTEGRITY_LINE:-/tmp/tier1_integrity_soak.json}"
    echo "tier1: integrity smoke (SOAK_INTEGRITY=1, line $INTEGRITY_LINE)"
    timeout -k 10 420 env JAX_PLATFORMS=cpu \
        SOAK_SECONDS="${TIER1_INTEGRITY_SECONDS:-25}" SOAK_INTEGRITY=1 \
        python tools/soak.py | tee "$INTEGRITY_LINE" || rc=1
    python tools/check_integrity_smoke.py "$INTEGRITY_LINE" || rc=1
fi
exit $rc
