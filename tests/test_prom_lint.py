"""Prometheus exposition lint (tools/check_prom.py, ISSUE 7 satellite):
the aggregated /monitoring/prometheus/metrics text is assembled from
nine planes and the lint is what guards the assembly — run it against a
FULLY ARMED server snapshot (every plane emitting, adversarial label
values), and prove it actually catches each failure mode it claims to."""

import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

sys.path.insert(
    0,
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"),
)
from check_prom import lint_text  # noqa: E402

from distributed_tf_serving_tpu.utils.metrics import (  # noqa: E402
    ServerMetrics,
    _family_lines,
)


def _fully_armed_text() -> str:
    """Every plane emitting at once — the worst-case assembly the lint
    exists to guard: batcher gauges, cache, overload, utilization,
    quality, and lifecycle series next to the TF-Serving-named families,
    with adversarial model names exercising the escaping path (now
    eleven planes: the ISSUE 13 mesh plane rides the same
    one-lint-covers-all invariant)."""
    from distributed_tf_serving_tpu.cache import ScoreCache
    from distributed_tf_serving_tpu.models import ServableRegistry
    from distributed_tf_serving_tpu.serving import lifecycle as lifecycle_mod
    from distributed_tf_serving_tpu.serving.batcher import BatcherStats
    from distributed_tf_serving_tpu.serving.lifecycle import LifecycleController
    from distributed_tf_serving_tpu.serving.quality import QualityMonitor
    from distributed_tf_serving_tpu.serving.recovery import RecoveryController
    from distributed_tf_serving_tpu.serving.utilization import OccupancyLedger
    from distributed_tf_serving_tpu.utils.config import (
        LifecycleConfig,
        OverloadConfig,
        RecoveryConfig,
    )

    m = ServerMetrics()
    m.observe("Predict", 0.01, ok=True, model='we"ird\\mo\ndel')
    m.observe("Predict", 0.02, ok=False, model="DCN")
    m.observe("REST.Predict", 0.03, ok=True, model="DCN")
    m.observe("PredictStream", 0.04, ok=True, model="DCN")
    stats = BatcherStats()
    stats.batches, stats.requests = 5, 9
    stats.inflight_peak, stats.inflight_window_waits = 3, 2
    # Continuous-batching pipeline snapshot (ISSUE 9): the shape
    # batcher.pipeline_stats() emits with a buffer ring armed and two
    # buckets in flight.
    pipeline = {
        "depth": 4, "inflight_window": 4, "in_flight": 2,
        "dispatch_pending": 1, "per_bucket_in_flight": {256: 1, 1024: 1},
        "inflight_peak": 3, "inflight_window_waits": 2,
        "readback_overlap_fraction": 0.93,
        "buffer_ring": {"reuses": 7, "allocs": 3, "free_buffers": 2},
    }
    cache = ScoreCache()
    # Row-granular tier (ISSUE 14, the twelfth plane): a RowScoreCache
    # snapshot with per-row counters + the rows-executed ratio.
    from distributed_tf_serving_tpu.cache import RowScoreCache

    row_cache = RowScoreCache()
    row_cache.note_rows('we"ird\\mo\ndel', requested=100, executed=37)
    row_cache._count('we"ird\\mo\ndel', "hits", 63)
    row_cache._count('we"ird\\mo\ndel', "misses", 37)
    ctrl = OverloadConfig(enabled=True).build()
    ctrl.bind(4096, 65536)
    ctrl.admit(5, 0, lane="sheddable")
    ledger = OccupancyLedger()
    quality = QualityMonitor(drift_check_interval_s=0.0, min_drift_count=10)
    rng = np.random.RandomState(0)
    quality.observe("DCN", 1, rng.uniform(0.2, 0.5, 200))
    quality.pin_reference(save=False)
    quality.observe("DCN", 2, rng.uniform(0.6, 0.9, 200))
    quality.observe('we"ird\\mo\ndel', 1, rng.rand(20))
    registry = ServableRegistry()
    lifecycle = LifecycleController(
        LifecycleConfig(enabled=True), registry=registry,
        model_name="DCN", quality=quality,
    )
    lifecycle.tick()
    lifecycle_mod.deactivate()  # drop the criticality-scan gate it armed

    class _BatcherSlot:  # the controller only needs somewhere to attach
        recovery = None

    recovery = RecoveryController(
        RecoveryConfig(enabled=True), _BatcherSlot(), clock=lambda: 12.0
    )
    recovery.auto_cycle = False
    # Mesh serving mode (ISSUE 13, the eleventh plane): the shape
    # impl.mesh_stats() emits with the utilization ledger riding along —
    # per-device busy gauges with an adversarial device label.
    mesh = {
        "enabled": True,
        "shape": {"data": 4, "model": 2},
        "devices": ["TFRT_CPU_0", 'cpu"we\\ird\n1'],
        "tensor_parallel": True,
        "executor": {
            "batches": 11, "rows": 520, "pad_batches": 3,
            "data_pad_rows": 6, "placed_servables": 1,
            "layout": {"DCN": "rules:dcn_v2"},
        },
        "per_device": {
            "TFRT_CPU_0": {"busy_fraction": 0.41},
            'cpu"we\\ird\n1': {"busy_fraction": 0.41},
        },
        "occupancy_attribution": "spmd_uniform",
    }
    # Elastic mesh serving (ISSUE 15, the thirteenth plane): the shape
    # impl.elastic_stats() emits mid-switch — drain pending, history
    # ring populated, controller attached.
    elastic = {
        "enabled": True,
        "current_split": "8x1",
        "splits": ["8x1", "4x2", "2x4"],
        "pending_drain_from": "4x2",
        "switches_up": 2,
        "switches_down": 1,
        "switches_refused_drain": 1,
        "last_drain_s": 0.031,
        "per_split": {
            "8x1": {"batches": 9, "rows": 420, "in_flight": 1},
            "4x2": {"batches": 4, "rows": 180, "in_flight": 1},
            "2x4": {"batches": 0, "rows": 0, "in_flight": 0},
        },
        "history": [
            {"t": 1.0, "from": "4x2", "to": "8x1", "direction": "up",
             "reason": "pressure=brownout", "drained_behind": 2,
             "drain_s": 0.031},
        ],
        "controller": {
            "ticks": 40, "pressure": "brownout", "load_ewma": 0.81,
            "occupancy_ewma": 0.77, "up_streak": 0, "down_streak": 0,
            "holds_dwell": 3, "holds_drain": 1, "dwell_s": 5.0,
            "load_up_threshold": 0.75, "load_down_threshold": 0.2,
        },
    }
    # Fleet plane (ISSUE 17, the fourteenth plane): the union shape —
    # router counters + gossip view + coordinator state + a follower
    # block — so every dts_tpu_fleet_* family appears in one exposition
    # (replica and router deployments each emit a subset).
    fleet = {
        "role": "router",
        "router": {
            "requests": 120, "errors": 2, "degraded": 1,
            "gossip_steers": 4, "gossip_rejoins": 1, "watch_updates": 7,
            "healthy_backends": 3, "backends": 3,
        },
        "gossip": {
            "members": {
                "127.0.0.1:8500": {"state": "serving"},
                "127.0.0.1:8501": {"state": "draining"},
                'we"ird\\id\n2': {"state": "quarantined"},
            },
            "member_count": 3,
            "counters": {
                "exchanges_ok": 40, "exchanges_failed": 2,
                "records_accepted": 38, "records_stale": 5,
                "records_expired": 1,
            },
        },
        "rollout": {
            "state": {"seq": 6, "canary_version": 3, "fraction": 0.25,
                      "leader": "127.0.0.1:8500", "blacklist": [2]},
            "counters": {"adoptions": 5, "blacklists": 1, "clears": 1},
        },
        "follower": {"applied_seq": 6, "applies": 5,
                     "blacklists_applied": 1, "last_actions": {}},
        # Fleet observability plane (ISSUE 18): the aggregate + SLO
        # blocks a router with FleetObservabilityPlane armed attaches.
        "agg": {
            "qps": 123.4, "p50_ms": 2.1, "p99_ms": 9.7,
            "requests": 4100, "errors": 3,
            "members": 3, "members_degraded": 1,
            "member_qps": {
                "127.0.0.1:8500": 61.7, "127.0.0.1:8501": 61.7,
                'we"ird\\id\n2': 0.0,
            },
        },
        "slo": {
            "enabled": True,
            "latency_target_ms": 50.0,
            "objectives": {"latency": 0.99, "availability": 0.999},
            "burn": {
                "latency": {"short": 1.2, "long": 0.8},
                "availability": {"short": 0.0, "long": 0.1},
            },
            "budget_remaining": {"latency": 0.2, "availability": 0.9},
            "breached": True,
            "breaches": 2,
        },
    }
    # Cascade plane (ISSUE 19, the fifteenth plane): the shape
    # impl.cascade_stats() emits after mixed traffic — device prunes with
    # one host fallback, a zero-survivor request, and two survivor
    # bucket rungs.
    cascade = {
        "enabled": True,
        "stage1_model": "stage1",
        "requests": 55,
        "fallbacks": 1,
        "stage1_failures": 1,
        "host_prunes": 2,
        "zero_survivor_requests": 1,
        "rows_requested": 56320,
        "rows_ranked": 14080,
        "survivor_rows": 14080,
        "pruned_rows": 42240,
        "survivor_fraction_observed": 0.25,
        "rank_fraction": 0.25,
        "stage1_seconds_total": 0.9,
        "prune_seconds_total": 0.05,
        "stage2_seconds_total": 1.4,
        "survivor_buckets": {"256": 50, "1024": 5},
    }
    # Integrity plane (ISSUE 20, the sixteenth plane): the shape
    # impl.integrity_stats() emits mid-incident — wire counters live,
    # a screen window partially filled, one shadow mismatch escalated,
    # the replica currently suspect.
    integrity = {
        "enabled": True,
        "wire": {
            "inputs_verified": 300, "inputs_rejected": 2,
            "responses_stamped": 298,
        },
        "screen": {"trips": 4, "window_trips": 1},
        "shadow": {
            "fraction": 0.02, "batches": 9, "mismatches": 1,
            "audits_requested": 3, "audits_run": 3,
        },
        "escalations": 1,
        "suspect": True,
        "suspect_reason": "shadow mismatch",
    }
    # The router side of the plane rides the fleet block: two-replica
    # audit counters + suspect-gossip steers.
    fleet["router"].update({
        "suspect_steers": 2, "integrity_audits": 12,
        "audit_disagreements": 1, "audit_suspects_marked": 1,
    })
    return m.prometheus_text(
        stats,
        cache=cache.snapshot(),
        row_cache=row_cache.snapshot(),
        overload=ctrl.snapshot(),
        utilization=ledger.snapshot(),
        quality=quality.snapshot(),
        lifecycle=lifecycle.snapshot(),
        pipeline=pipeline,
        recovery=recovery.snapshot(),
        mesh=mesh,
        elastic=elastic,
        fleet=fleet,
        cascade=cascade,
        integrity=integrity,
    )


def test_fully_armed_snapshot_passes_lint():
    text = _fully_armed_text()
    assert lint_text(text) == []
    # The assembly really did include every plane.
    for marker in (
        ":tensorflow:serving:request_count", "dts_tpu_batcher_",
        "dts_tpu_cache_", "dts_tpu_cache_row_hits_total",
        "dts_tpu_cache_rows_executed_total",
        "dts_tpu_cache_rows_executed_fraction",
        "dts_tpu_overload_", "dts_tpu_utilization_",
        "dts_tpu_quality_", "dts_tpu_lifecycle_", "dts_tpu_pipeline_",
        "dts_tpu_pipeline_bucket_in_flight", "buffer_ring",
        "dts_tpu_recovery_",
        "dts_tpu_mesh_", "dts_tpu_mesh_device_busy_fraction",
        "dts_tpu_elastic_", "dts_tpu_elastic_switches_total",
        "dts_tpu_elastic_split_in_flight",
        "dts_tpu_fleet_", "dts_tpu_fleet_members_by_state",
        "dts_tpu_fleet_gossip_exchanges_total",
        "dts_tpu_fleet_rollout_seq",
        "dts_tpu_fleet_router_requests_total",
        "dts_tpu_fleet_agg_qps", "dts_tpu_fleet_agg_latency_ms",
        "dts_tpu_fleet_agg_member_qps",
        "dts_tpu_fleet_agg_members_degraded",
        "dts_tpu_slo_burn_rate", "dts_tpu_slo_budget_remaining",
        "dts_tpu_slo_breached", "dts_tpu_slo_breaches_total",
        "dts_tpu_cascade_", "dts_tpu_cascade_rows_total",
        "dts_tpu_cascade_stage_seconds_total",
        "dts_tpu_cascade_survivor_bucket_total",
        "dts_tpu_cascade_rank_fraction",
        "dts_tpu_integrity_", "dts_tpu_integrity_wire_inputs_rejected_total",
        "dts_tpu_integrity_screen_trips_total",
        "dts_tpu_integrity_shadow_mismatches_total",
        "dts_tpu_integrity_suspect",
        "dts_tpu_fleet_router_integrity_audits_total",
    ):
        assert marker in text


def test_every_family_has_help_and_type():
    text = _fully_armed_text()
    helps = {
        ln.split(" ", 3)[2] for ln in text.splitlines()
        if ln.startswith("# HELP")
    }
    types = {
        ln.split(" ", 3)[2] for ln in text.splitlines()
        if ln.startswith("# TYPE")
    }
    assert helps == types and len(types) > 20


def test_lint_catches_duplicate_family():
    lines: list = []
    _family_lines(lines, "dup_metric", "counter")
    lines.append("dup_metric 1")
    _family_lines(lines, "dup_metric", "counter")
    errs = lint_text("\n".join(lines) + "\n")
    assert any("declared twice" in e for e in errs)


def test_lint_catches_missing_type_and_help():
    errs = lint_text("orphan_metric 1\n")
    assert any("no preceding # TYPE" in e for e in errs)
    errs = lint_text("# TYPE helpless counter\nhelpless 1\n")
    assert any("no # HELP" in e for e in errs)


def test_lint_catches_duplicate_series():
    lines: list = []
    _family_lines(lines, "m", "gauge")
    lines.append('m{a="x"} 1')
    lines.append('m{a="x"} 2')
    errs = lint_text("\n".join(lines) + "\n")
    assert any("duplicate series" in e for e in errs)
    # Same name, different label set: legal.
    lines = []
    _family_lines(lines, "m", "gauge")
    lines.append('m{a="x"} 1')
    lines.append('m{a="y"} 2')
    assert lint_text("\n".join(lines) + "\n") == []


def test_lint_catches_interleaved_families():
    lines: list = []
    _family_lines(lines, "a", "gauge")
    _family_lines(lines, "b", "gauge")
    lines += ["a 1", "b 2", "a 3"]
    errs = lint_text("\n".join(lines) + "\n")
    assert any("not contiguous" in e for e in errs)


def test_lint_catches_unescaped_label_and_bad_value():
    lines: list = []
    _family_lines(lines, "m", "gauge")
    lines.append('m{a="un"escaped"} 1')
    errs = lint_text("\n".join(lines) + "\n")
    assert errs, "unescaped quote must fail the line grammar"
    lines = []
    _family_lines(lines, "m", "gauge")
    lines.append('m{a="x"} not-a-number')
    errs = lint_text("\n".join(lines) + "\n")
    assert any("not a number" in e for e in errs)


def test_lint_accepts_histogram_suffixes_and_inf():
    lines: list = []
    _family_lines(lines, "h", "histogram")
    lines += [
        'h_bucket{le="1"} 1', 'h_bucket{le="+Inf"} 2', "h_sum 1.5", "h_count 2",
    ]
    assert lint_text("\n".join(lines) + "\n") == []
    # The same suffixes WITHOUT a declared histogram family fail.
    errs = lint_text('x_bucket{le="+Inf"} 2\n')
    assert any("no preceding # TYPE" in e for e in errs)
