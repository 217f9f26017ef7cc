"""In-server multi-stage ranking cascade (ISSUE 19).

The reference system's whole client exists to shard a large candidate
set, score it with ONE expensive model, and sort/merge the results. The
cascade turns that into a server-side pipeline stage: a cheap first-stage
servable scores the full candidate batch, a jitted on-device prune keeps
the top-`survivor_k` rows (only the survivor (score, index) pairs plus
the wire-dtype stage-1 vector cross the D2H link — ops/transfer.py
cascade_prune_device), and the full DCN ranks only the survivors in the
smaller bucket rung. Stage-2 scores scatter back to their original
candidate positions, non-survivors keep their stage-1 scores, and the
response carries per-row provenance (`cascade_stage`: 1 = stage-1 score,
2 = stage-2 ranked) so callers can tell a ranked head from a pruned tail.

Composition is the point, not an afterthought:

- BOTH stages are ordinary DynamicBatcher submits of ordinary servables,
  so the score cache, row cache, overload lanes, deadline propagation,
  tracing, and recovery planes apply per stage for free. The stage-1
  prune submit salts its whole-request cache key (mode+k folded into the
  feature digest, cache/digest.py) so a prune result can never answer a
  full-vector request; the row plane keys on the model NAME, so stage-1
  rows can never poison stage-2 keys structurally.
- The first-stage model is a NORMAL servable published under its own
  model name (interop/export.py publish_version + train/checkpoint
  save_servable): the version watcher hot-swaps it, the lifecycle plane
  can canary it, and a mid-swap stale resolution simply falls back to a
  full stage-2 pass — no request fails because retrieval moved.
- Deadlines recompute between stages: stage 2 submits with the budget
  that REMAINS after stage 1, never the original allotment.
- Refused compositions (serving/server.py build_stack): `output_top_k`
  (its wire replaces the score vector the scatter needs) and [mesh]/
  [elastic] (the sharded run_fn has no prune entry). The fleet router
  forwards cascade traffic unchanged — the cascade is invisible at the
  RPC boundary except for the provenance output.

Per-request spans: `cascade.stage1` (submit + wait), `cascade.prune`
(host finalize: threshold filter + survivor gather), `cascade.stage2`.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..utils.tracing import request_trace

# Provenance output name (encoded into the response alongside the score
# tensor, the int8-wire sidecar precedent — not part of the signature).
STAGE_OUTPUT = "cascade_stage"
STAGE1 = 1  # row kept its stage-1 score (pruned before ranking)
STAGE2 = 2  # row was ranked by the full model


class CascadeStats:
    """Counter block behind /cascadez and dts_tpu_cascade_*. Lock-guarded:
    RPC handler threads from both transports bump it concurrently."""

    _FIELDS = (
        "requests", "fallbacks", "stage1_failures", "rows_requested",
        "rows_ranked", "pruned_rows", "survivor_rows",
        "zero_survivor_requests", "host_prunes",
    )

    def __init__(self):
        self._lock = threading.Lock()
        for f in self._FIELDS:
            setattr(self, f, 0)
        self.stage1_s = 0.0
        self.prune_s = 0.0
        self.stage2_s = 0.0
        # survivor-count histogram keyed by the bucket rung stage 2 ran
        # in — the capacity-planning view (which rungs the cascade feeds).
        self.survivor_buckets: dict[int, int] = {}


class CascadeOrchestrator:
    """Two-stage retrieval->rank pipeline above the DynamicBatcher.

    Consulted by PredictionServiceImpl per request (one attribute read
    when the plane is off). A request is eligible when its output filter
    pinned exactly the score output — the same gate that arms top-k
    compaction: the cascade's scatter needs a score VECTOR to fill, and
    mixed-stage values for any other output would be meaningless — and it
    carries at least `min_candidates` rows.
    """

    def __init__(
        self,
        registry,
        batcher,
        stage1_model: str = "stage1",
        survivor_k: int = 0,
        survivor_fraction: float = 0.25,
        score_threshold: float = 0.0,
        min_candidates: int = 8,
    ):
        self.registry = registry
        self.batcher = batcher
        self.stage1_model = stage1_model
        self.survivor_k = survivor_k
        self.survivor_fraction = survivor_fraction
        self.score_threshold = score_threshold
        self.min_candidates = min_candidates
        self.stats = CascadeStats()

    # ------------------------------------------------------- eligibility

    def eligible(self, servable, fetch_keys, n: int) -> bool:
        """Cheap per-request gate, called on the RPC handler thread."""
        return (
            n >= self.min_candidates
            and servable.name != self.stage1_model
            and fetch_keys is not None
            and len(fetch_keys) == 1
            and fetch_keys[0] == servable.model.score_output
            and self.plan_k(n) < n
        )

    def plan_k(self, n: int) -> int:
        """Survivor count for an n-candidate request: the fixed
        survivor_k when set, else the fraction of n (at least 1)."""
        if self.survivor_k > 0:
            return self.survivor_k
        return max(1, int(n * self.survivor_fraction))

    # ---------------------------------------------------------- pipeline

    def _stage1_servable(self):
        """Latest stage-1 version, or None (not yet published, or swapped
        out mid-rollout) — the caller falls back to a full stage-2 pass."""
        try:
            return self.registry.resolve(self.stage1_model, None)
        except Exception:  # noqa: BLE001 — NOT_FOUND during rollout
            return None

    def _finalize_prune(self, s1: dict, stage1, n: int, k: int):
        """Host tail of the prune: accept either the on-device prune
        result (survivor pairs + stage-1 vector) or a full score vector
        (the batcher's arming fallback — x64 model, custom run_fn), apply
        the optional score threshold, and return (survivor_indices,
        stage1_scores as a writable f32[n])."""
        if "survivor_indices" in s1:
            idx = np.asarray(s1["survivor_indices"])[:k]
            vals = np.asarray(s1["survivor_scores"], np.float32)[:k]
            full = np.array(s1["stage1_scores"], np.float32, copy=True)
        else:
            with self.stats._lock:
                self.stats.host_prunes += 1
            full = np.array(
                s1[stage1.model.score_output], np.float32, copy=True
            ).reshape(-1)
            # argpartition, then order the head by score descending so the
            # threshold filter below sees the same sorted view the device
            # top_k returns.
            idx = np.argpartition(-full, k - 1)[:k]
            idx = idx[np.argsort(-full[idx], kind="stable")]
            vals = full[idx]
        if self.score_threshold > 0.0:
            keep = vals >= self.score_threshold
            idx = idx[keep]
        return idx.astype(np.int64), full

    def _scatter(self, final: np.ndarray, idx, stage2_scores) -> dict:
        provenance = np.full(final.shape[0], STAGE1, np.int32)
        if len(idx):
            final[idx] = np.asarray(stage2_scores, np.float32).reshape(-1)
            provenance[idx] = STAGE2
        return provenance

    def _note(self, n: int, idx, bucket: int, t1: float, tp: float,
              t2: float) -> None:
        s = self.stats
        with s._lock:
            s.requests += 1
            s.rows_requested += n
            s.rows_ranked += len(idx)
            s.survivor_rows += len(idx)
            s.pruned_rows += n - len(idx)
            if len(idx) == 0:
                s.zero_survivor_requests += 1
            else:
                s.survivor_buckets[bucket] = (
                    s.survivor_buckets.get(bucket, 0) + 1
                )
            s.stage1_s += t1
            s.prune_s += tp
            s.stage2_s += t2

    def _note_fallback(self, n: int, stage1_failed: bool) -> None:
        s = self.stats
        with s._lock:
            s.requests += 1
            s.fallbacks += 1
            s.rows_requested += n
            s.rows_ranked += n
            if stage1_failed:
                s.stage1_failures += 1

    def _bucket_of(self, rows: int) -> int:
        from .batcher import bucket_for

        try:
            return bucket_for(rows, self.batcher.buckets)
        except Exception:  # noqa: BLE001 — accounting only
            return rows

    def run(self, impl, servable, arrays, fetch_keys, deadline_t,
            criticality) -> dict:
        """Synchronous cascade (thread-per-RPC transports). `impl` is the
        PredictionServiceImpl whose _run/_budget_left this rides — its
        error translation and degraded-marker forwarding apply per stage."""
        score_key = servable.model.score_output
        n = next(iter(arrays.values())).shape[0]
        k = self.plan_k(n)
        stage1 = self._stage1_servable()
        if stage1 is None:
            return self._full_fallback(
                impl, servable, arrays, fetch_keys, deadline_t,
                criticality, n, score_key, stage1_failed=False,
            )
        t0 = time.perf_counter()
        try:
            with request_trace.span("cascade.stage1"):
                s1 = impl._run(
                    stage1, arrays,
                    output_keys=(stage1.model.score_output,),
                    deadline_s=impl._budget_left(deadline_t),
                    criticality=criticality, prune_k=k,
                )
        except Exception:  # noqa: BLE001 — stage-1 must never fail the RPC
            # Mid-rollout unload, stage-1 shape mismatch, stage-1 device
            # failure: the contract is "retrieval trouble degrades to a
            # full ranking pass", so the request still succeeds.
            return self._full_fallback(
                impl, servable, arrays, fetch_keys, deadline_t,
                criticality, n, score_key, stage1_failed=True,
            )
        t1 = time.perf_counter()
        with request_trace.span("cascade.prune"):
            idx, final = self._finalize_prune(s1, stage1, n, k)
            surv = {key: v[idx] for key, v in arrays.items()} if len(idx) \
                else None
        tp = time.perf_counter()
        if surv is None:
            self._note(n, idx, 0, t1 - t0, tp - t1, 0.0)
            return {score_key: final, STAGE_OUTPUT: self._scatter(final, idx, [])}
        with request_trace.span("cascade.stage2"):
            out2 = impl._run(
                servable, surv, output_keys=fetch_keys,
                deadline_s=impl._budget_left(deadline_t),
                criticality=criticality,
            )
        t2 = time.perf_counter()
        provenance = self._scatter(final, idx, out2[score_key])
        self._note(n, idx, self._bucket_of(len(idx)), t1 - t0, tp - t1,
                   t2 - tp)
        return {score_key: final, STAGE_OUTPUT: provenance}

    async def run_async(self, impl, servable, arrays, fetch_keys,
                        deadline_t, criticality) -> dict:
        """run() for the REST gateway's event loop: identical semantics,
        stage waits are awaited instead of blocking the loop's thread."""
        score_key = servable.model.score_output
        n = next(iter(arrays.values())).shape[0]
        k = self.plan_k(n)
        stage1 = self._stage1_servable()
        if stage1 is None:
            out = await impl._run_async(
                servable, arrays, output_keys=fetch_keys,
                deadline_s=impl._budget_left(deadline_t),
                criticality=criticality,
            )
            self._note_fallback(n, stage1_failed=False)
            return self._with_full_provenance(out, score_key, n)
        t0 = time.perf_counter()
        try:
            with request_trace.span("cascade.stage1"):
                s1 = await impl._run_async(
                    stage1, arrays,
                    output_keys=(stage1.model.score_output,),
                    deadline_s=impl._budget_left(deadline_t),
                    criticality=criticality, prune_k=k,
                )
        except Exception:  # noqa: BLE001 — stage-1 must never fail the RPC
            out = await impl._run_async(
                servable, arrays, output_keys=fetch_keys,
                deadline_s=impl._budget_left(deadline_t),
                criticality=criticality,
            )
            self._note_fallback(n, stage1_failed=True)
            return self._with_full_provenance(out, score_key, n)
        t1 = time.perf_counter()
        with request_trace.span("cascade.prune"):
            idx, final = self._finalize_prune(s1, stage1, n, k)
            surv = {key: v[idx] for key, v in arrays.items()} if len(idx) \
                else None
        tp = time.perf_counter()
        if surv is None:
            self._note(n, idx, 0, t1 - t0, tp - t1, 0.0)
            return {score_key: final, STAGE_OUTPUT: self._scatter(final, idx, [])}
        with request_trace.span("cascade.stage2"):
            out2 = await impl._run_async(
                servable, surv, output_keys=fetch_keys,
                deadline_s=impl._budget_left(deadline_t),
                criticality=criticality,
            )
        t2 = time.perf_counter()
        provenance = self._scatter(final, idx, out2[score_key])
        self._note(n, idx, self._bucket_of(len(idx)), t1 - t0, tp - t1,
                   t2 - tp)
        return {score_key: final, STAGE_OUTPUT: provenance}

    def _full_fallback(self, impl, servable, arrays, fetch_keys, deadline_t,
                       criticality, n, score_key, stage1_failed):
        """Full stage-2 pass (sync path): every row ranked, provenance
        all STAGE2 — the response a cascade-off server would have sent,
        plus honest provenance."""
        out = impl._run(
            servable, arrays, output_keys=fetch_keys,
            deadline_s=impl._budget_left(deadline_t),
            criticality=criticality,
        )
        self._note_fallback(n, stage1_failed)
        return self._with_full_provenance(out, score_key, n)

    @staticmethod
    def _with_full_provenance(out: dict, score_key: str, n: int) -> dict:
        out = dict(out)
        out[STAGE_OUTPUT] = np.full(n, STAGE2, np.int32)
        return out

    # ------------------------------------------------------------- stats

    def snapshot(self) -> dict:
        """/cascadez + /monitoring?section=cascade + dts_tpu_cascade_*."""
        s = self.stats
        with s._lock:
            req = s.requests
            rows_req = s.rows_requested
            snap = {
                "stage1_model": self.stage1_model,
                "survivor_k": self.survivor_k,
                "survivor_fraction": self.survivor_fraction,
                "score_threshold": self.score_threshold,
                "min_candidates": self.min_candidates,
                "requests": req,
                "fallbacks": s.fallbacks,
                "stage1_failures": s.stage1_failures,
                "host_prunes": s.host_prunes,
                "rows_requested": rows_req,
                "rows_ranked": s.rows_ranked,
                "pruned_rows": s.pruned_rows,
                "survivor_rows": s.survivor_rows,
                "zero_survivor_requests": s.zero_survivor_requests,
                "survivor_fraction_observed": (
                    s.survivor_rows / rows_req if rows_req else 0.0
                ),
                "rank_fraction": (
                    s.rows_ranked / rows_req if rows_req else 0.0
                ),
                "stage1_seconds_total": s.stage1_s,
                "prune_seconds_total": s.prune_s,
                "stage2_seconds_total": s.stage2_s,
                "survivor_buckets": dict(
                    sorted(s.survivor_buckets.items())
                ),
            }
        return snap


def publish_stage1(base_dir: str, servable, kind: str) -> tuple[int, str]:
    """Publish a stage-1 servable as a normal versioned model: write a
    native checkpoint (train/checkpoint.save_servable) into the next
    numeric version slot via the atomic interop/export.publish_version
    rename, so a VersionWatcher on `base_dir` picks it up exactly like
    any other rollout (and the cascade's resolve sees the swap)."""
    from ..interop.export import publish_version
    from ..train.checkpoint import save_servable

    return publish_version(
        base_dir, lambda tmp: save_servable(tmp, servable, kind)
    )
