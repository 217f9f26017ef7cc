"""Per-bucket kernel autotune harness (ISSUE 12) — the machinery behind
one invariant: **no execution variant serves live traffic unless it
measured faster than the baseline on THIS device at THIS bucket and passed
the accuracy gates.**

Variants per (servable, bucket), all minted through the batcher's OWN
jitted entries so measurement and serving share compiled executables:

  - baseline:   XLA, float params (today's path — always available)
  - xla_int8:   XLA, ops/quantize.py int8 weight-only params
  - pallas:     ops/cross_kernel.py fused cross+MLP+head kernel, float
  - pallas_int8: the fused kernel with int8 weight operands

Gates (config, [kernels] section): measured speedup >= min_speedup AND
max |Δscore| vs the f32 baseline <= max_abs_delta AND — when a labeled
eval set is supplied (the CI smoke, tools/check_kernel_smoke.py) —
|AUC_f32 - AUC_variant| <= auc_margin. A variant that errors or fails a
gate is recorded with its reason and left DISABLED — except a Pallas
variant the accelerator's compiler refuses, which raises
KernelLoweringError and stops start-up; in measure_only mode everything is
recorded and nothing is enabled (the CI smoke's contract). The per-bucket
decision picks the fastest enabled variant.

The decision table persists to artifacts/kernel_autotune.json keyed by
(model, version, PARAMS DIGEST, device kind, gate fingerprint) so a
restart adopts its own prior measurements instead of re-tuning, while a
version hot-swap or a same-version retrain misses the key by
construction; live decisions are additionally identity-guarded per tuned
Servable object, so a new canary never inherits the old version's
enablement and the stable version keeps its measured win across registry
events.

Also owns the module-level gate for the int8 score RESPONSE wire (the
x-dts-score-wire metadata opt-in — servers scan request metadata only
while a kernels plane armed it; the overload/lifecycle `active()`
precedent).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
import weakref

import numpy as np

log = logging.getLogger("dts_tpu.kernels")

# Request-metadata key for the int8 score response wire (client opt-in).
SCORE_WIRE_KEY = "x-dts-score-wire"

_WIRE_ACTIVE = False


def wire_active() -> bool:
    """True while a kernels plane with int8_score_wire is armed — the
    transport adapters scan request metadata only then (two module reads
    per RPC otherwise zero)."""
    return _WIRE_ACTIVE


def set_wire_active(on: bool) -> None:
    global _WIRE_ACTIVE
    _WIRE_ACTIVE = bool(on)


# Variant names (stable table/JSON vocabulary).
BASELINE = "xla_f32"
XLA_INT8 = "xla_int8"
PALLAS_F32 = "pallas_f32"
PALLAS_INT8 = "pallas_int8"
VARIANTS = (XLA_INT8, PALLAS_F32, PALLAS_INT8)

_VARIANT_FLAGS = {
    BASELINE: (False, False),
    XLA_INT8: (True, False),
    PALLAS_F32: (False, True),
    PALLAS_INT8: (True, True),
}


def _device_kind() -> str:
    """Keys the persisted decision table: a device jax cannot name raises —
    measurements must never be filed under (or adopted from) "unknown"."""
    import jax

    return jax.devices()[0].device_kind


class KernelLoweringError(RuntimeError):
    """A Pallas variant the operator asked for ([kernels] pallas = true) did
    not compile on this accelerator. Unlike a lost speed gate — a row in the
    table — this stops start-up with the compiler's message: a kernel that
    cannot run on the only device it is for must not hide behind XLA."""


def params_digest(params) -> str:
    """Cheap, deterministic digest of a param tree's WEIGHTS — the
    persisted decision table's staleness guard: a version number alone
    does not identify the weights (bench always serves v1; a checkpoint
    can be retrained in place), and gates measured against different
    weights must never be adopted. Strided sampling keeps it O(leaves),
    not O(bytes): path + shape + dtype + head/tail bytes per leaf."""
    import hashlib

    h = hashlib.blake2b(digest_size=16)

    def walk(node, path):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(node[k], f"{path}/{k}")
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(v, f"{path}[{i}]")
        else:
            arr = np.asarray(node)
            h.update(path.encode())
            h.update(str(arr.shape).encode())
            h.update(str(arr.dtype).encode())
            raw = np.ascontiguousarray(arr).view(np.uint8).ravel()
            h.update(raw[:64].tobytes())
            h.update(raw[-64:].tobytes())

    walk(params, "")
    return h.hexdigest()


class KernelManager:
    """The per-bucket variant router + autotune harness the batcher holds
    as `batcher.kernels` (None when the plane is off — one attribute read
    per dispatch, the tracing/cache/overload precedent).

    Fast path: decision(servable, bucket) is a dict probe under no lock
    (the decisions dict is replaced atomically, never mutated in place).
    """

    def __init__(self, config, clock=time.perf_counter):
        self.config = config
        self._clock = clock
        self._lock = threading.Lock()
        # (model_name, version) -> (weakref-to-the-tuned-Servable,
        # {bucket: (quantized, pallas)}). The weakref is the staleness
        # guard: decision() serves an entry only to the EXACT servable
        # object it was tuned for, so a same-version reload (new Servable,
        # possibly new weights) or a recycled object address can never
        # inherit another generation's enablement — while the stable
        # version keeps its measured win across unrelated registry events.
        self._decisions: dict[tuple[str, int], tuple] = {}
        # (model_name, version) -> the full measured table (snapshot/bench).
        self._tables: dict[tuple[str, int], dict] = {}
        self._qparams: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
        # servable -> (params identity, {quantized: apply_fn})
        self._pallas: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
        self.autotunes = 0
        self.table_saves = 0
        self.table_reuses = 0
        self.quantized_batches = 0
        self.pallas_batches = 0
        # The batcher whose entries adopted-enablement warm compiles run
        # through (set by prepare()/autotune(); reuse happens inside them).
        self._warm_batcher = None

    # ------------------------------------------------------------ fast path

    def decision(self, servable, bucket: int) -> tuple[bool, bool] | None:
        """(quantized, pallas) for this (servable, bucket), or None for
        the baseline. The entry answers only for the exact Servable it
        was tuned for (see _decisions) — anything else is baseline.
        Counters ride here (plain int += under the GIL — telemetry, not
        accounting)."""
        entry = self._decisions.get((servable.name, servable.version))
        if entry is None or entry[0]() is not servable:
            return None
        dec = entry[1].get(int(bucket))
        if dec is None:
            return None
        if dec[0]:
            self.quantized_batches += 1
        if dec[1]:
            self.pallas_batches += 1
        return dec

    def params_for(self, servable, quantized: bool):
        """The servable's params in the requested precision; the int8
        tree is minted once per servable (post-training, at first need)
        and cached under a weak key so an unloaded servable frees it."""
        if not quantized:
            return servable.params
        with self._lock:
            entry = self._qparams.get(servable)
            if entry is None or entry[0] is not servable.params:
                from .quantize import quantize_params

                entry = (servable.params, quantize_params(servable.params))
                self._qparams[servable] = entry
        return entry[1]

    def pallas_apply_for(self, servable, quantized: bool):
        """The fused-serving apply callable for this servable (built once
        per (servable, precision); rebuilt when params are swapped).
        Raises for ineligible param trees — eligibility is checked before
        a decision ever routes here (autotune gates on it)."""
        import jax

        from .cross_kernel import build_fused_serve

        # Resolve the (possibly quantized) params BEFORE taking the lock:
        # params_for acquires the same non-reentrant lock, and the build
        # below is idempotent — a racing double-build wastes one trace,
        # a nested acquire would deadlock the dispatch thread forever.
        params = (
            self.params_for(servable, True) if quantized else servable.params
        )
        with self._lock:
            entry = self._pallas.get(servable)
            if entry is None or entry[0] is not servable.params:
                entry = (servable.params, {})
                self._pallas[servable] = entry
            cache = entry[1]
            fn = cache.get(quantized)
            if fn is None:
                fn = cache[quantized] = build_fused_serve(
                    params, servable.model.config,
                    interpret=jax.default_backend() == "cpu",
                )
        return fn

    # ------------------------------------------------------------- autotune

    def _pallas_eligible(self, servable, arrays) -> tuple[bool, str]:
        from .cross_kernel import serve_fits_vmem, serve_params_supported

        model = servable.model
        cfg = model.config
        if model.needs_x64 or not model.folds_ids_on_host:
            return False, "model input contract (x64 / raw ids)"
        if set(arrays) != {"feat_ids", "feat_wts"}:
            return False, "inputs beyond feat_ids/feat_wts"
        if not serve_params_supported(servable.params):
            return False, "param tree is not dcn_v2-shaped"
        mlp_dims = tuple(
            p.get("qw", p.get("w")).shape[1] for p in servable.params["mlp"]
        )
        if not serve_fits_vmem(
            cfg.num_fields * cfg.embed_dim, len(servable.params["cross"]),
            mlp_dims, cfg.cdtype,
        ):
            return False, "over VMEM budget"
        return True, ""

    @staticmethod
    def _tune_arrays(batcher, servable, bucket: int, seed: int = 7) -> dict:
        """Representative random batch: warmup_arrays' geometry with live
        value distributions (random gather addresses defeat the content
        cache's trivial all-zero hit and exercise real HBM reads)."""
        rng = np.random.RandomState(seed + bucket)
        arrays = {}
        for k, v in batcher.warmup_arrays(servable, bucket).items():
            if np.issubdtype(v.dtype, np.integer):
                arrays[k] = rng.randint(0, 1 << 40, size=v.shape).astype(v.dtype)
            else:
                arrays[k] = rng.rand(*v.shape).astype(v.dtype)
        return arrays

    def _scores_of(self, batcher, servable, arrays, override) -> np.ndarray:
        from .transfer import restore_outputs_host

        score_key = servable.model.score_output
        out = batcher._execute(
            servable, dict(arrays), out_keys=(score_key,),
            _kernel_override=override,
        )
        host = restore_outputs_host({k: np.asarray(v) for k, v in out.items()})
        return np.asarray(host[score_key], np.float32)

    def _time_variant(self, batcher, servable, arrays, override,
                      iters: int) -> float:
        import jax

        score_key = servable.model.score_output
        run = lambda: batcher._execute(  # noqa: E731
            servable, dict(arrays), out_keys=(score_key,),
            _kernel_override=override,
        )
        jax.block_until_ready(run())  # compile + warm
        best = float("inf")
        for _ in range(max(iters, 1)):
            t0 = self._clock()
            jax.block_until_ready(run())
            best = min(best, self._clock() - t0)
        return best

    def _auc_of(self, batcher, servable, eval_data, override):
        """Windowed-eval AUC of one variant over the supplied labeled
        arrays (padded into the nearest bucket; scores sliced back)."""
        from ..serving.batcher import bucket_for
        from ..train.data import auc as exact_auc

        arrays, labels = eval_data
        n = int(next(iter(arrays.values())).shape[0])
        top = int(batcher.buckets[-1])
        if n > top:
            # Clamp to the ladder: ranking quality over the first
            # bucket's worth of held-out rows is the same statistic.
            arrays = {k: v[:top] for k, v in arrays.items()}
            labels = np.asarray(labels)[:top]
            n = top
        bucket = bucket_for(n, batcher.buckets)
        padded = {}
        for k, v in arrays.items():
            buf = np.zeros((bucket,) + v.shape[1:], v.dtype)
            buf[:n] = v
            padded[k] = buf
        scores = self._scores_of(batcher, servable, padded, override)[:n]
        return float(exact_auc(np.asarray(labels, np.float64), scores))

    def prepare(self, batcher, servable, buckets=None, eval_data=None) -> None:
        """Load-time entry: adopt a persisted decision table when one
        matches exactly, else run the measurement harness (config
        permitting — autotune=false serves the baseline rather than
        measuring at every restart)."""
        buckets = tuple(
            int(b) for b in (buckets or self.config.autotune_buckets or batcher.buckets)
        )
        self._warm_batcher = batcher  # for adopted-enablement warm compiles
        if self._try_reuse(servable, buckets) is not None:
            return
        if self.config.autotune:
            self.autotune(batcher, servable, buckets, eval_data=eval_data)

    def autotune(self, batcher, servable, buckets=None, eval_data=None,
                 force: bool = False) -> dict:
        """Measure every candidate variant per bucket, gate, decide,
        persist. Returns this servable's table block (also served via
        snapshot()/ /monitoring / bench). `eval_data` = (arrays, labels)
        arms the AUC gate; without it the gate records "skipped" and the
        decision rests on speedup + max|Δscore| alone. `force` skips the
        persisted-table adoption and ALWAYS measures — the bench A/B's
        contract is fresh numbers per round, not round 1's replayed."""
        import jax

        cfg = self.config
        self.autotunes += 1
        key = (servable.name, servable.version)
        buckets = tuple(
            int(b) for b in (buckets or cfg.autotune_buckets or batcher.buckets)
        )
        self._warm_batcher = batcher
        if not force:
            reused = self._try_reuse(servable, buckets)
            if reused is not None:
                return reused
        on_cpu = jax.default_backend() == "cpu"
        force_pallas = os.environ.get("DTS_KERNELS_FORCE_PALLAS") == "1"
        iters = int(cfg.measure_iters) or (4 if on_cpu else 30)
        sample = self._tune_arrays(batcher, servable, buckets[0])
        pallas_ok, pallas_why = self._pallas_eligible(servable, sample)
        if pallas_ok and on_cpu and not force_pallas:
            pallas_ok, pallas_why = False, (
                "cpu backend runs the kernel in interpret mode — timing it "
                "would be meaningless (and slow); gates run on real devices"
            )
        candidates = []
        if cfg.quantize:
            candidates.append(XLA_INT8)
        if cfg.pallas and pallas_ok:
            candidates.extend([PALLAS_F32] + ([PALLAS_INT8] if cfg.quantize else []))

        # AUC gate: one evaluation per variant KIND (rank quality is
        # bucket-independent), against the f32 baseline's AUC.
        aucs: dict[str, float | None] = {BASELINE: None}
        auc_errors: dict[str, str] = {}
        if eval_data is not None:
            try:
                aucs[BASELINE] = self._auc_of(
                    batcher, servable, eval_data, _VARIANT_FLAGS[BASELINE]
                )
            except Exception as exc:  # noqa: BLE001 — record, keep tuning
                auc_errors[BASELINE] = f"{type(exc).__name__}: {exc}"[:200]
            for name in candidates:
                try:
                    aucs[name] = self._auc_of(
                        batcher, servable, eval_data, _VARIANT_FLAGS[name]
                    )
                except Exception as exc:  # noqa: BLE001
                    self._raise_if_lowering(name, servable, on_cpu, exc)
                    auc_errors[name] = f"{type(exc).__name__}: {exc}"[:200]

        table: dict = {
            "model": servable.name,
            "version": servable.version,
            "params_digest": params_digest(servable.params),
            "device": _device_kind(),
            "measure_iters": iters,
            "measure_only": bool(cfg.measure_only),
            "gates": {
                "min_speedup": cfg.min_speedup,
                "max_abs_delta": cfg.max_abs_delta,
                "auc_margin": cfg.auc_margin,
                "auc_evaluated": eval_data is not None,
            },
            "pallas_eligible": pallas_ok,
            **({"pallas_ineligible_reason": pallas_why} if not pallas_ok else {}),
            "auc": {
                k: (round(v, 4) if v is not None else None)
                for k, v in aucs.items()
            },
            **({"auc_errors": auc_errors} if auc_errors else {}),
            "buckets": {},
        }
        decisions: dict[int, tuple[bool, bool]] = {}
        for bucket in buckets:
            arrays = self._tune_arrays(batcher, servable, bucket)
            row: dict = {}
            try:
                base_scores = self._scores_of(
                    batcher, servable, arrays, _VARIANT_FLAGS[BASELINE]
                )
                base_t = self._time_variant(
                    batcher, servable, arrays, _VARIANT_FLAGS[BASELINE], iters
                )
            except Exception as exc:  # noqa: BLE001 — baseline broken: skip bucket
                table["buckets"][str(bucket)] = {
                    "error": f"{type(exc).__name__}: {exc}"[:300]
                }
                continue
            row[BASELINE] = {"step_us": round(base_t * 1e6, 1)}
            best: tuple[float, str] | None = None
            for name in candidates:
                flags = _VARIANT_FLAGS[name]
                entry: dict = {}
                try:
                    scores = self._scores_of(batcher, servable, arrays, flags)
                    t = self._time_variant(batcher, servable, arrays, flags, iters)
                    entry["step_us"] = round(t * 1e6, 1)
                    entry["speedup"] = round(base_t / t, 3) if t > 0 else None
                    entry["max_abs_delta"] = round(
                        float(np.max(np.abs(scores - base_scores))), 6
                    )
                    auc_v, auc_b = aucs.get(name), aucs.get(BASELINE)
                    if auc_v is not None and auc_b is not None:
                        entry["auc_delta"] = round(abs(auc_b - auc_v), 5)
                        entry["auc_gate"] = (
                            "pass" if entry["auc_delta"] <= cfg.auc_margin
                            else "fail"
                        )
                    elif eval_data is not None:
                        # Eval data was SUPPLIED but this variant's (or
                        # the baseline's) AUC evaluation errored: the
                        # gate fails CLOSED — an un-evaluated ranking-
                        # quality gate must never read as passed.
                        entry["auc_gate"] = "error"
                    else:
                        entry["auc_gate"] = "skipped"
                    enabled = (
                        entry["speedup"] is not None
                        and entry["speedup"] >= cfg.min_speedup
                        and entry["max_abs_delta"] <= cfg.max_abs_delta
                        and entry["auc_gate"] in ("pass", "skipped")
                        and not cfg.measure_only
                    )
                    entry["enabled"] = enabled
                    if enabled and (best is None or entry["speedup"] > best[0]):
                        best = (entry["speedup"], name)
                except Exception as exc:  # noqa: BLE001 — a variant that
                    # fails is a disabled variant, never a serving error —
                    # except a Pallas kernel refused by the accelerator.
                    self._raise_if_lowering(name, servable, on_cpu, exc)
                    entry["error"] = f"{type(exc).__name__}: {exc}"[:300]
                    entry["enabled"] = False
                row[name] = entry
            if best is not None:
                decisions[bucket] = _VARIANT_FLAGS[best[1]]
                row["decision"] = best[1]
            else:
                row["decision"] = BASELINE
            table["buckets"][str(bucket)] = row
        if decisions:
            self._warm_enabled(batcher, servable, decisions)
        with self._lock:
            new = dict(self._decisions)
            new[key] = (weakref.ref(servable), decisions)
            self._decisions = new  # atomic swap: decision() reads lock-free
            self._tables[key] = table
        if decisions:
            log.info(
                "kernel autotune %s v%d: %s", servable.name, servable.version,
                {b: table["buckets"][str(b)]["decision"] for b in decisions},
            )
        self._save_table()
        return table

    @staticmethod
    def _raise_if_lowering(name: str, servable, on_cpu: bool, exc) -> None:
        """Off the CPU (where the kernel only ever runs interpreted), a
        Pallas variant that raises is the compiler refusing the kernel."""
        if _VARIANT_FLAGS[name][1] and not on_cpu:
            raise KernelLoweringError(
                f"{name} did not compile for {servable.name} "
                f"v{servable.version} on {_device_kind()}: "
                f"{type(exc).__name__}: {exc}"
            ) from exc

    def _warm_enabled(self, batcher, servable, decisions: dict) -> None:
        """Compile the entry variants LIVE traffic hits for every enabled
        (bucket, decision): the harness only measured the score-only
        entry, but live buckets also serve the all-outputs entry
        (unfiltered requests) — left cold, the first live batch after
        enablement would pay a fresh XLA/Pallas compile on the dispatch
        path under the wedge clock
        (with [recovery] armed, a >15s compile trips a spurious
        quarantine). The warmup contract applies to variants too."""
        import jax

        b = batcher if batcher is not None else self._warm_batcher
        if b is None:
            return
        score_only = (servable.model.score_output,)
        for bucket, flags in sorted(decisions.items()):
            try:
                arrays = self._tune_arrays(b, servable, bucket)
                for out_keys in (None, score_only):
                    jax.block_until_ready(b._execute(
                        servable, dict(arrays), out_keys=out_keys,
                        _kernel_override=flags,
                    ))
            except Exception:  # noqa: BLE001 — a failed warm compiles at
                # first use instead; never blocks enablement itself.
                log.exception(
                    "kernel variant warm failed (%s:%s bucket %s)",
                    servable.name, servable.version, bucket,
                )

    # --------------------------------------------------------- persistence

    def _fingerprint(self) -> dict:
        cfg = self.config
        return {
            "min_speedup": cfg.min_speedup,
            "max_abs_delta": cfg.max_abs_delta,
            "auc_margin": cfg.auc_margin,
            "quantize": cfg.quantize,
            "pallas": cfg.pallas,
        }

    def _try_reuse(self, servable, buckets: tuple[int, ...]):
        """Adopt a persisted decision table for this exact (model,
        version, PARAMS DIGEST, device, gate fingerprint, bucket set) —
        restarts skip re-tuning; anything else (a version swap, a
        same-version retrain, changed gates) re-measures. The params
        digest is the load-bearing part: a version number alone does not
        identify the weights the gates were measured against."""
        key = (servable.name, servable.version)
        path = self.config.table_file
        if not path or not os.path.exists(path) or self.config.measure_only:
            return None
        try:
            with open(path) as f:
                data = json.load(f)
        except Exception:  # noqa: BLE001 — a corrupt table is re-tuned
            return None
        if data.get("device") != _device_kind() or \
                data.get("fingerprint") != self._fingerprint():
            return None
        entry = (data.get("entries") or {}).get(f"{key[0]}:{key[1]}")
        if entry is None:
            return None
        if entry.get("measure_only"):
            # A measure-only run's table records decisions that were
            # never allowed to enable anything; adopting it would make a
            # real serving process skip the harness and serve the
            # baseline forever. Re-measure instead.
            return None
        if entry.get("params_digest") != params_digest(servable.params):
            return None
        if sorted(entry.get("buckets") or {}) != sorted(str(b) for b in buckets):
            return None
        decisions = {
            int(b): tuple(_VARIANT_FLAGS[row.get("decision", BASELINE)])
            for b, row in entry["buckets"].items()
            if "error" not in row
        }
        decisions = {b: d for b, d in decisions.items() if d != (False, False)}
        if decisions:
            # Adopted enablement compiles here, at load — the first live
            # batch of an enabled bucket must not pay the variant compile
            # under the wedge clock (the warmup contract).
            self._warm_enabled(batcher=None, servable=servable,
                               decisions=decisions)
        entry = dict(entry)
        entry["reused_from"] = path
        with self._lock:
            new = dict(self._decisions)
            new[key] = (weakref.ref(servable), decisions)
            self._decisions = new
            self._tables[key] = entry
        self.table_reuses += 1
        log.info("kernel autotune: reused persisted table for %s:%s", *key)
        return entry

    def _save_table(self) -> None:
        path = self.config.table_file
        if not path:
            return
        with self._lock:
            entries = {
                f"{name}:{ver}": table
                for (name, ver), table in self._tables.items()
            }
        # MERGE with what is already on disk (same device + gates only —
        # a fingerprint change invalidates the whole file): a process
        # serving v2 must not erase v1's measured entry, or a rollback
        # (and every other model/process sharing the file) re-pays the
        # measurement the persistence layer exists to skip.
        try:
            with open(path) as f:
                prior = json.load(f)
            if prior.get("device") == _device_kind() and \
                    prior.get("fingerprint") == self._fingerprint():
                entries = {**(prior.get("entries") or {}), **entries}
        except Exception:  # noqa: BLE001 — absent/corrupt prior: fresh file
            pass
        data = {
            "version": 1,
            "device": _device_kind(),
            "fingerprint": self._fingerprint(),
            "entries": entries,
        }
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(data, f, indent=1, sort_keys=True)
            os.replace(tmp, path)  # atomic: readers never see half a table
            self.table_saves += 1
        except Exception:  # noqa: BLE001 — persistence is best-effort
            log.exception("kernel autotune: table save failed (%s)", path)

    # ------------------------------------------------------------ lifecycle

    def invalidate_model(self, name: str) -> None:
        """Drop a model's live decisions and tables (operator/test
        surface). NOT wired as the version-watcher hook: decision() is
        identity-guarded per tuned Servable, so a hot-loaded or reloaded
        version can never inherit another generation's enablement anyway
        — and blunt invalidation on every registry event would strip the
        STABLE version's measured win for the rest of the process (a
        silent loss /monitoring would still show as an armed plane)."""
        with self._lock:
            self._decisions = {
                k: v for k, v in self._decisions.items() if k[0] != name
            }
            for k in [k for k in self._tables if k[0] == name]:
                self._tables.pop(k, None)

    # -------------------------------------------------------------- surface

    def snapshot(self) -> dict:
        """The /monitoring `kernels` block + dts_tpu_kernel_* source."""
        cfg = self.config
        with self._lock:
            decisions = {
                f"{name}:{ver}": {
                    str(b): {"quantized": q, "pallas": p}
                    for b, (q, p) in sorted(entry[1].items())
                }
                for (name, ver), entry in self._decisions.items()
                if entry[0]() is not None  # tuned servable still alive
            }
            tables = {
                f"{name}:{ver}": table
                for (name, ver), table in self._tables.items()
            }
        return {
            "enabled": True,
            "measure_only": bool(cfg.measure_only),
            "int8_score_wire": bool(cfg.int8_score_wire),
            "counters": {
                "autotunes": self.autotunes,
                "table_saves": self.table_saves,
                "table_reuses": self.table_reuses,
                "quantized_batches": self.quantized_batches,
                "pallas_batches": self.pallas_batches,
            },
            "decisions": decisions,
            "tables": tables,
            "gates": {
                "min_speedup": cfg.min_speedup,
                "max_abs_delta": cfg.max_abs_delta,
                "auc_margin": cfg.auc_margin,
            },
        }
