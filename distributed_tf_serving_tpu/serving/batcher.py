"""Dynamic batching engine — the in-tree replacement for TF-Serving's
server-side batching (the reference claims it as a core capability,
README.md:5,9, but delegates it to the external tensorflow_model_server).

TPU-first design:

- **Padded candidate buckets.** XLA compiles one executable per input shape,
  so arbitrary candidate counts would cause a compile storm. Incoming work is
  padded up to a fixed bucket ladder (powers of two by default); jax.jit's
  own trace cache then keys on the bucket shape, giving exactly one compiled
  executable per (servable, bucket).
- **Request coalescing.** Concurrent small requests targeting the same
  (servable, signature) are concatenated along the candidate axis into one
  device call, then split back — amortizing dispatch overhead exactly like
  TF-Serving's BatchingSession. At low load a request waits at most
  `max_wait_us` before dispatch (the longest a batch stays open for
  company, not a wait every request pays: a request that would be alone
  anyway crosses on its own handler thread, see below); under sustained load the window is
  *pipeline-aware*: while >= `pipeline_depth` batches are already in
  flight, dispatching another partial batch would only queue behind device
  work, so the batcher keeps filling past the deadline for free — latency
  is unchanged (the dispatch would have waited anyway) and occupancy rises
  toward full buckets.
- **Host-side id folding.** Wire ids are int64 (DCNClient.java:98-102) but
  jax runs x64-disabled; ids are folded into the vocab with int64 numpy on
  the host (exact `mod`, not truncation) before device transfer, which also
  shrinks the transfer 2x.

- **Transfer-optimized output path.** The jitted entry returns only the
  requested output tensors, downcast on-device to a configurable wire dtype
  (bf16/f16; float32 = the exact fallback) — and, for retrieval-style
  single-request batches, only the top-k (score, index) pairs — so the D2H
  link never carries full fp32 output tensors. The D2H copy is *issued* at
  dispatch time (`readback.issue`) and only *awaited* on a completer thread
  (`readback.wait`), so the transfer overlaps host work instead of
  serializing behind it.

The core is a dedicated batching thread with a thread-safe queue, so it
serves both the grpc server (handler threads block on a Future) and the REST
gateway's event loop (await wrap_future). Device work is serialized: in pipelined
mode (default) the batching thread collects+pads while ONE dispatch thread
runs the device stage (cache/pack/upload/jit-call) — batch k+1's H2D upload
starts while batch k executes — and with pipelining off both stages share
the batching thread exactly as before. One more thread may run that same
code, still one group at a time: a sync handler thread that submits a
request onto an empty queue, below the load at which batches share
anything, closes the batch and runs its stage inside submit()
(`_crosses_direct_locked`, `_cross_direct`) instead of waking the batching
thread to wait out a window nobody joins and the dispatch thread after it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import hashlib
import re
import threading
import time
import weakref
from collections.abc import Callable
from typing import NamedTuple
from collections import OrderedDict, deque
from concurrent.futures import Future, InvalidStateError, ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults
from . import overload as overload_mod
from ..cache import CoalescedLeaderCancelled, collapse_rows
from ..cache.digest import canonical_rows
from ..models.base import Model, step_jit
from ..models.embeddings import serving_gathers
from ..models.sequence import product_summary, serving_attention
from ..models.registry import Servable
from ..ops.transfer import (
    cascade_prune_device,
    combined_layout,
    combined_supported,
    compact_outputs_device,
    describe_layout,
    is_wire_sidecar,
    output_wire_dtype as _wire_dtype_of,
    pack_host,
    pack_host_combined,
    restore_outputs_host,
    topk_compact_device,
    topk_restore_host,
    transfer_spec,
    unpack_device,
    unpack_device_combined,
)
from jax import enable_x64
from ..utils import tracing
from ..utils.tracing import request_trace
from .integrity import IntegrityScreenError

DEFAULT_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048, 4096)

# Reusable (stateless) no-op context for the non-x64 hot path.
_NULL_CTX = contextlib.nullcontext()


class BatchTooLargeError(ValueError):
    pass


class QueueOverloadError(RuntimeError):
    """Queue admission refused: accepting more work would only build a
    backlog no deadline survives. Maps to RESOURCE_EXHAUSTED at the RPC
    layer — shedding beats queueing past the client's deadline."""


class AdmissionRefusedError(QueueOverloadError):
    """The adaptive overload plane (serving/overload.py) refused this
    request: capacity/lane shedding (`reason` "shed") or doomed-work
    refusal ("doomed" — the backlog's estimated wait already exceeds the
    request's remaining deadline budget). Carries the retry-after-ms
    pushback hint the RPC layer forwards in trailing metadata. Subclasses
    QueueOverloadError so the status mapping (RESOURCE_EXHAUSTED) and
    every existing handler stay correct."""

    def __init__(self, message: str, reason: str = "shed",
                 retry_after_ms: int | None = None):
        super().__init__(message)
        self.reason = reason
        self.retry_after_ms = retry_after_ms


class DeviceWedgedError(RuntimeError):
    """Circuit breaker open: a dispatched batch has been stuck past the
    wedge threshold, so the device (or its compile path) is presumed hung.
    New work fails fast (UNAVAILABLE) instead of burning a handler thread
    per request for the full RPC deadline; the breaker closes by itself the
    moment the stuck batch completes."""


class DeviceQuarantinedError(DeviceWedgedError):
    """The recovery plane (serving/recovery.py) has quarantined this
    replica: the device executor is being torn down and rebuilt, so new
    work fails fast (UNAVAILABLE — fan-out clients reroute via the
    scoreboard) while the in-flight/queued work the replica already
    accepted rides the replay path instead of dying. Subclasses
    DeviceWedgedError so every existing status mapping and handler stays
    correct."""


class PoisonedInputError(ValueError):
    """This request's input deterministically kills the device executor:
    the recovery plane's bisection replayed progressively smaller
    sub-batches after repeated executor deaths and isolated THIS request
    as the culprit. A ValueError (-> INVALID_ARGUMENT at the RPC layer,
    the DISTINCT status the recovery contract promises): retrying the
    same bytes anywhere would kill another executor, so the client must
    not fail over with it — while the batchmates it took down are
    re-dispatched and succeed."""


class BatcherThreadDead(RuntimeError):
    """The batching loop, the pipelined dispatch stage, or a completer
    worker died from an unhandled exception. Every queued waiter is
    failed with this immediately and new submits raise it up front —
    submitters must never hang on the condition variable waiting for a
    thread that no longer exists. Maps to UNAVAILABLE (RuntimeError
    catch-all); the recovery plane, when armed, revives the thread and
    replays the shed work instead."""


def poison_fault_key(arrays: dict) -> str:
    """Content digest of one request's PREPARED input arrays (the bytes
    _WorkItem.arrays holds — post prepare_inputs, pre fold) — the `key`
    the device_lost fault site fires with once per batch member, so a
    keyed rule deterministically kills exactly the batches containing one
    specific request's content. Tests/soaks compute the same digest over
    the payload they submit to address their poison rule."""
    h = hashlib.blake2b(digest_size=8)
    for name in sorted(arrays):
        arr = np.ascontiguousarray(arrays[name])
        h.update(name.encode())
        # uint8 view: ml_dtypes arrays refuse the buffer protocol
        # directly (the DeviceInputCache._key precedent).
        h.update(arr.view(np.uint8).data)
    return h.hexdigest()


def _inject_readback_corruption(host: dict, group: list) -> dict:
    """Named fault sites (faults.py): readback_bitflip / score_nan — the
    silent-corruption chaos the integrity plane (ISSUE 20) exists to
    catch. Fired once per member request with the same content digest
    device_lost uses, AFTER the D2H asarray so the corrupted bytes are
    exactly what readback handed the completer: a keyed rule
    deterministically flips one payload bit (shadow compare's prey) or
    NaN-poisons the member's score rows (the screen's prey). The error
    kinds are markers — the raise is caught HERE and applied as the
    corruption, never surfaced. Returns `host` with the score array
    replaced by a corrupted writable copy (np.asarray views of device
    buffers are read-only)."""
    fi = faults.get()
    score_key = group[0].servable.model.score_output
    scores = host.get(score_key)
    if scores is None:
        return host
    corrupted = None
    off = 0
    for it in group:
        n = it.n
        sl = slice(off, off + n)
        off += n
        key = poison_fault_key(it.arrays)
        for site in ("readback_bitflip", "score_nan"):
            if not fi.has_site(site):
                continue
            try:
                fi.fire(site, key=key)
            except faults.InjectedFaultError:
                if corrupted is None:
                    corrupted = np.ascontiguousarray(scores).copy()
                if site == "score_nan" and corrupted.dtype.kind == "f":
                    corrupted[sl] = np.nan
                else:
                    # One bit, lowest-order, first element of the row
                    # range — below any plausible-range screen's radar,
                    # exactly the divergence only a bit-identity compare
                    # detects.
                    flat = corrupted.reshape(-1).view(
                        np.dtype(f"u{corrupted.dtype.itemsize}")
                    )
                    stride = max(corrupted.size // max(len(scores), 1), 1)
                    flat[sl.start * stride] ^= 1
    if corrupted is not None:
        host = dict(host)
        host[score_key] = corrupted
    return host


class RequestDeadlineError(TimeoutError):
    """Queued work whose CLIENT deadline expired before a dispatch slot
    opened: shed instead of executed — the caller stopped listening, so the
    device time would buy nothing and delay everyone behind it. A
    TimeoutError so the service's translator maps it to DEADLINE_EXCEEDED."""


def bucket_for(n: int, buckets: tuple[int, ...]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise BatchTooLargeError(f"candidate count {n} exceeds largest bucket {buckets[-1]}")


def fold_ids_host(ids: np.ndarray, vocab_size: int) -> np.ndarray:
    """Exact int64 modulo fold on the host; models re-fold idempotently.
    Delegates to the one canonical fold (native.fold_ids) shared with the
    client's compact_payload."""
    from .. import native

    return native.fold_ids(ids, vocab_size)


def _immutably_backed(arr: np.ndarray) -> bool:
    """True only when the array's ULTIMATE buffer is a `bytes` object —
    the one backing genuinely immutable to every party (the serving path's
    np.frombuffer(proto.tensor_content) views). writeable=False alone is
    NOT enough: a frozen view over a writable base (broadcast_to,
    setflags(write=False)) can still see its bytes change under it, and
    even a read-only memoryview does not freeze its underlying bytearray/
    mmap — its owner can keep writing through the original object."""
    a = arr
    while isinstance(a.base, np.ndarray):
        a = a.base
    b = a.base
    if isinstance(b, memoryview):
        b = b.obj
    return isinstance(b, bytes)


def prepare_inputs(
    model: Model, arrays: dict[str, np.ndarray], fold_ids: bool = True
) -> dict[str, np.ndarray]:
    """Host-side normalization before padding/transfer.

    Every output array is OWNED or IMMUTABLE (never writable-aliased to the
    caller): submit() returns before the batch is padded/uploaded, so a
    caller mutating its array after submit() would race the async device
    transfer — and poison the content-addressed DeviceInputCache digest
    (round-1 advisor finding). fold/astype copy as a side effect; the
    passthrough branch skips the copy only for arrays whose backing buffer
    is itself immutable — the serving hot path's arrays are np.frombuffer
    views over protobuf bytes, which NOBODY can mutate (~50 us per 1k x 43
    request back on the 1-core host); anything else is copied.

    fold_ids=False defers the vocab fold to batch time (_execute folds the
    whole padded batch in ONE native call): per-request folding charged
    ~130 us of ctypes+alloc overhead per 1k-candidate request to the RPC
    thread/event loop — at 500 QPS that is ~7% of the single-core budget —
    while the batched fold costs the batcher thread ~150 us per 8k batch,
    GIL released. Callers that apply the model directly on the returned
    arrays (tests, measurement harnesses) keep the folding default: unfolded
    int64 would be silently int32-cast by device_put under x64-disabled
    JAX and re-fold into garbage for ids past 2^31."""
    out = {}
    for key, arr in arrays.items():
        if key == "feat_ids" and fold_ids and model.folds_ids_on_host:
            out[key] = fold_ids_host(arr, model.config.vocab_size)
        elif arr.dtype == np.float64 and not model.needs_x64:
            # Convenience downcast for the 32-bit zoo path only: an x64
            # model (graph executor with DT_DOUBLE inputs) must see the
            # doubles it was exported with.
            out[key] = arr.astype(np.float32)
        elif _immutably_backed(arr):
            out[key] = arr
        else:
            out[key] = arr.copy()
    return out


# Weight of the newest observation in the batcher's two load averages (the
# gap between arrivals, a batch's time across the pipeline): sixteen or so
# observations deep, so one odd gap of a Poisson stream does not flip
# _holds_open.
_LOAD_EWMA = 1.0 / 16.0


def _smoothed(mean: float | None, seen: float) -> float:
    return seen if mean is None else mean + (seen - mean) * _LOAD_EWMA


class DeviceInputCache:
    """Content-addressed LRU of device-resident input arrays.

    The serving hot path is host->device upload bound: a padded batch is
    ~0.2 KB/candidate and the host<->device link is the slowest hop in the
    stack. CTR traffic re-scores the same hot candidate
    sets continuously (the reference's own benchmark re-sends one payload for
    all 6,000 requests, DCNClient.java:208-210), so identical batch bytes
    recur. Keying the *device* array by a content digest of the packed host
    bytes lets a repeat batch skip the upload entirely — the jitted call gets
    an argument that is already resident in HBM.

    Misses cost one content digest (~0.1 ms/MB native, ~1.5 ms/MB blake2b
    fallback) plus the device_put the dispatch needed anyway; hits cost only
    the digest. Capacity is bounded by entry count (batches are ~1 MB;
    default 64 entries ~ 64 MB of a v5e's 16 GB HBM) with least-recently-used
    eviction.

    Traffic that never repeats would pay the digest for nothing, so the
    cache self-disables — and re-probes: the hit rate is tracked over a
    SLIDING window of `probe_window` lookups (not the process lifetime —
    a unique-traffic phase after a long repeated phase must still flip to
    pass-through, round-3 weak #3: the one-shot probe never fired because
    global hit rate stayed high). When a window's rate is below
    `min_hit_rate`, hashing stops; after `reprobe_every` bypassed lookups
    the cache re-enters probing so a traffic regime that turns repetitive
    again re-engages it (probing costs one window of digests per
    `reprobe_every` lookups, ~12% of digest cost while traffic stays
    unique).
    """

    def __init__(
        self,
        max_entries: int = 64,
        # 64-lookup windows: repeated traffic hits ~100% so false bypass
        # needs a 63/64-miss window (won't happen), while a unique phase
        # is detected within ~64 batches; reprobe_every=512 caps probing
        # overhead at ~11% of digest cost during sustained-unique traffic
        # and bounds regime-flip recovery to ~576 batches.
        probe_window: int = 64,
        min_hit_rate: float = 0.02,
        reprobe_every: int = 512,
    ):
        self.max_entries = max_entries
        self.probe_window = probe_window
        self.min_hit_rate = min_hit_rate
        self.reprobe_every = reprobe_every
        self._lru: OrderedDict[tuple, jax.Array] = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.bytes_skipped = 0
        self.bypassed = False
        self.bypass_cycles = 0
        self._win_hits = 0
        self._win_lookups = 0
        self._bypassed_lookups = 0

    def _note_bypassed(self) -> None:
        """Count a pass-through lookup; periodically re-enter probing."""
        with self._lock:
            self._bypassed_lookups += 1
            if self._bypassed_lookups >= self.reprobe_every:
                self._bypassed_lookups = 0
                self._win_hits = 0
                self._win_lookups = 0
                self.bypassed = False

    @staticmethod
    def _key(name: str, arr: np.ndarray) -> tuple:
        from .. import native

        if native.available():
            digest = native.hash128(arr)  # ~5x blake2b, GIL released
        else:
            # uint8 view: ml_dtypes (bf16) arrays refuse the buffer
            # protocol directly ("cannot include dtype 'E'"), and the
            # digest is over raw bytes anyway.
            digest = hashlib.blake2b(
                np.ascontiguousarray(arr).view(np.uint8).data, digest_size=16
            ).digest()
        return (name, arr.shape, arr.dtype.str, digest)

    def get_or_put(
        self,
        name: str,
        arr: np.ndarray,
        pack: Callable[[np.ndarray], np.ndarray] | None = None,
        pack_tag: str = "",
    ) -> jax.Array | np.ndarray:
        """Device array for `arr`'s content, uploading (after `pack`, when
        given) only on miss. The digest keys on the PRE-pack bytes so a hit
        skips the transfer-compression work too. `pack` must be pure and
        `pack_tag` must identify the transform: the stored value is
        POST-pack, so the same raw bytes packed differently must occupy
        distinct entries."""
        if self.bypassed:
            self._note_bypassed()
            return pack(arr) if pack is not None else arr  # plain jit path
        key = (pack_tag, *self._key(name, arr))
        return self._lookup(key, lambda: pack(arr) if pack is not None else arr)

    def get_or_put_group(
        self,
        arrays: dict[str, np.ndarray],
        build: Callable[[], np.ndarray],
        tag: str,
    ) -> jax.Array | np.ndarray:
        """Device buffer for a GROUP of arrays (the combined-transfer path):
        keyed on every member's content digest plus `tag` (the layout), so a
        hit skips pack+concat+upload in one lookup. `build()` produces the
        combined host buffer only on miss."""
        if self.bypassed:
            self._note_bypassed()
            return build()
        key = (tag,) + tuple(self._key(k, arrays[k]) for k in sorted(arrays))
        return self._lookup(key, build)

    def _lookup(self, key: tuple, build_host: Callable[[], np.ndarray]):
        """Shared LRU hit/miss core: one implementation of the accounting,
        eviction, and the adaptive-bypass probe."""
        with self._lock:
            cached = self._lru.get(key)
            if cached is not None:
                self._lru.move_to_end(key)
                self.hits += 1
                self._win_hits += 1
                self._close_window_locked()
                # The avoided upload is the stored (post-pack) size.
                self.bytes_skipped += cached.nbytes
                return cached
        device_arr = jax.device_put(build_host())  # async; the executable waits, not us
        with self._lock:
            self._lru[key] = device_arr
            self.misses += 1
            self._close_window_locked()
            while len(self._lru) > self.max_entries:
                self._lru.popitem(last=False)
        return device_arr

    def _close_window_locked(self) -> None:
        """Advance the sliding probe window; flip to bypass on a cold one.
        Caller holds _lock."""
        self._win_lookups += 1
        if self._win_lookups < self.probe_window:
            return
        if self._win_hits < self._win_lookups * self.min_hit_rate:
            self.bypassed = True
            self.bypass_cycles += 1
            self._bypassed_lookups = 0
            self._lru.clear()
        self._win_hits = 0
        self._win_lookups = 0

    def clear(self) -> None:
        with self._lock:
            self._lru.clear()


class _HostBufferRing:
    """Reusable padded-batch host buffers (continuous-batching satellite).

    Every dispatched batch allocates one `np.empty((bucket,) + row_shape)`
    per input; at depth-k pipelining that is k live multi-MB allocations
    per model churning through the allocator while the device works. The
    ring hands back the SAME buffers once their batch fully completes —
    safe to reuse by construction: a buffer is released only from the
    completer's finally (the batch's readback finished, so the H2D upload
    that read it is long done) or from a pre-device failure path, never
    while a transfer could still be reading it. The padding loops fully
    overwrite every acquired buffer (rows + zero tail), so stale content
    can never leak between batches.

    Bounded: at most `per_key` free buffers are retained per (shape,
    dtype) — an acquire beyond the ring is a plain allocation and its
    release is dropped on the floor (GC'd), so a bucket-ladder sweep
    cannot pin unbounded memory. Off by default (buffer_ring=False keeps
    the historical allocate-per-batch behavior)."""

    def __init__(self, per_key: int = 8):
        self.per_key = per_key
        self._free: dict[tuple, list[np.ndarray]] = {}
        self._lock = threading.Lock()
        self.reuses = 0
        self.allocs = 0

    def acquire(self, shape: tuple, dtype) -> np.ndarray:
        key = (shape, np.dtype(dtype).str)
        with self._lock:
            free = self._free.get(key)
            if free:
                self.reuses += 1
                return free.pop()
            self.allocs += 1
        return np.empty(shape, dtype)

    def release(self, arrs) -> None:
        with self._lock:
            for a in arrs:
                key = (a.shape, a.dtype.str)
                free = self._free.setdefault(key, [])
                if len(free) < self.per_key:
                    free.append(a)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "reuses": self.reuses,
                "allocs": self.allocs,
                "free_buffers": sum(len(v) for v in self._free.values()),
            }


class _RowContext:
    """One batch's row-granular cache consultation (ISSUE 14): the
    RowBatchPlan plus the index machinery that turns (cold device rows +
    cached hot rows + foreign in-flight fills) back into every request's
    original row order.

    - `inverse` maps each ORIGINAL row onto its execution-planning slot
      (identity when dedup found no duplicates); `lead_slots` are the
      slots this batch executes, in execution order, so cold row j of the
      device output is slot `lead_slots[j]`.
    - `passthrough` marks the degenerate plan — every row cold, no
      duplicates, no foreign flights to join — where execution covers the
      original batch in original order: the normal pad/fused/delivery
      paths serve it unchanged and only the cache fill rides along.
    """

    __slots__ = ("cache", "plan", "overload", "n_slots", "inverse",
                 "lead_slots", "n_cold", "exec_arrays", "passthrough",
                 "all_fresh")

    def fill_from_host(self, host: dict) -> None:
        """Close the plan's lead flights from the executed rows: fill the
        cache (same-generation only) and resolve every foreign waiter
        riding them. host arrays are post-readback, post-widen,
        post-sidecar-consume — exactly what delivery slices, so a later
        cache assembly is bit-identical to this execution."""
        values = {}
        for j, slot in enumerate(self.plan.lead):
            values[slot] = {
                k: np.array(v[j], copy=True) for k, v in host.items()
            }
        self.cache.complete_rows(self.plan, values)

    def abort(self, exc: BaseException) -> None:
        self.cache.abort_rows(self.plan, exc)

    def assemble(self, host: dict | None):
        """Full-batch outputs in ORIGINAL row order from the three row
        sources (executed / cached hit / foreign fill). Returns (full,
        failed_rows, row_errors): failed_rows is a bool mask over
        original rows whose foreign fill failed (their requests get the
        error, never a garbage score), row_errors maps failed slots to
        their exceptions. host None = the zero-cold batch."""
        plan = self.plan
        failed: dict[int, BaseException] = {}
        wvals: dict[int, dict] = {}
        for slot, fut in plan.waiters.items():
            if fut.cancelled():
                failed[slot] = CoalescedLeaderCancelled(
                    "row fill leader was cancelled before completing"
                )
                continue
            exc = fut.exception()
            if exc is not None:
                failed[slot] = exc
            else:
                wvals[slot] = fut.result()
        if host is not None:
            sample = {k: v[0] for k, v in host.items()}
        elif plan.hits:
            sample = next(iter(plan.hits.values()))
        elif wvals:
            sample = next(iter(wvals.values()))
        else:
            # Every slot rode a foreign flight and every one failed.
            raise next(iter(failed.values()))
        full = {}
        for k, v in sample.items():
            arr = np.asarray(v)
            # zeros, not empty: a failed slot's rows are never delivered,
            # but uninitialized memory must not be reachable even by bug.
            vals = np.zeros((self.n_slots,) + arr.shape, arr.dtype)
            if host is not None and self.n_cold:
                vals[self.lead_slots] = host[k][: self.n_cold]
            for slot, hv in plan.hits.items():
                vals[slot] = hv[k]
            for slot, wv in wvals.items():
                vals[slot] = wv[k]
            full[k] = vals[self.inverse]
        failed_rows = None
        if failed:
            failed_rows = np.isin(
                self.inverse, np.fromiter(failed.keys(), np.int64)
            )
        return full, failed_rows, failed


@dataclasses.dataclass
class _WorkItem:
    servable: Servable
    arrays: dict[str, np.ndarray]  # host arrays, candidate-major
    n: int
    future: Future  # resolves to dict[str, np.ndarray]
    enqueue_t: float
    output_keys: tuple[str, ...] | None  # None = all model outputs
    # Absolute perf_counter deadline propagated from the client RPC (None =
    # no client deadline): expired items are shed pre-dispatch.
    deadline_t: float | None = None
    # Warmup work legitimately spends minutes compiling on the batcher
    # thread; it must not read as a wedged device to the circuit breaker.
    warmup: bool = False
    # Per-request tracing handle (utils/tracing.Span of the submitting
    # RPC): the batcher attaches queue-wait + per-phase child spans and
    # fault annotations to it from its own threads. None = untraced.
    span: "tracing.Span | None" = None
    # Criticality lane (overload plane metadata), carried so the quality
    # plane can label its observations per lane. None = unset.
    criticality: str | None = None
    # Streamed sub-batch (ISSUE 9): never coalesced with neighbors — the
    # whole point of the split is that each sub-batch becomes its OWN
    # device batch riding the k-deep pipeline, so its readback (and its
    # chunk flush) completes independently. Coalescing would concatenate
    # the stream right back into the one big batch it was split from.
    solo: bool = False
    # Recovery plane (ISSUE 11): how many times this item has been
    # re-dispatched by the replay path, how many device executors its
    # batches have killed, and — during poisoned-input bisection — the
    # half it belongs to (the coalescer only merges items with EQUAL
    # bisect_key, so a bisected half dispatches as its own batch).
    replays: int = 0
    device_kills: int = 0
    bisect_key: int | None = None
    # Cascade stage-1 prune (ISSUE 19): > 0 asks the jitted entry to
    # return the k best (score, index) survivor pairs plus the stage-1
    # score vector instead of full outputs. Prune submits are forced
    # solo — the survivor indices address the request's own rows.
    prune_k: int = 0
    # When the group this item rides was closed (the collector left its
    # coalesce loop, or submit kept the item for a direct crossing): the end
    # of `req.queue`, the start of `req.assemble`.
    closed_t: float | None = None
    # A direct crossing's hold on `_dispatch_pending`: True from submit's
    # decision until the count is given back (_run_stage, where a staged
    # group's is; else _cross_direct on its way out). Under `_cv`.
    direct: bool = False


def _replay_group_phases(group: list["_WorkItem"], phases: list) -> None:
    """Attach a batch's collected phase intervals + annotations to every
    traced member request's span (each co-batched request carries the full
    batch timeline — the batch work WAS its work)."""
    if not phases:
        return
    for it in group:
        if it.span is not None:
            tracing.replay_phases(it.span, phases)


# The key a counting step's counters take among its executable's outputs
# (Model.apply_stats). The dispatch stage takes it out of what the entry
# returned before anything reads the outputs, so it is never in a fetch, a
# shadow compare or a response.
STEP_STATS_KEY = "::step_stats"


def _with_step_stats(apply_stats, finish):
    """For a model whose step counts what it did (Model.apply_stats): the
    `apply` and the trace-time `finish` of _build_entry, such that the
    executable returns the counters under STEP_STATS_KEY beside the outputs
    that the selection and the wire downcast leave. A variant that returns
    something else than `finish`'s (top-k, prune) carries none."""

    def apply(params, batch):
        out, stats = apply_stats(params, batch)
        return {**out, STEP_STATS_KEY: stats}

    def finish_beside(out, out_keys):
        out = dict(out)
        stats = out.pop(STEP_STATS_KEY, None)
        done = finish(out, out_keys)
        if stats is not None:
            done[STEP_STATS_KEY] = stats
        return done

    return apply, finish_beside


class _Timeline:
    """One batch's share of the request timeline `req.*` (request_trace).

    Six stamps on perf_counter tile a request's way through a batch with
    no gap and no overlap: `enqueue_t` (submit) -> `closed_t` (the
    collector closed its group) -> `stage_t0` (device stage entered) ->
    `issue_t0` (readback issued) -> `done_t` (fetch returned) ->
    `resolved_t` (this request's set_result) -> the handler running again.
    The first five differences are summed here over the batch's requests
    and added once; `resolved_t` rides the future (`dts_resolved_t`) and
    the handler adds the sixth, `req.resume`. Warm-up items and requests
    that fail or were withdrawn add nothing."""

    __slots__ = ("stage_t0", "issue_t0", "done_t", "count", "queue_s",
                 "assemble_s", "deliver_s")

    def __init__(self, stage_t0: float, issue_t0: float, done_t: float):
        self.stage_t0, self.issue_t0, self.done_t = stage_t0, issue_t0, done_t
        self.count = 0
        self.queue_s = self.assemble_s = self.deliver_s = 0.0

    def resolve(self, it: "_WorkItem", result) -> None:
        """`it.future.set_result(result)`, stamped. InvalidStateError (the
        waiter withdrew) propagates before anything is counted."""
        if it.warmup or it.closed_t is None:
            it.future.set_result(result)
            return
        resolved_t = it.future.dts_resolved_t = time.perf_counter()
        it.future.set_result(result)
        self.count += 1
        self.queue_s += it.closed_t - it.enqueue_t
        self.assemble_s += self.stage_t0 - it.closed_t
        self.deliver_s += resolved_t - self.done_t

    def flush(self) -> None:
        n = self.count
        if n:
            request_trace.add_many((
                ("req.queue", self.queue_s, n),
                ("req.assemble", self.assemble_s, n),
                ("req.dispatch", n * (self.issue_t0 - self.stage_t0), n),
                ("req.device", n * (self.done_t - self.issue_t0), n),
                ("req.deliver", self.deliver_s, n),
            ))


class ServedKernel(NamedTuple):
    """A Pallas kernel the one-chip entry may run in place of XLA's path, and
    every name its bookkeeping goes by. WHETHER it runs is the family's
    `*_choice` (named beside each row), at trace time, from what the trace
    sees; the batcher only notes what was chosen."""

    kind: str
    stamp: str  # /monitoring's `startup.<stamp>`: the choice as traced, a servable
    counter: str  # BatcherStats' and the metrics block's count of the batches that ran it
    phase: str  # the phase that counts the same
    pallas_key: str  # the key of a note that reads "pallas" where the kernel runs
    # Where rungs or layers differ, the stamp is the note of the highest
    # (is pallas, *rank(note)).
    rank: Callable[[dict], tuple]


def _served(kind: str, stamp: str, pallas_key: str, rank) -> ServedKernel:
    return ServedKernel(kind, stamp, f"{kind}_kernel_batches", f"batch.{kind}_kernel", pallas_key, rank)


SERVED_KERNELS: tuple[ServedKernel, ...] = (
    # models/embeddings.py gather_choice: the embedding rows' gather.
    _served("gather", "gather", "kernel", lambda n: (n["in_flight"],)),
    # models/sequence.py attention_choice: the attention at all positions.
    _served("attention", "attention", "kernel", lambda n: (n["block"], "why" in n)),
    # models/routed.py grouped_choice: a routed layer's held experts.
    _served("grouped", "grouped", "kernel", lambda n: (n.get("rows", 0),)),
    # models/olmo_hybrid.py delta_choice: the gated delta rule's chunk pass.
    _served("delta", "delta_rule", "kernel", lambda n: ()),
    # models/falcon_h1.py ssd_choice: a Mamba-2 mixer's SSD chunk walk.
    _served("ssd", "ssd", "path", lambda n: (n["chunk"],)),
    # models/falcon_h1.py conv_choice: a Mamba-2 mixer's convolution.
    _served("conv", "conv", "path", lambda n: (n["positions"], "why" in n)),
)


@dataclasses.dataclass
class BatcherStats:
    """Occupancy/queueing gauges (SURVEY.md §5 metrics obligations)."""

    batches: int = 0
    requests: int = 0
    candidates: int = 0
    padded_candidates: int = 0
    # Batches assembled by the native pass (hostops.cc assemble_batch:
    # fold + pack + pad + concat in one pass an input, for any combined
    # layout, instead of 4 python/numpy passes + 3 temporaries).
    fused_batches: int = 0
    # Batches that ran an entry whose step runs that Pallas kernel: a field a
    # row of SERVED_KERNELS, by the row's `counter`.
    gather_kernel_batches: int = 0
    attention_kernel_batches: int = 0
    grouped_kernel_batches: int = 0
    delta_kernel_batches: int = 0
    ssd_kernel_batches: int = 0
    conv_kernel_batches: int = 0
    # Batches of one request that its own handler thread closed and staged
    # (submit's direct crossing): no collector, no coalesce window, no
    # dispatch thread. The phase `batch.direct` counts the same.
    direct_batches: int = 0
    # Batches whose outputs rode the top-k compaction (only k (score, idx)
    # pairs crossed the D2H link instead of the full score vector).
    topk_batches: int = 0
    # Cascade stage-1 prune batches (ISSUE 19): the jitted entry returned
    # survivor (score, index) pairs + the wire-dtype stage-1 vector, and
    # the batches where the prune could not arm (needs_x64, custom
    # run_fn, coalesced group) so the orchestrator fell back to a host
    # argpartition over the full score vector.
    prune_batches: int = 0
    prune_fallback_batches: int = 0
    max_queue_depth: int = 0
    # Times coalescing waited past max_wait because the dispatch pipeline
    # was saturated (the wait was latency-free; see _coalesce_next).
    fill_waits: int = 0
    # Intra-batch duplicate collapse (cache/dedup.py): batches whose
    # combined rows held exact duplicates, and how many rows were never
    # padded/uploaded/executed because of it (effective-batch shrink).
    dedup_batches: int = 0
    dedup_rows_collapsed: int = 0
    # Row-granular score cache (cache/row_cache.py, ISSUE 14): batches
    # that went through cold-row extraction, the rows they asked for vs
    # the rows actually dispatched to the device, and batches answered
    # entirely from cache (zero device work). rows_executed ≪
    # rows_requested is the plane's headline claim at zipfian skew.
    row_batches: int = 0
    rows_requested: int = 0
    rows_executed: int = 0
    row_full_hit_batches: int = 0
    # Queued items shed because their propagated client deadline expired
    # before a dispatch slot opened (deadline propagation, ISSUE 2).
    deadline_sheds: int = 0
    # D2H attribution: bytes actually fetched to the host (post-compaction
    # wire dtype, post output filter) vs. what a full-fp32 all-outputs
    # readback of the same batches would have moved.
    bytes_downloaded: int = 0
    bytes_download_full_f32: int = 0
    # Readback overlap: per batch, `window` spans issue->fetch-done and
    # `blocked` is how long the completer actually stalled in the fetch.
    # window==blocked (overlap 0) on the synchronous fallback path.
    readback_window_s: float = 0.0
    readback_blocked_s: float = 0.0
    # Continuous-batching pipeline (ISSUE 9): high-water mark of batches
    # simultaneously in flight (executing or awaiting readback), and how
    # often the dispatch thread waited for the k-deep in-flight window
    # to open before issuing the next batch (inflight_window armed).
    inflight_peak: int = 0
    inflight_window_waits: int = 0

    def kernel_batches(self) -> dict[str, int]:
        """The SERVED_KERNELS counters by name, as the metrics block holds them."""
        return {k.counter: getattr(self, k.counter) for k in SERVED_KERNELS}

    @property
    def mean_occupancy(self) -> float:
        return self.candidates / self.padded_candidates if self.padded_candidates else 0.0

    @property
    def mean_requests_per_batch(self) -> float:
        return self.requests / self.batches if self.batches else 0.0

    @property
    def readback_overlap_fraction(self) -> float:
        """Fraction of the in-flight D2H window the completer did NOT
        block on — 1.0 means the transfer fully hid behind other work."""
        if self.readback_window_s <= 0:
            return 0.0
        return max(0.0, 1.0 - self.readback_blocked_s / self.readback_window_s)

    @property
    def download_compaction_ratio(self) -> float:
        """full-fp32 baseline bytes / actual downloaded bytes (>=1)."""
        if not self.bytes_downloaded:
            return 0.0
        return self.bytes_download_full_f32 / self.bytes_downloaded


class DynamicBatcher:
    """Queue + batching thread + per-bucket jit cache.

    run_fn(servable, batch) -> outputs is injected so the parallel layer can
    swap in a sharded executor (pjit over a mesh) without touching batching
    logic; the default executes servable.model.apply under jax.jit.
    """

    def __init__(
        self,
        buckets: tuple[int, ...] = DEFAULT_BUCKETS,
        max_wait_us: int = 200,
        max_batch_candidates: int | None = None,
        run_fn: Callable | None = None,
        completion_workers: int = 4,
        compress_transfer: bool = True,
        input_cache_entries: int = 64,
        queue_capacity_candidates: int | None = None,
        breaker_timeout_s: float | None = 90.0,
        pipeline_depth: int = 2,
        inflight_window: int = 0,
        buffer_ring: bool = False,
        output_wire_dtype: str = "float32",
        output_top_k: int = 0,
        pipelined_dispatch: bool = True,
        score_cache=None,
        row_cache=None,
        dedup: bool = False,
        overload=None,
        utilization=None,
        quality=None,
    ):
        self.compress_transfer = compress_transfer
        # Device-failure recovery plane (serving/recovery.py): a
        # RecoveryController attached post-construction. When set, a
        # device-fatal batch failure hands its work items to the
        # controller for quarantine -> reinit -> replay instead of
        # failing their futures, new submits are refused while the
        # executor rebuilds, and the dispatching/in-flight GROUPS are
        # tracked so a wedge-triggered capture can replay them. None
        # (default) costs one attribute read per hook — the
        # tracing/cache/overload precedent.
        self.recovery = None
        # Data-integrity plane (serving/integrity.py, ISSUE 20): an
        # IntegrityPlane attached post-construction. When set, sampled
        # batches re-execute for a bit-identity shadow compare, delivered
        # score rows pass a post-readback NaN/Inf screen (failing rows
        # fail their OWN request; batchmates deliver), and screen-trip
        # bursts escalate to the recovery cycle. None (default) costs one
        # attribute read per hook — the recovery/quality precedent.
        self.integrity = None
        # Thread-death watchdog (recovery satellite): set to the
        # BatcherThreadDead the moment any batcher-owned thread dies from
        # an unhandled exception; submit() fails fast on it instead of
        # letting submitters hang on the condition variable.
        self._dead: BatcherThreadDead | None = None
        # Model-quality plane (serving/quality.py): a QualityMonitor fed
        # one observe() per completed non-warmup request from _complete —
        # scores are already in host f32 memory post-readback, so the
        # hook costs no device work. Cache hits and brownout stale-serves
        # never reach the completer, so only freshly computed scores are
        # sketched. None (default) costs one attribute read per batch.
        self.quality = quality
        # Utilization plane (serving/utilization.py): an OccupancyLedger
        # fed one interval per completed batch from the existing
        # dispatch/readback sites, plus cheap wait-interval records while
        # the batcher idles (the device-idle causes the gap waterfall
        # attributes). None (default) costs one attribute read per hook.
        self.utilization = utilization
        # Overload plane (serving/overload.py): an AdmissionController
        # replaces the static queue_capacity_candidates check with a
        # self-tuning limit, criticality lanes, deadline-aware refusal,
        # and the brownout stale-serve gate. None (default) keeps the
        # static bound and costs one attribute read per submit.
        self.overload = overload
        # Cache plane (cache/): an exact-match ScoreCache short-circuits
        # whole-request repeats at submit (hit = no queue, no device, no
        # dispatch slot; identical concurrent misses single-flight onto one
        # computation), and dedup collapses duplicate rows inside a
        # combined batch before padding/upload. Both off by default; when
        # score_cache is None / dedup False the hot path pays one attribute
        # read per submit/dispatch — the tracing/faults precedent.
        self.score_cache = score_cache
        # Row-granular score cache (cache/row_cache.py, ISSUE 14): after
        # collect, each batch's rows are digested and looked up per row —
        # hot rows answer from cache, ONLY the cold rows are packed,
        # bucketed, and dispatched (possibly a smaller bucket), and the
        # completer scatters device + cached scores back into every
        # request's slice. The whole-request cache above stays in front
        # (a full hit never reaches this plane). None (default) costs one
        # attribute read per batch.
        self.row_cache = row_cache
        self.dedup = bool(dedup)
        # Output-transfer pipeline knobs (utils/config.py ServerConfig
        # carries the same names). wire dtype is validated HERE so a typo'd
        # config fails at construction, not at first dispatch.
        self.output_wire_dtype = output_wire_dtype
        self._wire_dt = _wire_dtype_of(output_wire_dtype)
        self.output_top_k = max(int(output_top_k or 0), 0)
        # Content-addressed device-resident inputs (only meaningful for the
        # default jit path; a custom run_fn manages its own placement).
        self.input_cache = (
            DeviceInputCache(input_cache_entries)
            if input_cache_entries and run_fn is None
            else None
        )
        self.buckets = tuple(sorted(buckets))
        self.max_wait_s = max_wait_us / 1e6
        # Clamped: coalescing past the largest bucket would build a batch no
        # bucket can hold and fail the whole group at dispatch time.
        self.max_batch_candidates = min(
            max_batch_candidates or self.buckets[-1], self.buckets[-1]
        )
        # Admission bound: at most this many candidates queued (not yet
        # dispatched). 16 full max-size batches of backlog is already several
        # deadlines' worth of work; past that, shedding with
        # RESOURCE_EXHAUSTED is strictly kinder than queueing.
        # Clamped to at least one full max-size batch: a capacity below
        # buckets[-1] would permanently reject every request larger than it
        # even on an idle queue.
        self.queue_capacity_candidates = max(
            queue_capacity_candidates
            if queue_capacity_candidates is not None
            else 16 * self.buckets[-1],
            self.buckets[-1],
        )
        if self.overload is not None:
            # Resolve the controller's auto limit bounds against this
            # batcher's real geometry (min = one largest bucket, max = the
            # static capacity the controller replaces).
            self.overload.bind(self.buckets[-1], self.queue_capacity_candidates)
        # Wedge threshold for the circuit breaker. Default is above any sane
        # steady-state batch but below the 120s RPC deadline; first compiles
        # belong in warmup(), not live traffic.
        self.breaker_timeout_s = breaker_timeout_s
        # The k-deep continuous-batching window (ISSUE 9). pipeline_depth
        # bounds how many ASSEMBLED groups may be staged ahead of the
        # device stage (the coalescer's free-ride gate reads it too);
        # depth 1 serializes assembly against the device stage (readback
        # still overlaps via the completers) and is allowed but rarely
        # wanted — the historical floor of 2 remains the default.
        self.pipeline_depth = max(pipeline_depth, 1)
        # inflight_window > 0 additionally bounds how many batches may be
        # simultaneously IN FLIGHT (executing or awaiting readback): the
        # dispatch thread keeps issuing batch k+2 while k awaits readback
        # until the window fills, then waits for a completion — deep
        # enough to hide the D2H link, bounded so a slow device cannot
        # accumulate unbounded in-flight HBM. 0 = unbounded (the
        # historical behavior).
        self.inflight_window = max(int(inflight_window or 0), 0)
        # Padded-batch host buffer reuse; None = allocate fresh
        # per batch (the historical behavior).
        self.buffer_ring = (
            _HostBufferRing(per_key=max(self.inflight_window, 4) + 4)
            if buffer_ring else None
        )
        self._items: "deque[_WorkItem]" = deque()
        self._cv = threading.Condition()
        self._queued_candidates = 0
        # Wedge bookkeeping: wall-clock starts of (a) the device stage
        # currently executing (dispatch thread in pipelined mode, batcher
        # thread otherwise) and (b) every readback in flight.
        self._dispatching_since: float | None = None
        self._inflight: dict[int, float] = {}
        self._inflight_seq = 0
        # Recovery bookkeeping (populated only while a RecoveryController
        # is attached): the group currently in the device stage and the
        # groups executing-or-awaiting-readback, registered/popped at the
        # same _cv sites as the wedge clock so a quarantine capture can
        # replay the EXACT work a wedged device stranded.
        self._dispatching_group: list | None = None
        self._inflight_groups: dict[int, list] = {}
        # Per-bucket in-flight accounting (continuous batching, ISSUE 9):
        # bucket -> batches currently executing-or-awaiting-readback, fed
        # under _cv at the same register/pop sites as _inflight so the
        # two can never disagree. Read by pipeline_stats() and the
        # dts_tpu_pipeline_* Prometheus series.
        self._inflight_buckets: dict[int, int] = {}
        # Pipelined dispatch: groups handed to the dispatch thread but not
        # yet registered in flight. Admission counts their candidates (the
        # queue bound must not weaken just because the pipeline popped
        # them), shedding fails their futures, and _coalesce_next's
        # free-ride gate counts them toward pipeline saturation.
        self.pipelined_dispatch = pipelined_dispatch
        self._dispatcher = (
            ThreadPoolExecutor(max_workers=1, thread_name_prefix="batch-dispatch")
            if pipelined_dispatch
            else None
        )
        self._dispatch_pending = 0
        # The load as _holds_open weighs it: smoothed seconds between two
        # arrivals, and from a batch's stage start to its readback's end.
        # None until measured: a batcher that has seen no load holds nothing.
        self._last_arrival_t: float | None = None
        self._arrival_gap_s: float | None = None
        self._traversal_s: float | None = None
        # The direct crossing (_crosses_direct_locked): whether the collector
        # is parked in `wait.queue_empty`, and the item a handler thread is
        # crossing with right now (None: nobody is). The collector starts no
        # _dispatch while one is, so _dispatch and _run_stage never run for
        # two groups at once because of it.
        self._collector_parked = False
        self._direct_item: _WorkItem | None = None
        self._staged_candidates = 0
        self._staged_groups: dict[int, tuple[list, int]] = {}
        self._staged_seq = 0
        # servable -> [bytes/row of a full-fp32 all-outputs readback],
        # recorded at trace time by the jitted entry (the baseline the
        # bytes_download_full_f32 counter charges).
        self._out_row_bytes: weakref.WeakKeyDictionary[Servable, list] = (
            weakref.WeakKeyDictionary()
        )
        # servable -> the upload formats its entry has been traced for
        # (ops/transfer.py describe_layout; "per key ..." off the combined
        # buffer): /monitoring's `startup.upload_format`.
        self._upload_formats: weakref.WeakKeyDictionary[Servable, list] = (
            weakref.WeakKeyDictionary()
        )
        # servable -> {kind: what that kind's `*_choice` chose, each time its
        # entry was traced} (SERVED_KERNELS): `startup.<stamp>`; and servable
        # -> the rows whose kernel its entry runs, whose batches are counted.
        self._kernel_notes: weakref.WeakKeyDictionary[Servable, dict[str, list]] = (
            weakref.WeakKeyDictionary()
        )
        self._kernel_kinds: weakref.WeakKeyDictionary[Servable, tuple[ServedKernel, ...]] = (
            weakref.WeakKeyDictionary()
        )
        # And every product of an activation in pieces against a weight that
        # its entry was traced with, `(M, k, n, pieces, form)`
        # (models/sequence.py product): `startup.products`. Read at a
        # scrape, never by a batch.
        self._products: weakref.WeakKeyDictionary[Servable, list] = (
            weakref.WeakKeyDictionary()
        )
        # jit_entry is reached from the batcher thread (fused-path
        # eligibility) AND the dispatch thread; one lock keeps the entry
        # build single-shot.
        self._jit_lock = threading.Lock()
        # Weak keys: unloaded servables must not pin their compiled
        # executables, and a recycled object address must not serve a stale
        # one (Servable uses eq=False, so it is hashable and weakref-able).
        self._jitted: weakref.WeakKeyDictionary[Servable, tuple[Callable, dict]] = (
            weakref.WeakKeyDictionary()
        )
        self._run_fn = run_fn
        self.stats = BatcherStats()
        self._thread = threading.Thread(target=self._loop, name="batcher", daemon=True)
        self._started = False
        self._stopping = False
        # Device->host readback happens off the batching thread so batch k+1's
        # transfer+compute dispatch overlaps batch k's result fetch — this is
        # what pipelines over host<->device link latency (jax dispatch is
        # async; only the fetch blocks). Several workers = several batches'
        # readbacks in flight.
        # Retained for the recovery plane's pool rebuild
        # (replace_workers_for_recovery) — the recovered server must keep
        # this configured readback concurrency.
        self.completion_workers = completion_workers
        self._completers = ThreadPoolExecutor(
            # At least one completer per in-flight-window slot: a window
            # deeper than the pool would leave issued readbacks queued
            # behind completer capacity instead of actually overlapping.
            max_workers=max(completion_workers, self.inflight_window),
            thread_name_prefix="batch-complete",
        )

    # ------------------------------------------------------------------ API

    def start(self) -> "DynamicBatcher":
        if not self._started:
            self._started = True
            # On /monitoring from the start, at count 0: a server that
            # crosses nothing direct reads 0, one that cannot reads nothing.
            request_trace.add_many((("batch.direct", 0.0, 0),))
            self._thread.start()
            # Compile/load the native host ops off-thread so the first
            # request never pays the g++ latency (numpy fallback until ready).
            from .. import native

            native.warm_async()
        return self

    def drain(self, timeout_s: float) -> bool:
        """Block until every accepted item has fully completed — queue
        empty, no staged groups, no dispatch in progress, no readback in
        flight — or `timeout_s` elapses. True = fully drained. The
        graceful-shutdown path (serving/server.py GracefulShutdown) calls
        this AFTER new admissions are refused, so the wait is bounded by
        the work already accepted, not by arriving traffic.

        Recovery interplay (ISSUE 11 satellite): while the recovery plane
        holds captured work (quarantine/reinit/replay in progress), the
        queue can look empty here even though accepted requests are still
        pending replay — the predicate observes the controller's
        cycle_active() so drain neither returns a false True mid-REINIT
        nor deadlocks: the wait stays bounded by `timeout_s` (the
        remaining grace) and GracefulShutdown aborts the cycle first."""
        deadline = time.perf_counter() + max(timeout_s, 0.0)
        rec = self.recovery
        with self._cv:
            while (
                self._items
                or self._staged_groups
                or self._inflight
                or self._dispatch_pending
                or self._dispatching_since is not None
                or (rec is not None and rec.cycle_active())
            ):
                if self._dead is not None:
                    # A dead batching thread will never drain this work;
                    # the waiters were already failed fast.
                    return False
                left = deadline - time.perf_counter()
                if left <= 0:
                    return False
                self._cv.wait(min(left, 0.05))
        return True

    def stop(self) -> None:
        if self._started:
            with self._cv:
                self._stopping = True
                self._cv.notify_all()
                # A direct crossing under way is a handler thread's: its
                # stage ends as the dispatch thread's do below, before the
                # completers go.
                give_up = time.perf_counter() + 5
                while self._direct_item is not None and time.perf_counter() < give_up:
                    self._cv.wait(0.05)
            self._thread.join(timeout=5)
            if self._dispatcher is not None:
                # Every staged group still executes (accepted work is
                # served); the dispatch thread drains before the
                # completers do.
                self._dispatcher.shutdown(wait=True)
            self._completers.shutdown(wait=True)
            self._started = False

    def _wedged_for(self, now: float) -> float:
        """Seconds the oldest stuck batch has been in flight past the
        breaker threshold; 0.0 when healthy. Caller holds _cv."""
        t = self.breaker_timeout_s
        if t is None:
            return 0.0
        worst = 0.0
        if self._dispatching_since is not None:
            worst = now - self._dispatching_since
        for t0 in self._inflight.values():
            worst = max(worst, now - t0)
        return worst if worst > t else 0.0

    def _shed_queued(self, exc: Exception) -> None:
        """Fail every queued (not yet dispatched) item AND every staged
        group still waiting behind the wedged device stage. Caller holds
        _cv."""
        while self._items:
            it = self._items.popleft()
            self._queued_candidates -= it.n
            if not it.future.done():
                it.future.set_exception(exc)
        for sid in list(self._staged_groups):
            group, total = self._staged_groups.pop(sid)
            self._staged_candidates -= total
            for it in group:
                if not it.future.done():
                    it.future.set_exception(exc)

    def submit(
        self,
        servable: Servable,
        arrays: dict[str, np.ndarray],
        output_keys: tuple[str, ...] | None = None,
        deadline_s: float | None = None,
        span: "tracing.Span | None" = None,
        criticality: str | None = None,
        _warmup: bool = False,
        _solo: bool = False,
        _prune_k: int = 0,
        _may_block: bool = False,
    ) -> Future:
        """Enqueue one request's arrays; returns a Future of output arrays
        (sliced back to the request's own candidate count). output_keys limits
        which model outputs are fetched back to the host. deadline_s (when
        given) is the CLIENT's remaining budget: an item still queued when it
        expires is shed (RequestDeadlineError -> DEADLINE_EXCEEDED) before
        wasting a dispatch slot. `span` (when per-request tracing is on) is
        the RPC's span handle: the batcher attaches queue-wait and device-
        stage phase child spans to it from its own threads. `criticality`
        (overload plane) picks the admission lane — sheddable traffic is
        refused first under pressure; warmup rides the probe lane.

        Admission control (SURVEY.md §5 failure-detection obligations): a
        wedged device fails the request immediately (DeviceWedgedError, and
        the backlog is shed with it), and a backlog past the admission
        limit — the static queue_capacity_candidates bound, or the
        adaptive overload controller's self-tuned limit when armed — is
        refused (QueueOverloadError / AdmissionRefusedError) instead of
        queueing work no deadline survives.

        _prune_k (cascade stage-1, ISSUE 19): > 0 turns this submit into a
        prune — the result dict carries survivor (score, index) pairs plus
        the stage-1 score vector instead of full outputs. Forced solo
        (survivor indices address the request's own rows), and the score-
        cache key is salted with the mode+k so a prune result can never be
        served to a full-vector request for the same features (or vice
        versa).

        _may_block: the caller is a thread that is about to sleep on the
        Future anyway (service._run's handler thread, and nobody else: never
        the REST gateway's event loop). Such a caller crosses the batcher
        ITSELF when the request would be alone in its batch
        (_crosses_direct_locked): it closes the batch and runs its stage
        inside this call, and the Future it gets back is resolved by a
        completer as ever."""
        if _prune_k:
            _solo = True
        if self._stopping:
            raise RuntimeError("batcher is stopped")
        if self._dead is not None:
            # Thread-death watchdog: a batcher-owned thread died from an
            # unhandled exception — fail fast instead of queueing work
            # nobody will ever dispatch (the recovery plane, when armed,
            # revives the thread and clears this).
            raise self._dead
        ns = {k: v.shape[0] for k, v in arrays.items()}
        n = next(iter(ns.values()))
        if any(v != n for v in ns.values()):
            raise ValueError(f"inconsistent candidate counts across inputs: {ns}")
        bucket_for(n, self.buckets)  # validate size up front, raises if too big
        # Score-cache lookup BEFORE admission: a hit (or a coalesced join
        # onto an identical in-flight miss) bypasses the queue entirely —
        # including the wedge/overload checks, deliberately: cached scores
        # are servable even while the device is wedged or the queue full.
        cache = self.score_cache
        ov = self.overload
        handle = None
        if cache is not None and not _warmup:
            # Brownout stale-serve (overload plane): while pressure is past
            # NOMINAL, an entry up to stale_while_overloaded_s past its TTL
            # still answers — marked degraded, never re-filled — so hot-key
            # traffic keeps getting scores while the device catches up.
            stale_s = (
                ov.stale_window_s
                if ov is not None and ov.stale_serve_active()
                else 0.0
            )
            with request_trace.span("cache.lookup"):
                handle = cache.begin(
                    servable.name, servable.version, output_keys, arrays,
                    stale_s=stale_s,
                    salt=b"prune:%d" % _prune_k if _prune_k else b"",
                )
            if handle.hit is not None:
                if handle.stale:
                    ov.note_brownout_serve()
                    overload_mod.mark_degraded("stale")
                    if span is not None:
                        span.attrs["brownout_stale"] = True
                        span.annotate("overload.stale_serve",
                                      stale_window_s=stale_s)
                elif span is not None:
                    span.attrs["cache_hit"] = True
                fut: Future = Future()
                fut.set_result(handle.hit)
                return fut
            if handle.waiter is not None:
                if span is not None:
                    span.attrs["cache_coalesced"] = True
                return handle.waiter
        try:
            return self._submit_miss(
                servable, arrays, n, output_keys, deadline_s, span, _warmup,
                handle, cache, criticality, _solo, _prune_k, _may_block,
            )
        except BaseException as exc:
            if handle is not None and handle.leader:
                # The leader never enqueued (admission refused, prepare
                # failed): close the flight so coalesced waiters fail with
                # the same error instead of hanging.
                cache.abort(handle, exc)
            raise

    def _submit_miss(
        self, servable, arrays, n, output_keys, deadline_s, span, _warmup,
        handle, cache=None, criticality=None, solo=False, prune_k=0,
        may_block=False,
    ) -> Future:
        """The no-cache-hit tail of submit(): admission, prepare, enqueue
        (exactly the pre-cache-plane submit body). The cache handle, when
        this request leads a single-flight, is armed on the future so the
        completion fans out to waiters and fills the cache."""
        # Admission BEFORE the defensive copy: a shed request must not pay
        # the copy/fold cost — overload is exactly when the host can least
        # afford it. Capacity is reserved under the lock so concurrent
        # submits cannot overshoot while this one prepares its arrays.
        ov = self.overload
        rec = self.recovery
        with self._cv:
            if rec is not None and not _warmup and rec.refusing():
                # Quarantine gate (recovery plane): the executor is being
                # torn down/rebuilt — refuse NEW work fast (UNAVAILABLE,
                # clients failover via the scoreboard) while the already-
                # accepted work rides the replay path. Warmup is exempt:
                # the REINIT phase re-warms the bucket ladder through
                # this very queue.
                raise DeviceQuarantinedError(
                    "replica quarantined: device executor is being "
                    f"rebuilt (recovery state {rec.state()}); retry "
                    "against another backend"
                )
            stuck_s = self._wedged_for(time.perf_counter())
            if stuck_s:
                exc = DeviceWedgedError(
                    f"a dispatched batch has been stuck {stuck_s:.1f}s "
                    f"(> breaker {self.breaker_timeout_s:.0f}s); failing fast"
                )
                self._shed_queued(exc)
                raise exc
            backlog = self._queued_candidates + self._staged_candidates
            if ov is not None:
                # Adaptive admission: self-tuned limit + criticality lane
                # + doomed-work refusal, with a retry-after pushback hint
                # on every refusal (serving/overload.py).
                lane = (
                    overload_mod.PROBE if _warmup
                    else overload_mod.normalize_criticality(criticality)
                )
                decision = ov.admit(n, backlog, lane=lane, deadline_s=deadline_s)
                if not decision.admitted:
                    if span is not None:
                        span.annotate(
                            "overload.shed", reason=decision.reason,
                            lane=lane, retry_after_ms=decision.retry_after_ms,
                        )
                    if (util := self.utilization) is not None:
                        # Gap-attribution event: an empty queue during a
                        # shed storm is refused traffic, not absent
                        # traffic (idle cause "admission_shed").
                        util.note_shed()
                    raise AdmissionRefusedError(
                        decision.message,
                        reason=decision.reason or "shed",
                        retry_after_ms=decision.retry_after_ms,
                    )
            elif backlog + n > self.queue_capacity_candidates:
                if (util := self.utilization) is not None:
                    util.note_shed()
                raise QueueOverloadError(
                    f"queue holds {backlog} candidates (queued + staged); "
                    f"admitting {n} more would exceed capacity "
                    f"{self.queue_capacity_candidates}"
                )
            self._queued_candidates += n
        fut: Future = Future()
        try:
            now = time.perf_counter()
            item = _WorkItem(
                servable=servable,
                arrays=prepare_inputs(servable.model, arrays, fold_ids=False),
                n=n,
                future=fut,
                enqueue_t=now,
                output_keys=output_keys,
                deadline_t=(now + deadline_s) if deadline_s is not None else None,
                warmup=_warmup,
                span=span if tracing.enabled() else None,
                criticality=criticality,
                solo=solo,
                prune_k=prune_k,
            )
        except BaseException:
            with self._cv:
                self._queued_candidates -= n
            raise
        with self._cv:
            if not _warmup:
                if self._last_arrival_t is not None:
                    self._arrival_gap_s = _smoothed(
                        self._arrival_gap_s, now - self._last_arrival_t
                    )
                self._last_arrival_t = now
            direct = False
            if may_block and self._crosses_direct_locked(item):
                # Out of the queue as _take takes an item, and shed as _take
                # sheds one (the Future then carries the deadline's error).
                self._queued_candidates -= n
                if not self._drop_stale_locked(item):
                    # The count a staged group holds: a request that arrives
                    # during this stage sees a stage running, queues and is
                    # held for (_holds_open), and no second crossing starts
                    # beside it.
                    direct = item.direct = True
                    self._direct_item = item
                    self._dispatch_pending += 1
                    item.closed_t = time.perf_counter()
            else:
                self._items.append(item)
                self.stats.max_queue_depth = max(
                    self.stats.max_queue_depth, len(self._items)
                )
                self._cv.notify()
        if handle is not None and handle.leader:
            # Fill + waiter fan-out ride the future's completion (success,
            # failure, or cancellation), on whichever thread resolves it.
            # `cache` is the instance that MINTED the handle in submit()
            # (passed down, never re-read from self here): detaching or
            # swapping score_cache with leaders in flight (bench A/B
            # teardown) must still close those leaders' flights, or their
            # coalesced waiters hang. The leader's servable/arrays ride
            # along so a deadline-killed leader's waiters can be
            # re-dispatched instead of inheriting its deadline fate.
            fut.add_done_callback(
                lambda f, h=handle, c=cache, sv=servable, a=arrays,
                ok=output_keys, pk=prune_k:
                self._cache_complete(c, h, f, sv, a, ok, pk)
            )
        if direct:
            self._cross_direct(item)
        return fut

    def _crosses_direct_locked(self, item: _WorkItem) -> bool:
        """Whether the thread that submits `item`, and may block, crosses
        the batcher itself: closes a batch of this one request and runs its
        stage, with no hand-over to the collector, no coalesce window and no
        hand-over to the dispatch thread. The caller holds `_cv`.

        When the request would be alone in its batch anyway, by what the
        batcher already keeps: an ordinary item (not solo, not a warm-up,
        no bisection half); nobody ahead of it and no batch open that it
        could join (the queue empty, the collector parked in
        `wait.queue_empty`); no stage staged or running and the pipeline
        not full (a batch on the device is no obstacle, a stage on the
        dispatch thread is: _holds_open holds a batch for what arrives
        during one); and requests arriving slower than batches cross the
        pipeline, by the two averages _holds_open weighs and on the other
        side of its threshold (Little's law: on average nobody joins).
        While either average is unknown everything queues."""
        if item.solo or item.warmup or item.bisect_key is not None:
            return False
        if self._items or not self._collector_parked or self._stopping:
            return False
        if (
            self._dispatch_pending
            or self._direct_item is not None
            or len(self._inflight) >= self.pipeline_depth
        ):
            return False
        gap, crossing = self._arrival_gap_s, self._traversal_s
        return gap is not None and crossing is not None and gap > crossing

    def _cross_direct(self, item: _WorkItem) -> None:
        """The direct crossing itself, on the submitting thread: the same
        _dispatch the collector calls, its stage run inline. _run_stage
        gives the pending count back where it does a staged group's; what
        ends before a stage (a failed assembly, a batch answered from the
        row cache) gives it back here, where the collector is let go too.

        _dispatch and _run_stage fail their group on an Exception
        themselves. What escapes them is what _guard_worker_future catches
        on a pool's thread; this thread is the caller's, so no thread of the
        batcher has died and there is no verdict: the request fails, alone."""
        try:
            self._dispatch([item], item.n)
        except BaseException as exc:  # noqa: BLE001 — the waiter must resolve
            if not item.future.done():
                try:
                    item.future.set_exception(exc)
                except InvalidStateError:
                    pass
        finally:
            with self._cv:
                if item.direct:
                    item.direct = False
                    self._dispatch_pending = max(self._dispatch_pending - 1, 0)
                if self._direct_item is item:
                    self._direct_item = None
                self._cv.notify_all()

    def _cache_complete(
        self, cache, handle, fut: Future, servable, arrays, output_keys,
        prune_k: int = 0,
    ) -> None:
        """Close a single-flight leader's computation into the cache:
        successful results fill (and wake coalesced waiters), failures fan
        out. A leader killed by ITS OWN deadline (service-timeout cancel,
        queued-deadline shed) does not doom its waiters — their budgets are
        their own, so the computation is re-dispatched once on their
        behalf (deadline-free; a fresh identical request would coalesce
        onto it). Runs as a Future done-callback on a completer/service
        thread."""
        deadline_shaped = fut.cancelled() or isinstance(
            fut.exception(), RequestDeadlineError
        )
        if deadline_shaped:
            waiters = [
                w for w in cache.take_waiters(handle) if not w.cancelled()
            ]
            if not waiters:
                return
            try:
                retry = self.submit(
                    servable, arrays, output_keys=output_keys,
                    _prune_k=prune_k,
                )
            except BaseException as exc:  # stopped/wedged/overloaded batcher
                for w in waiters:
                    try:
                        w.set_exception(exc)
                    except InvalidStateError:
                        pass
                return

            def chain(rf: Future) -> None:
                for w in waiters:
                    if w.cancelled():
                        continue
                    try:
                        if rf.cancelled():
                            w.cancel()
                        elif rf.exception() is not None:
                            w.set_exception(rf.exception())
                        else:
                            w.set_result(rf.result())
                    except InvalidStateError:
                        pass

            retry.add_done_callback(chain)
            return
        degraded = getattr(fut, "dts_degraded", None)
        if degraded is not None:
            # The leader's response was assembled with brownout-STALE row
            # entries (row plane, ISSUE 14): it must never fill the
            # whole-request cache — a fresh-TTL entry would keep serving
            # past-TTL data unmarked long after the brownout clears — and
            # every coalesced waiter inherits the degraded marker with
            # the result (the service forwards it per future).
            waiters = cache.take_waiters(handle)
            if waiters:
                result = fut.result()
                for w in waiters:
                    if w.cancelled():
                        continue
                    w.dts_degraded = degraded
                    try:
                        w.set_result(result)
                    except InvalidStateError:
                        pass
            return
        with request_trace.span("cache.fill"):
            cache.complete(handle, fut)

    @staticmethod
    def warmup_arrays(servable: Servable, n: int) -> dict[str, np.ndarray]:
        """Zero batch matching the servable's default-signature inputs —
        signature-driven so optional inputs (DLRM dense_features) are
        included and imported signatures warm what they actually declare."""
        from .. import codec

        sig = servable.signature("")
        out = {}
        for spec in sig.inputs:
            if spec.shape is None or len(spec.shape) < 1:
                continue  # unknown rank: nothing sensible to synthesize
            dims = (n,) + tuple(d or 1 for d in spec.shape[1:])
            out[spec.name] = np.zeros(dims, codec.dtype_to_numpy(spec.dtype))
        return out

    def warmup(self, servable: Servable, buckets: tuple[int, ...] | None = None) -> None:
        """Precompile the bucket ladder for a servable (compile storms belong
        at load time, not first-request time). Executes directly — only safe
        before the batcher serves traffic; once live, use warmup_via_queue.
        EXCEPTION: elastic run_fns — the elastic branch below routes through
        warmup_call into each ShardedExecutor's internally-locked entry
        cache and never touches the single-chip _jitted dict this contract
        protects, so warmup_via_queue's ladder tail and the recovery
        re-warm deliberately call it on a LIVE batcher. Keep it that way:
        batcher-level warmup state for run_fn executors belongs behind
        the queue, not here.

        Each bucket warms the output-selection variants live traffic
        predictably hits: the all-outputs entry (unfiltered requests,
        direct submits), the score-only entry (output_filter'd requests —
        the reference client filters to its output_key), the top-k entry
        when configured (its queue-path gate skips warmup items, so ONLY
        this direct pass can precompile it — a live compile on the dispatch
        path would stall the pipeline with the wedge clock armed). A client
        filtering to any OTHER output subset still compiles its variant at
        first request — rare enough (subsets of the signature's outputs)
        that warming the combinatorial space is not worth the load-time."""
        model = servable.model
        if self._run_fn is not None:
            # Custom executors ignore topk — but an executor that
            # honors output selection (the mesh path's supports_out_keys)
            # compiles a distinct executable per out_keys, so both
            # variants live traffic predictably hits (all-outputs +
            # score-only) warm here; other executors get the historical
            # one execution per bucket.
            out_variants: tuple = (None,)
            if getattr(self._run_fn, "supports_out_keys", False):
                out_variants = (None, (model.score_output,))
            # Elastic executors warm EVERY split's executable per variant
            # (warmup_call) — the switch-never-compiles contract: a
            # runtime split change must never pay an XLA compile on the
            # dispatch path (which would stall the pipeline, and trip the
            # [recovery] wedge clock when armed). The arrays are folded
            # here exactly like _execute folds them, so the warmed
            # executables match live traffic's dtypes.
            warm_all = (
                self._run_fn.warmup_call
                if getattr(self._run_fn, "elastic", False) else None
            )
            for b in buckets or self.buckets:
                arrays = prepare_inputs(model, self.warmup_arrays(servable, b))
                for out_keys in out_variants:
                    if warm_all is not None:
                        warm_all(
                            servable, self._fold_host(servable, arrays),
                            out_keys=out_keys,
                        )
                    else:
                        self._execute(servable, arrays, out_keys=out_keys)
            return
        score_only = (model.score_output,)
        for b in buckets or self.buckets:
            arrays = prepare_inputs(model, self.warmup_arrays(servable, b))
            for out_keys in (None, score_only):
                self._execute(servable, arrays, out_keys=out_keys)
            if (
                self.output_top_k
                and self._run_fn is None
                and not model.needs_x64
                and self.output_top_k < b
            ):
                self._execute(
                    servable, arrays, out_keys=score_only,
                    topk=self.output_top_k, n_valid=b,
                )

    def warmup_via_queue(
        self, servable: Servable, buckets: tuple[int, ...] | None = None
    ) -> None:
        """Warm a servable THROUGH the request queue: compilation happens on
        the batching thread exactly like live traffic, so hot-loading a new
        model version never races the jit caches with in-flight requests."""
        futures = [
            self.submit(servable, self.warmup_arrays(servable, b), _warmup=True)
            for b in buckets or self.buckets
        ]
        for fut in futures:
            fut.result(timeout=600)
        if getattr(self._run_fn, "elastic", False):
            # The queue path compiled only the CURRENT split's entries.
            # Warm the rest of the ladder directly (warmup() routes
            # elastic run_fns through warmup_call — every split; the
            # current split's second pass is a cache hit), so a
            # hot-loaded version keeps the switch-never-compiles
            # contract: its first post-switch batch must not pay an XLA
            # compile on the dispatch path.
            self.warmup(servable, buckets)

    def jit_entry(self, servable: Servable) -> tuple[Callable, dict[str, str], bool]:
        """The (jitted fn, transfer spec, combined) this batcher serves
        `servable` with — public so tests time and inspect the EXACT
        serving executable, warm caches included, instead of compiling a
        lookalike. When
        `combined` is True the fn signature is (params, uint32_buffer,
        layout) with layout static (ops/transfer.py combined_layout); both
        shapes accept optional keywords (out_keys, topk, n_valid)
        selecting the output-compaction variant — defaults reproduce the
        all-outputs entry (see _build_entry)."""
        with self._jit_lock:
            entry = self._jitted.get(servable)
            if entry is None:
                combined = self.compress_transfer and not servable.model.needs_x64
                entry = self._build_entry(servable, combined)
                self._jitted[servable] = entry
        return entry

    def queue_load(self) -> tuple[int, int]:
        """(queued + staged candidates, configured queue capacity) — the
        elastic controller's queue-pressure signal (parallel/elastic.py):
        the fraction of the admission bound currently waiting is the
        backlog term of its load EWMA. One lock hold, called at most once
        per controller tick interval."""
        with self._cv:
            return (
                self._queued_candidates + self._staged_candidates,
                self.queue_capacity_candidates,
            )

    def upload_formats(self) -> dict[str, str]:
        """"name:version" -> how that servable's batches cross to the
        device, for every servable an entry was built for (after the
        ladder's warm-up: every loaded one)."""
        with self._jit_lock:
            return {
                f"{sv.name}:{sv.version}": "; ".join(sorted(formats))
                for sv, formats in self._upload_formats.items()
            }

    def _stamp(self, kind: str) -> dict[str, dict]:
        """"name:version" -> that kind's choice in the servable's entry as
        traced (the row's highest note where rungs or layers differ), for
        every servable whose step has such an operation. A custom run_fn
        traces its own entries, outside serving_gathers and
        serving_attention: XLA's path, no stamp."""
        row = next(k for k in SERVED_KERNELS if k.kind == kind)
        with self._jit_lock:
            return {
                f"{sv.name}:{sv.version}": max(
                    notes[kind], key=lambda n: (n[row.pallas_key] == "pallas", *row.rank(n))
                )
                for sv, notes in self._kernel_notes.items() if notes[kind]
            }

    # A kind's stamp by its older public name; the note's fields are its
    # `*_choice`'s (SERVED_KERNELS names each).
    gathers = functools.partialmethod(_stamp, "gather")
    attentions = functools.partialmethod(_stamp, "attention")
    groupeds = functools.partialmethod(_stamp, "grouped")
    delta_rules = functools.partialmethod(_stamp, "delta")
    ssds = functools.partialmethod(_stamp, "ssd")

    def kernel_stamps(self) -> dict[str, dict[str, dict]]:
        """`startup.<stamp>` for every row of SERVED_KERNELS."""
        return {k.stamp: self._stamp(k.kind) for k in SERVED_KERNELS}

    def products(self) -> dict[str, dict]:
        """"name:version" -> the products of an activation in pieces against
        a weight in that servable's entries as traced, every rung and variant
        added up: `{"ops", "fused_ops", "forms"}`, the operations (2 M k n a
        piece), those of them whose pieces meet in ONE product's accumulation
        (`sequence.FUSED_FORMS`), and the calls by form. For every servable
        whose step makes one (the sequence families); a custom run_fn traces
        its own entries, outside serving_attention: no stamp."""
        with self._jit_lock:
            return {f"{sv.name}:{sv.version}": product_summary(notes) for sv, notes in self._products.items() if notes}

    def pipeline_stats(self) -> dict:
        """Continuous-batching pipeline snapshot (ISSUE 9): configured
        depth/window, live in-flight occupancy (total and per bucket),
        high-water marks, and the readback-overlap fraction — the body of
        the /monitoring `pipeline` block and the dts_tpu_pipeline_*
        Prometheus series. Always available (core batcher state, not a
        gated plane)."""
        with self._cv:
            in_flight = len(self._inflight)
            dispatching = self._dispatching_since is not None
            per_bucket = {
                int(b): n for b, n in sorted(self._inflight_buckets.items())
                if n
            }
            pending = self._dispatch_pending
            peak = self.stats.inflight_peak
            window_waits = self.stats.inflight_window_waits
            overlap = self.stats.readback_overlap_fraction
        out = {
            "depth": self.pipeline_depth,
            "inflight_window": self.inflight_window,
            "in_flight": in_flight,
            "dispatching": dispatching,
            "dispatch_pending": pending,
            "per_bucket_in_flight": per_bucket,
            "inflight_peak": peak,
            "inflight_window_waits": window_waits,
            "readback_overlap_fraction": round(overlap, 4),
        }
        if self.buffer_ring is not None:
            out["buffer_ring"] = self.buffer_ring.snapshot()
        return out

    # ------------------------------------------- recovery plane (ISSUE 11)

    def wedge_age(self) -> float:
        """Seconds the OLDEST dispatched-or-in-flight batch has been
        outstanding (0.0 when idle/healthy) — the raw wedge clock the
        recovery watchdog escalates into a quarantine decision at its own
        (usually much lower) threshold, independent of the circuit
        breaker's fail-fast bound."""
        with self._cv:
            now = time.perf_counter()
            worst = 0.0
            if self._dispatching_since is not None:
                worst = now - self._dispatching_since
            for t0 in self._inflight.values():
                worst = max(worst, now - t0)
            return worst

    def capture_for_recovery(self) -> tuple[list, list]:
        """Quarantine capture: pop EVERY accepted-but-unanswered work item
        out of the batcher — queued items, staged groups, the group in the
        device stage, and every group executing-or-awaiting-readback — and
        clear the wedge bookkeeping so the rebuilt executor starts with a
        clean clock. Returns (queued_items, inflight_groups): queued items
        were never in a failing device call (replayed without a kill
        mark), in-flight groups were (the wedge IS their kill evidence).

        Safe against the stranded threads by construction: a wedged stage
        call whose sid was popped no-ops when it eventually runs, a stuck
        readback that eventually completes resolves futures the replay
        already resolved (set_result is first-wins, InvalidStateError
        guarded), and the pending-count decrements clamp at zero."""
        with self._cv:
            queued: list[_WorkItem] = []
            while self._items:
                it = self._items.popleft()
                self._queued_candidates -= it.n
                if not it.future.done():
                    queued.append(it)
            for sid in list(self._staged_groups):
                group, total = self._staged_groups.pop(sid)
                self._staged_candidates -= total
                queued.extend(it for it in group if not it.future.done())
            inflight: list[list[_WorkItem]] = []
            if self._dispatching_group is not None:
                live = [
                    it for it in self._dispatching_group
                    if not it.future.done()
                ]
                if live:
                    inflight.append(live)
                self._dispatching_group = None
            for group in self._inflight_groups.values():
                live = [it for it in group if not it.future.done()]
                if live:
                    inflight.append(live)
            self._inflight_groups.clear()
            self._inflight.clear()
            self._inflight_buckets.clear()
            self._dispatching_since = None
            self._dispatch_pending = 0
            if self._direct_item is not None:
                # A crossing stranded in a wedged device call: its thread
                # finds its count and its hold on the collector gone.
                self._direct_item.direct = False
                self._direct_item = None
            self._cv.notify_all()
        rc = self.row_cache
        if rc is not None:
            # Close EVERY in-flight row fill: the leaders of these flights
            # may be stranded in wedged threads the pool replacement
            # abandons (never unwinding through the abort paths), and a
            # foreign — or future — batch joining such a zombie flight
            # would hang to its deadline on a fill that can never land.
            # Replayed batches re-plan their rows fresh; the failed
            # waiters' clients failover on UNAVAILABLE like any
            # quarantine refusal.
            rc.fail_flights(DeviceQuarantinedError(
                "replica quarantined: in-flight row fills abandoned "
                "(the replayed batches re-plan their rows)"
            ))
        return queued, inflight

    def requeue_for_replay(self, items: list) -> None:
        """Re-enqueue captured/failed items at the FRONT of the queue (the
        replay path; they were accepted before anything now queued).
        Admission is deliberately bypassed — this work was already
        admitted once — and enqueue_t restarts so replay queue-wait is
        charged to the replay, while the propagated client deadline rides
        along unchanged (a waiter that gave up mid-recovery is shed
        exactly like any expired item)."""
        now = time.perf_counter()
        with self._cv:
            for it in reversed(items):
                it.enqueue_t = now
                self._items.appendleft(it)
                self._queued_candidates += it.n
            self._cv.notify_all()

    def replace_workers_for_recovery(self) -> None:
        """Abandon the dispatch/completer pools (a thread wedged inside a
        native device call cannot be preempted in-process — the pool
        around it can) and mint fresh ones so REPLAY has live workers.
        The old pools shut down without waiting: their idle threads exit,
        a stranded one finishes (or never does) against bookkeeping that
        capture_for_recovery already reset."""
        old_dispatcher, old_completers = self._dispatcher, self._completers
        if self._dispatcher is not None:
            self._dispatcher = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="batch-dispatch"
            )
        self._completers = ThreadPoolExecutor(
            # The constructor's sizing rule, not a hardcoded floor: a
            # recovered server must keep its configured readback
            # concurrency.
            max_workers=max(self.completion_workers, self.inflight_window),
            thread_name_prefix="batch-complete",
        )
        for pool in (old_dispatcher, old_completers):
            if pool is not None:
                pool.shutdown(wait=False)

    def revive_batching_thread(self) -> bool:
        """Clear a thread-death verdict and restart the batching loop if
        it is gone (recovery REINIT). True when a restart happened. The
        dying thread reports its own death BEFORE its final frames
        unwind, so on a RECORDED death a brief join lets it actually exit
        — without it the is_alive() check would read the corpse as a
        live loop. No death recorded = no join: a healthy loop blocked
        in _take must not add a fixed stall to every recovery cycle."""
        with self._cv:
            died = self._dead is not None
            self._dead = None
        t = self._thread
        if died and t.is_alive() and t is not threading.current_thread():
            t.join(timeout=2.0)
        if (
            self._started
            and not self._stopping
            and not self._thread.is_alive()
        ):
            self._thread = threading.Thread(
                target=self._loop, name="batcher", daemon=True
            )
            self._thread.start()
            return True
        return False

    def _note_thread_death(self, which: str, exc: BaseException) -> None:
        """A batcher-owned thread died from an unhandled exception: record
        the verdict so submit() fails fast, fail everything queued (no
        recovery plane) or hand the death to the recovery plane (armed —
        it revives the thread and replays), and wake every waiter."""
        err = BatcherThreadDead(
            f"batcher {which} thread died: {type(exc).__name__}: {exc}"
        )
        err.__cause__ = exc
        rec = self.recovery
        with self._cv:
            first = self._dead is None
            if first:
                self._dead = err
            self._cv.notify_all()
        # Hand the death to the recovery plane ONLY if it accepts it (a
        # stopped controller — drain in progress — returns False): queued
        # waiters are either replayed by the cycle or failed fast here,
        # never left hanging between the two.
        handled = rec is not None and first and rec.note_thread_death(err)
        if first and not handled:
            with self._cv:
                self._shed_queued(err)
                self._cv.notify_all()

    def _guard_worker_future(self, fut: Future, group: list, which: str) -> None:
        """Done-callback on dispatch/completer pool submissions: the stage
        bodies catch Exception, so anything surfacing HERE is an escape
        (BaseException, a bug in a finally) that would otherwise strand
        the group's waiters silently. Fail them fast and record the
        death."""
        exc = fut.exception()
        if exc is None:
            return
        for it in group:
            if not it.future.done():
                try:
                    it.future.set_exception(
                        BatcherThreadDead(
                            f"batcher {which} worker died: "
                            f"{type(exc).__name__}: {exc}"
                        )
                    )
                except InvalidStateError:
                    pass
        self._note_thread_death(which, exc)

    # ------------------------------------------------------------- internals

    def _build_entry(
        self, servable: Servable, combined: bool
    ) -> tuple[Callable, dict[str, str], bool]:
        """One callable serving every executable variant for `servable`.

        The returned fn accepts optional keywords beyond the positional
        (params, inputs[, layout]) contract jit_entry publishes:

        - out_keys: hashable tuple restricting which model outputs the
          EXECUTABLE returns (None = all). Dead outputs are DCE'd by XLA
          and never materialize in HBM, let alone cross the D2H link.
        - topk/n_valid: top-k output compaction — only the k best
          (score, index) pairs of the first n_valid rows come back.
          n_valid is traced, so executables key on (bucket, k) alone.

        Each distinct (layout, out_keys, topk, prune), or (out_keys, topk,
        prune) off the combined buffer, is a separate jit closure, cached
        here; the inner jax.jit trace cache still keys on buffer shape. The
        variant count is bounded by the
        distinct output_filter subsets clients actually send (the service
        validates filters against the signature, so the space is subsets of
        the signature's outputs — a handful), not by traffic volume. All
        float32 outputs are downcast to the configured wire dtype on-device,
        and the full-fp32 row bytes are recorded at trace time so the
        bytes_download_full_f32 counter charges an honest baseline.
        """
        model = servable.model
        spec = transfer_spec(model) if self.compress_transfer else {}
        apply = model.apply
        # x64 graphs may carry f64 outputs whose downcast would not be a
        # transparent wire encoding; they keep full-precision outputs.
        wire = None if model.needs_x64 else self._wire_dt
        score_key = model.score_output
        rowbytes = self._out_row_bytes.setdefault(servable, [0])
        # A list, appended to at trace time and read by upload_formats()
        # from another thread: an append never breaks a reader's iteration.
        formats = self._upload_formats[servable] = []
        # Likewise what each SERVED_KERNELS choice was, noted while an
        # executable is traced (every variant and every rung notes again).
        # This entry runs on one chip (a mesh executor is a run_fn and never
        # gets here), so it is the one trace in which a choice may take its
        # kernel (embeddings.serving_gathers, sequence.serving_attention).
        notes = self._kernel_notes[servable] = {k.kind: [] for k in SERVED_KERNELS}
        self._kernel_kinds.pop(servable, None)
        products = self._products[servable] = []

        def traced(p, batch):
            # `apply` as it stands when an executable is traced: the step
            # that counts, where the model has one (below).
            with serving_gathers(notes["gather"]), serving_attention(
                    notes["attention"], grouped=notes["grouped"], delta=notes["delta"],
                    ssd=notes["ssd"], products=products, conv=notes["conv"]):
                out = apply(p, batch)
            self._kernel_kinds[servable] = tuple(
                k for k in SERVED_KERNELS
                if any(note[k.pallas_key] == "pallas" for note in notes[k.kind])
            )
            return out

        if not combined:
            formats.append("per key: " + (", ".join(
                f"{k} {v}" for k, v in sorted(spec.items())) or "as sent"))

        def finish(out, out_keys):
            # Runs at TRACE time: record the full-fp32 readback baseline
            # for this servable (bytes/row across ALL outputs), then apply
            # output selection + the on-device wire downcast.
            n = next(iter(out.values())).shape[0]
            rb = 0
            for v in out.values():
                per_row = max(int(np.prod(v.shape)) // max(n, 1), 1)
                width = 4 if jnp.issubdtype(v.dtype, jnp.floating) else v.dtype.itemsize
                rb += per_row * width
            rowbytes[0] = max(rowbytes[0], rb)
            if out_keys is not None:
                picked = {k: v for k, v in out.items() if k in out_keys}
                out = picked or out  # never trace an empty output pytree
            return compact_outputs_device(out, wire)

        # A step that counts (models/base.py Model.apply_stats): the counters
        # leave the executable beside the outputs that were asked for, and
        # the dispatch stage, which reads `model.step_stats`, takes them out.
        if model.apply_stats is not None:
            apply, finish = _with_step_stats(model.apply_stats, finish)

        variants: dict[tuple, Callable] = {}

        def variant_jit(unpack, out_keys, topk, prune):
            """The jitted step of one variant: `unpack` (the transfer's
            decompression, traced into the executable as its `unpack` scope),
            the model, then the variant's selection."""
            if topk:
                select = cascade_prune_device if prune else topk_compact_device

                def run(p, b, nv):
                    out = traced(p, unpack(b))
                    finish(out, None)  # records the baseline
                    return select(out[score_key], nv, topk, wire)
            else:
                def run(p, b):
                    return finish(traced(p, unpack(b)), out_keys)
            # The executable's name in a profiler trace (`jit_<name>`, the
            # host plane's `PjitFunction(<name>)`): model and variant.
            variant = "prune" if prune else "topk" if topk else "score"
            run.__name__ = re.sub(r"\W", "_", f"{servable.name}_{variant}")
            return step_jit(model, run)

        if combined:
            # One uint32 buffer per batch = ONE host->device transfer
            # instead of one per input; the layout split, the planes'
            # shifts and masks and the bitcasts are traced into the
            # executable (ops/transfer.py).
            # (x64 models keep the per-key path: their int64 inputs
            # must cross the boundary as int64, not raw bytes plus an
            # in-graph bitcast that enable_x64 scoping complicates.)
            #
            # The layout is CLOSED OVER per distinct variant key (a
            # handful per servable and bucket: it names the batch's rows,
            # which the buffer's length alone does not) instead
            # of riding static_argnums: hashing that nested tuple on
            # every call cost ~175 us/batch of pure dispatch overhead
            # (round-4 microbench: 426 -> 251 us/call arg processing),
            # and each variant's jit compiles for its one buffer shape.
            def fn(params, buf, layout, out_keys=None, topk=0, n_valid=None, prune=False,
                   _cache=variants):
                key = (layout, out_keys, topk, prune)
                jfn = _cache.get(key)
                if jfn is None:
                    if (fmt := describe_layout(layout)) not in formats:
                        formats.append(fmt)
                    jfn = _cache[key] = variant_jit(
                        lambda b: unpack_device_combined(b, layout), out_keys, topk, prune)
                return jfn(params, buf, n_valid) if topk else jfn(params, buf)
        else:
            def unpack(b):
                return unpack_device(b, spec) if spec else b

            def fn(params, packed, out_keys=None, topk=0, n_valid=None, prune=False,
                   _cache=variants):
                key = (out_keys, topk, prune)
                jfn = _cache.get(key)
                if jfn is None:
                    jfn = _cache[key] = variant_jit(unpack, out_keys, topk, prune)
                return jfn(params, packed, n_valid) if topk else jfn(params, packed)

        if model.needs_x64:
            # Trace AND call inside enable_x64: graph-executor models
            # (interop/graph_exec.py) carry int64 feature ids that the
            # default 32-bit canonicalization would silently truncate at
            # the jit boundary — before the graph's own hashing/mod runs.
            base = fn

            def fn(params, batch, *args, _base=base, **kwargs):
                with enable_x64():
                    return _base(params, batch, *args, **kwargs)

        return (fn, spec, combined)

    def _generic_reason(self, servable: Servable) -> str | None:
        """Why every batch of `servable` takes the generic pad+pack path,
        None when the native assembler can take them (a batch may still
        fall back by what it holds: _fused_ctx)."""
        from .. import native

        if self._run_fn is not None:
            return "custom run_fn"
        if not self.compress_transfer:
            return "compress_transfer off"
        if servable.model.needs_x64:
            return "x64 model"
        if not native.available():
            return "no native library"
        if not self.jit_entry(servable)[2]:
            return "per-key upload"
        return None

    def assemblers(self) -> dict[str, str]:
        """"name:version" -> "native" or "generic: <why>", for every
        servable an entry was built for: /monitoring's `startup.assembler`,
        beside `upload_format`."""
        with self._jit_lock:
            servables = list(self._jitted)
        out = {}
        for sv in servables:
            why = self._generic_reason(sv)
            out[f"{sv.name}:{sv.version}"] = (
                "native" if why is None else f"generic: {why}"
            )
        return out

    def _fused_ctx(
        self, servable: Servable, parts: dict[str, list[np.ndarray]],
        bucket: int,
    ) -> dict | None:
        """Eligibility + host-side metadata for the native batch assembler;
        None = the generic pad+pack path runs instead. Pure bookkeeping (no
        device work), so it runs on the batcher thread — the device stage
        itself (_execute_fused) rides the dispatch pipeline.

        hostops.cc assemble_batch reads each request's arrays once and
        writes the final padded word buffer directly, for any combined
        layout — the generic path makes 3 full passes (pad copy, fold,
        pack) with 2 temporaries per batch. The buffer is bit-identical to
        pack_host_combined over the padded batch (pinned by
        tests/test_fused_pack.py), so it shares the same compiled
        executables and the same content-cache semantics (keyed per-part
        here; distinct tag keeps the two key schemes apart). What the
        layout is comes from what the batch holds, as on the generic path:
        per input the spec's packed width, else the parts' own dtype
        (int32 for int64 ids the model folds on the host)."""
        from .. import native

        if self._generic_reason(servable) is not None:
            return None
        model = servable.model
        fn, spec, _combined = self.jit_entry(servable)
        fold = (
            {"feat_ids": model.config.vocab_size}
            if model.folds_ids_on_host and "feat_ids" in parts else {}
        )
        # The padded batch's arrays as combined_layout would see them, by
        # zero-row stand-ins of the first part's trailing shape and dtype.
        shown = {}
        for key, key_parts in parts.items():
            first = key_parts[0]
            if first.ndim < 1 or 0 in first.shape[1:]:
                return None
            dtype = first.dtype
            if key in fold and dtype == np.int64:
                dtype = np.int32
            shown[key] = np.empty((0,) + first.shape[1:], dtype)
        if not combined_supported(shown):
            return None
        layout = combined_layout(shown, spec, rows=bucket)
        for key, bits, trailing, dtype_str in layout[1]:
            readable = native.part_kinds(bits, dtype_str, key in fold)
            for part in parts[key]:
                if part.dtype not in readable or part.shape[1:] != trailing:
                    return None
        return {
            "servable": servable,
            "fn": fn,
            "layout": layout,
            "fold": fold,
            "parts": parts,
        }

    def _execute_fused(
        self, ctx: dict, bucket: int,
        out_keys: tuple[str, ...] | None, topk: int, n_valid,
        prune: bool = False,
    ):
        """Device stage of the native path: content cache / native assembly
        / upload / jit call. `batch.cache` covers digest (while the cache
        probes), assembly and upload in EVERY batch, as the generic path's
        does, with `batch.fusedpack` inside it around the native call
        alone and `batch.fusedpack_native` the pass's own time by its own
        clock; `batch.jitcall` follows."""
        from .. import native

        servable, fn, layout = ctx["servable"], ctx["fn"], ctx["layout"]
        fold, parts = ctx["fold"], ctx["parts"]

        def build():
            with request_trace.span("batch.fusedpack"):
                buf, native_ns = native.assemble_batch(layout, parts, fold)
            # The pass by its own clock inside the span above: the rest of
            # the span is the wrapper, ctypes and this thread's wait to take
            # the interpreter lock back, once a batch. Aggregate only.
            request_trace.add_many((("batch.fusedpack_native", native_ns * 1e-9, 1),))
            return buf

        cache = self.input_cache
        with request_trace.span("batch.cache"):
            if cache is not None and not cache.bypassed:
                # Per-part content digests (same digest primitive, same
                # total bytes as the group digest) + padded geometry.
                # The fold's vocab is IN the tag: the digests are over RAW
                # ids, and the stored buffer's fold depends on it — two
                # servables sharing a batcher but not a vocab must
                # never share entries (review finding; the generic
                # path's digests are post-fold so it gets this free).
                key = (f"fused:{layout}:{sorted(fold.items())}",) + tuple(
                    cache._key(k, a) for k in sorted(parts) for a in parts[k]
                )
                buf = cache._lookup(key, build)
            else:
                if cache is not None:
                    cache._note_bypassed()
                buf = build()
        # np.int32, matching _execute and warmup(): a raw Python int has a
        # different jax aval (weak type) and would force a fresh trace on
        # the first live fused top-k batch despite warmup's precompile.
        n_valid = None if not topk else np.int32(n_valid)
        with request_trace.span("batch.jitcall"):
            return fn(servable.params, buf, layout,
                      out_keys=out_keys, topk=topk, n_valid=n_valid, prune=prune)

    @staticmethod
    def _fold_host(servable: Servable, arrays: dict) -> dict:
        """Deferred per-request fold (prepare_inputs fold_ids=False): one
        native fold over the whole padded batch. Runs BEFORE the content
        digest, so cache keys are over the same folded bytes as the
        eager-fold path produced. Shared by _execute and the elastic
        warmup path (which calls the run_fn directly and must hand it the
        exact dtype live traffic carries — an unfolded int64 batch would
        warm an executable no live batch ever hits)."""
        ids = arrays.get("feat_ids")
        if ids is not None and ids.dtype == np.int64 and servable.model.folds_ids_on_host:
            arrays = dict(arrays)
            arrays["feat_ids"] = fold_ids_host(ids, servable.model.config.vocab_size)
        return arrays

    def _execute(
        self,
        servable: Servable,
        arrays: dict[str, np.ndarray],
        out_keys: tuple[str, ...] | None = None,
        topk: int = 0,
        n_valid: int | None = None,
        prune: bool = False,
    ):
        """Device stage for one padded batch: fold, content cache, pack,
        upload, jit call. out_keys/topk/n_valid ride through to the jitted
        entry (output selection and top-k compaction are traced into the
        executable)."""
        arrays = self._fold_host(servable, arrays)
        if self._run_fn is not None:
            if getattr(self._run_fn, "supports_out_keys", False):
                # Mesh executor (parallel/executor.py): the group's
                # output-selection union rides through so unwanted outputs
                # are DCE'd on-mesh and never cross the gathered D2H link
                # — the same PR-1 compaction the single-chip entries get.
                return self._run_fn(servable, arrays, out_keys=out_keys)
            return self._run_fn(servable, arrays)
        fn, spec, combined = self.jit_entry(servable)
        if combined and not combined_supported(arrays):
            # Rare servable whose inputs cannot ride the word buffer (string/
            # bool/8-byte tensors): rebuild the per-key entry once and pin
            # it (same spec — only the transfer packaging changes).
            with self._jit_lock:
                entry = self._build_entry(servable, combined=False)
                self._jitted[servable] = entry
            fn, spec, combined = entry
        n_valid = None if not topk else np.int32(n_valid)
        # x64 models need the context around the UPLOADS too: device_put
        # (inside the input cache) canonicalizes, and an int64 batch put
        # outside the context reaches the x64-traced executable as int32.
        ctx = enable_x64() if servable.model.needs_x64 else _NULL_CTX
        with ctx:
            if combined:
                layout = combined_layout(arrays, spec)
                cache = self.input_cache
                if cache is not None:
                    # Digest the RAW arrays (a content hit skips pack AND
                    # concat AND upload); layout in the tag keeps distinct
                    # packings of identical bytes apart.
                    with request_trace.span("batch.cache"):
                        buf = cache.get_or_put_group(
                            arrays,
                            build=lambda: pack_host_combined(arrays, spec),
                            tag=str(layout),
                        )
                else:
                    buf = pack_host_combined(arrays, spec)
                inputs = (buf, layout)
            elif self.input_cache is not None:
                # Digest BEFORE packing: a content hit skips both the upload
                # and the pack (u24/bf16) work.
                with request_trace.span("batch.cache"):
                    inputs = ({
                        k: self.input_cache.get_or_put(
                            k, v,
                            pack=(lambda a, _k=k: pack_host({_k: a}, spec)[_k]) if spec else None,
                            pack_tag=spec.get(k, "") if spec else "",
                        )
                        for k, v in arrays.items()
                    },)
            else:
                inputs = (pack_host(arrays, spec) if spec else arrays,)
            with request_trace.span("batch.jitcall"):
                return fn(servable.params, *inputs,
                          out_keys=out_keys, topk=topk, n_valid=n_valid, prune=prune)

    def _shed_expired_locked(self, it: _WorkItem) -> bool:
        """True when `it`'s propagated client deadline already expired —
        the item is failed (DEADLINE_EXCEEDED at the RPC layer) instead of
        dispatched: its waiter stopped listening, so device time spent on
        it would only delay the still-live work behind it. Caller holds
        _cv and has already popped the item."""
        if it.deadline_t is None or time.perf_counter() < it.deadline_t:
            return False
        self.stats.deadline_sheds += 1
        if not it.future.done():
            try:
                it.future.set_exception(
                    RequestDeadlineError(
                        "client deadline expired while queued "
                        f"({time.perf_counter() - it.enqueue_t:.3f}s); "
                        "shed before dispatch"
                    )
                )
            except InvalidStateError:
                # The service-side wait times out at the SAME instant this
                # deadline expires and cancels the future; losing that race
                # must not kill the batcher thread (same guard as
                # _complete's set_result).
                pass
        return True

    def _drop_stale_locked(self, it: _WorkItem) -> bool:
        """Staleness classification for a just-popped queue item — the ONE
        implementation both _take and _coalesce_next use. Cancelled waiter:
        skip the work, and when the item's propagated deadline has actually
        EXPIRED count it as a deadline shed (the RPC wait expires at the
        same instant and withdraws the future first — the common ordering
        over gRPC; a cancellation BEFORE expiry, e.g. the service's 120s
        bound firing under a looser client deadline, is not one).
        Otherwise defer to the expiry shed. True = drop. Caller holds _cv
        and has adjusted _queued_candidates."""
        if it.future.cancelled():
            if it.deadline_t is not None and time.perf_counter() >= it.deadline_t:
                self.stats.deadline_sheds += 1
            return True
        return self._shed_expired_locked(it)

    def _take(self) -> _WorkItem | None:
        """Pop the next live queued item, blocking; None on shutdown after
        the queue drains (every accepted item is still served)."""
        with self._cv:
            while True:
                while self._items:
                    it = self._items.popleft()
                    self._queued_candidates -= it.n
                    if self._drop_stale_locked(it):
                        continue  # cancelled waiter or expired deadline
                    return it
                if self._stopping:
                    return None
                # No work arrived: the transport/client-bound share. Parked
                # here, the collector has no batch open: what a direct
                # crossing asks (_crosses_direct_locked), and sleeps through.
                self._collector_parked = True
                try:
                    self._wait(
                        "queue_empty",
                        until=lambda: self._items or self._stopping,
                    )
                finally:
                    self._collector_parked = False

    # The utilization ledger's gap cause for a wait (its names predate the
    # `wait.*` phases; /utilz keeps them). `window` is the dispatch
    # thread's and has none: the ledger follows the collector.
    _LEDGER_CAUSE = {
        "queue_empty": "queue_empty",
        "coalesce": "host_pack",
        "pipeline": "readback_wait",
    }

    def _wait(
        self, cause: str, timeout: float | None = None,
        until: Callable | None = None,
    ) -> None:
        """One wait on the batcher's condition (the caller holds `_cv`),
        timed once: phase `wait.<cause>` of request_trace, the same name
        on the profiler's clock, and, under `_LEDGER_CAUSE`'s name for it,
        the utilization ledger when armed. With `until`, the wait is taken
        again, `timeout` at a time, until `until()` holds: every completion
        notifies the condition, and a wait cut into the pieces between them
        would cover no idle gap of the device. The caller tests its
        condition again either way. Clock reads only on the waiting path."""
        phase = "wait." + cause
        ledger_cause = self._LEDGER_CAUSE.get(cause)
        util = self.utilization if ledger_cause is not None else None
        token = util.wait_begin(ledger_cause) if util is not None else None
        t0 = time.perf_counter()
        try:
            with tracing.annotation(phase):
                self._cv.wait(timeout)
                while until is not None and not until():
                    self._cv.wait(timeout)
        finally:
            request_trace.add(phase, time.perf_counter() - t0)
            if token is not None:
                util.wait_end(token)

    def _holds_open(self, native: bool, busy: int) -> bool:
        """Whether a batch past its deadline, the queue empty, stays open
        for what arrives while the pipeline is occupied (`busy` batches
        staged, in their stage or in flight; the caller holds `_cv`).

        Always while the pipeline is saturated (>= pipeline_depth): the
        batch would queue behind device work regardless. Where the native
        assembler builds the batch on the dispatch thread (`native`), the
        collector has no assembly of its own to overlap with the stage
        before, so a batch closed early is only a smaller batch, and two
        more cases hold it: a stage staged or running on the dispatch
        thread (the batch would wait behind it, closed to later arrivals);
        and a batch in flight while requests arrive at least as fast as
        batches cross the pipeline (Little's law: on average one more
        joins before the pipeline drains), which is when a batch's fixed
        host cost is worth sharing. Below that rate a lone request
        dispatches at once and overlaps the batch in flight (on its own
        handler thread where that may block: _crosses_direct_locked reads
        the same two averages from the other side)."""
        if busy >= self.pipeline_depth:
            return True
        if not native or not busy:
            return False
        if self._dispatch_pending:
            return True
        gap, crossing = self._arrival_gap_s, self._traversal_s
        return gap is not None and crossing is not None and crossing >= gap

    def _coalesce_next(
        self, item: _WorkItem, total: int, deadline: float, native: bool = False,
    ) -> _WorkItem | None:
        """Next same-target item within the (pipeline-extended) window, or
        None. The head item stays put when it doesn't match — deque order is
        preserved (the old SimpleQueue requeue pushed it to the BACK,
        reordering traffic).

        Past `deadline` the wait continues only while _holds_open says the
        pipeline is occupied (and none wedged): saturated, so the next
        dispatch would queue behind device work regardless and the extra
        fill time costs no latency; or, for a natively assembled batch
        (`native`), the dispatch thread busy or a batch in flight under
        load. Completion of any stage or in-flight batch notifies this
        wait, ending the free-ride the moment dispatch could actually
        start."""
        free_ride_counted = False
        with self._cv:
            while True:
                while not self._items:
                    now = time.perf_counter()
                    if self._stopping:
                        return None
                    if now < deadline:
                        # Coalesce fill: the host deliberately holds the
                        # batch open (the ledger charges host_pack, clamped
                        # out where the pipeline keeps the device busy).
                        self._wait("coalesce", deadline - now)
                        continue
                    busy = len(self._inflight) + self._dispatch_pending
                    if not self._holds_open(native, busy) or self._wedged_for(now):
                        return None
                    # Free-riding the busy pipeline; a completion notifies.
                    # Bounded wait: the wedge clock advances with wall time
                    # alone, so never sleep unboundedly on the condition.
                    # Counted once per episode, not per poll iteration.
                    if not free_ride_counted:
                        self.stats.fill_waits += 1
                        free_ride_counted = True
                    # Pipeline saturated: dispatch blocked behind in-flight
                    # readbacks (the ledger's idle cause readback_wait).
                    self._wait(
                        "pipeline", 0.005,
                        until=lambda: (
                            self._items or self._stopping
                            or not self._holds_open(
                                native,
                                len(self._inflight) + self._dispatch_pending,
                            )
                            or self._wedged_for(time.perf_counter())
                        ),
                    )
                nxt = self._items[0]
                if nxt.future.cancelled() or (
                    nxt.deadline_t is not None
                    and time.perf_counter() >= nxt.deadline_t
                ):
                    self._items.popleft()
                    self._queued_candidates -= nxt.n
                    self._drop_stale_locked(nxt)
                    continue
                if (
                    nxt.servable is item.servable
                    and not nxt.solo
                    # Bisection halves (recovery plane) only merge with
                    # their OWN half: a half that re-absorbed the other
                    # half's rows would never isolate the poison.
                    and nxt.bisect_key == item.bisect_key
                    and nxt.arrays.keys() == item.arrays.keys()
                    and total + nxt.n <= self.max_batch_candidates
                ):
                    self._items.popleft()
                    self._queued_candidates -= nxt.n
                    return nxt
                return None

    def _loop(self) -> None:
        try:
            self._loop_inner()
        except BaseException as exc:  # noqa: BLE001 — thread-death watchdog
            # An unhandled exception here would silently kill the batching
            # thread and leave every submitter hanging on the condition
            # variable until its RPC deadline. Fail fast and visibly
            # instead (BatcherThreadDead), and let the recovery plane —
            # when armed — revive the thread and replay the shed work.
            self._note_thread_death("batching", exc)

    def _loop_inner(self) -> None:
        while True:
            item = self._take()
            if item is None:
                return
            group = [item]
            total = item.n
            deadline = item.enqueue_t + self.max_wait_s
            # Whether the dispatch thread assembles this servable's batches
            # (then the collector holds a batch open longer: _holds_open).
            native = self._generic_reason(item.servable) is None
            # Coalesce same-servable work until the deadline or size cap.
            # Solo items (streamed sub-batches) dispatch alone: merging
            # them would undo the very split that lets their readbacks
            # complete (and flush) independently.
            while total < self.max_batch_candidates and not item.solo:
                nxt = self._coalesce_next(item, total, deadline, native)
                if nxt is None:
                    break
                group.append(nxt)
                total += nxt.n
            closed_t = time.perf_counter()
            for it in group:
                it.closed_t = closed_t
            with self._cv:
                if self._direct_item is not None:
                    # A handler thread is crossing direct. _holds_open held
                    # this batch for its stage where a batch is held; a solo
                    # item, a full batch and a generic-path one come here
                    # early, and wait the rest of the crossing out: _dispatch
                    # and _run_stage run for one group at a time.
                    self._wait(
                        "pipeline", 0.005,
                        until=lambda: self._direct_item is None,
                    )
            self._dispatch(group, total)

    def _dispatch(self, group: list[_WorkItem], total: int) -> None:
        """Host-side batch assembly (batcher thread), then the device stage
        — handed to the dispatch thread in pipelined mode so this thread
        returns to collecting+padding batch k+1 while batch k's
        pack/upload/jit-call proceeds (and batch k-1 executes on device).

        A direct crossing (_cross_direct) calls this on a handler thread
        with its one item and runs the stage inline. It starts only while
        the collector is parked and nothing is staged, and the collector
        starts no _dispatch until it is over (_loop_inner), so this runs for
        one group at a time, as it always has; beside it may run the tail
        of the stage before, as beside the collector's ever."""
        # Per-request tracing: one phase sink per batch — request_trace's
        # existing call sites (batch.pad here; cache/pack/jitcall/readback
        # on the stage threads) land in it once and are replayed onto
        # EVERY member request's span, so co-batched requests each carry
        # the full batch timeline. None = nobody in this group is traced.
        phases: list | None = (
            [] if tracing.enabled() and any(it.span is not None for it in group)
            else None
        )
        # Buffer ring: padded-batch buffers acquired here are
        # released only after the batch fully completes (the completer's
        # finally) or on a pre-device failure path — never while the async
        # H2D upload could still be reading them.
        ring = self.buffer_ring
        ring_bufs: list = []

        def pad_buffer(shape: tuple, dtype) -> np.ndarray:
            if ring is None:
                return np.empty(shape, dtype)
            buf = ring.acquire(shape, dtype)
            ring_bufs.append(buf)
            return buf

        row_ctx: _RowContext | None = None
        try:
            bucket = bucket_for(total, self.buckets)
            first = group[0]
            # Union of the group's wanted outputs; None on any item = all.
            # Computed up front: output selection is traced into the jitted
            # entry, and the top-k gate needs it.
            wanted: set[str] | None = set()
            for it in group:
                if it.output_keys is None:
                    wanted = None
                    break
                wanted.update(it.output_keys)
            wanted_key = tuple(sorted(wanted)) if wanted is not None else None
            # Top-k output compaction: single-request retrieval-style
            # batches whose caller asked for exactly the score vector. A
            # coalesced group cannot ride it (top-k over concatenated
            # requests would mix candidates across requests).
            topk, n_valid, prune = 0, None, False
            if (
                self.output_top_k
                and self._run_fn is None
                and len(group) == 1
                and not first.warmup
                and 0 < self.output_top_k < first.n
                and wanted_key == (first.servable.model.score_output,)
                and not first.servable.model.needs_x64
            ):
                topk, n_valid = self.output_top_k, first.n
            # Cascade stage-1 prune (ISSUE 19): a prune submit rides the
            # same on-device selection machinery as top-k compaction (and
            # reuses its k/n_valid plumbing) but returns the survivor
            # pairs PLUS the wire-dtype stage-1 vector. Prune items are
            # solo, so the group is single-request by construction; when
            # the variant cannot arm (custom run_fn, x64 model, k >= n)
            # the batch runs as a normal full-vector execution and the
            # orchestrator selects survivors on host — counted so the
            # fallback rate is visible.
            if first.prune_k and not first.warmup:
                if (
                    self._run_fn is None
                    and len(group) == 1
                    and 0 < first.prune_k < first.n
                    and wanted_key == (first.servable.model.score_output,)
                    and not first.servable.model.needs_x64
                ):
                    topk, n_valid, prune = first.prune_k, first.n, True
                else:
                    self.stats.prune_fallback_batches += 1
            # Intra-batch duplicate collapse (cache/dedup.py): exact-bytes
            # duplicate rows across the combined batch execute ONCE; the
            # completer scatters the unique rows' scores back into every
            # requester's original order. Skipped for top-k batches (the
            # returned indices address original rows) and warmup groups
            # (all-zero warmup rows would collapse to one and compile the
            # wrong bucket).
            scatter = None
            dedup_cats = None
            # Row-granular score cache (ISSUE 14): digest + look up every
            # row after collect, pack/dispatch only the cold ones. The
            # plan subsumes the dedup block below (its unique-collapse
            # runs inside _plan_rows when [cache] dedup is armed, and
            # intra-batch duplicates additionally coalesce onto one row
            # flight), so exactly one of the two paths runs per batch.
            # Top-k batches are excluded (the returned indices address
            # original rows) and warmup groups (all-zero rows would
            # collapse and poison the cache with compile traffic).
            rc = self.row_cache
            if (
                rc is not None
                and not topk
                and not any(it.warmup for it in group)
            ):
                with (tracing.collect_phases(phases) if phases is not None
                      else _NULL_CTX), request_trace.span("cache.row_lookup"):
                    row_ctx = self._plan_rows(rc, group, total, wanted_key)
                self.stats.row_batches += 1
                self.stats.rows_requested += total
                self.stats.rows_executed += row_ctx.n_cold
                if row_ctx.n_cold == 0:
                    # Every row answered from cache (or a foreign
                    # in-flight fill): no device work at all. Delivery
                    # rides a completer so the batching thread never
                    # blocks on another batch's fill.
                    self.stats.row_full_hit_batches += 1
                    if phases is not None:
                        _replay_group_phases(group, phases)
                    self._completers.submit(
                        self._complete_rows_only, group, row_ctx
                    ).add_done_callback(
                        lambda f, g=group: self._guard_worker_future(
                            f, g, "completer"
                        )
                    )
                    return
                if row_ctx.passthrough:
                    # Every row cold and distinct: execution covers the
                    # original batch in original order — the normal
                    # pad/fused paths serve it from the concat the plan
                    # already built; only the fill rides along.
                    dedup_cats = row_ctx.exec_arrays
                else:
                    bucket = bucket_for(row_ctx.n_cold, self.buckets)
                    dedup_cats = row_ctx.exec_arrays
            elif (
                self.dedup
                and not topk
                and total > 1
                and not any(it.warmup for it in group)
            ):
                with (tracing.collect_phases(phases) if phases is not None
                      else _NULL_CTX), request_trace.span("batch.dedup"):
                    uniq, scatter, dedup_cats = collapse_rows(
                        {k: [it.arrays[k] for it in group] for k in first.arrays}
                    )
                if scatter is not None:
                    n_unique = next(iter(uniq.values())).shape[0]
                    bucket = bucket_for(n_unique, self.buckets)
                    self.stats.dedup_batches += 1
                    self.stats.dedup_rows_collapsed += total - n_unique
            # A collapsed batch skips the native assembler (the dedup plane
            # pads its unique rows below). Otherwise it reads the requests'
            # own arrays, or, where a screen already concatenated them
            # (all-unique dedup, a row-cache plan's rows to execute), that
            # concat as a single part: its output is row-sequential, so one
            # pre-concatenated part packs bit-identically to the part list,
            # and the screen's concat is never discarded.
            fused = None
            if scatter is None:
                fused = self._fused_ctx(
                    first.servable,
                    {k: [v] for k, v in dedup_cats.items()}
                    if dedup_cats is not None
                    else {k: [it.arrays[k] for it in group] for k in first.arrays},
                    bucket,
                )
            batched = None
            if fused is None and (scatter is not None or dedup_cats is not None):
                # Pad from the dedup screen's arrays: the unique rows when
                # duplicates collapsed, else the concatenated batch
                # collapse_rows built anyway (all-unique outcome) — never
                # a SECOND concat of the same parts.
                src = uniq if scatter is not None else dedup_cats
                batched = {}
                with (tracing.collect_phases(phases) if phases is not None
                      else _NULL_CTX), request_trace.span("batch.pad"):
                    for k, arr in src.items():
                        if arr.shape[0] == bucket:
                            # Owned either way: a multi-part concat, a
                            # first-occurrence gather, or a single item's
                            # prepare_inputs-owned array (same passthrough
                            # contract as the generic pad path below).
                            batched[k] = arr
                            continue
                        out = pad_buffer((bucket,) + arr.shape[1:], arr.dtype)
                        out[: arr.shape[0]] = arr
                        out[arr.shape[0]:] = 0  # padding rows
                        batched[k] = out
            elif fused is None:
                keys = list(first.arrays.keys())
                batched = {}
                with (tracing.collect_phases(phases) if phases is not None
                      else _NULL_CTX), request_trace.span("batch.pad"):
                    for k in keys:
                        parts = [it.arrays[k] for it in group]
                        if len(parts) == 1 and parts[0].shape[0] == bucket:
                            # Safe to pass through uncopied: prepare_inputs
                            # guarantees item arrays never alias caller buffers.
                            batched[k] = parts[0]
                            continue
                        # Single allocation + one copy per part (no concat temporaries).
                        # Mixed dtypes (an int64 wire request coalesced with a
                        # pre-folded int32 direct submit) widen, never wrap.
                        dt = parts[0].dtype
                        if any(p.dtype != dt for p in parts):
                            dt = np.result_type(*(p.dtype for p in parts))
                        out = pad_buffer((bucket,) + parts[0].shape[1:], dt)
                        off = 0
                        for p in parts:
                            out[off : off + p.shape[0]] = p
                            off += p.shape[0]
                        out[off:] = 0  # padding rows
                        batched[k] = out
        except Exception as exc:  # assembly failed: fail the group, keep serving
            if ring is not None and ring_bufs:
                ring.release(ring_bufs)
            if row_ctx is not None:
                # Close the plan's row flights: foreign batches waiting on
                # this batch's cold rows fail now instead of hanging.
                row_ctx.abort(exc)
            for it in group:
                if not it.future.done():
                    it.future.set_exception(exc)
            return
        if self._dispatcher is None or first.direct:
            # No dispatch thread, or a direct crossing, which is its own.
            self._run_stage(
                None, group, total, bucket, wanted, wanted_key,
                topk, n_valid, fused, batched, phases, scatter, ring_bufs,
                row_ctx, prune,
            )
            return
        with self._cv:
            self._staged_seq += 1
            sid = self._staged_seq
            self._staged_groups[sid] = (group, total)
            self._staged_candidates += total
            self._dispatch_pending += 1
        self._dispatcher.submit(
            self._run_stage, sid, group, total, bucket, wanted, wanted_key,
            topk, n_valid, fused, batched, phases, scatter, ring_bufs,
            row_ctx, prune,
        ).add_done_callback(
            # Thread-death guard: _run_stage catches Exception broadly,
            # so only a BaseException (or a bug in its own finally) can
            # escape — which would leave this group's waiters hanging and
            # the stage slot poisoned. Fail them fast instead.
            lambda f, g=group: self._guard_worker_future(f, g, "dispatch")
        )
        # Backpressure: up to pipeline_depth-1 groups may queue behind the
        # running stage — enough to keep the pipeline full (assembly of
        # k+1 overlaps the stage of k; deeper depths stage further ahead),
        # bounded so a slow device never lets the batcher thread run
        # arbitrarily far ahead of admission control. Depth 1 serializes
        # assembly against the stage. Bounded waits: the wedge clock
        # advances on wall time.
        with self._cv:
            if self._dispatch_pending >= self.pipeline_depth and not self._stopping:
                self._wait("pipeline", 0.005, until=lambda: (
                    self._dispatch_pending < self.pipeline_depth
                    or self._stopping
                ))

    def _plan_rows(
        self, rc, group: list[_WorkItem], total: int,
        wanted_key: tuple | None,
    ) -> _RowContext:
        """Row-granular cache consultation for one collected batch: build
        the concatenated batch, digest each row (dedup-unique rows only
        when [cache] dedup is armed — the collapse_rows machinery
        generalized), and classify every slot hit / foreign-flight waiter
        / cold. The returned context carries the gathered COLD rows as
        the batch to execute and the inverse map the completer scatters
        through. Runs on the batcher thread (the dedup precedent); the
        per-row blake2b digests are the plane's host cost, paid only
        while it is armed."""
        from ..cache.row_cache import digest_rows, row_structure_header

        first = group[0]
        # np.concatenate widens mixed dtypes exactly like the pad loop
        # (an int64 wire request coalesced with a pre-folded int32 direct
        # submit), so row identity is over the bytes the device would see.
        cats = {
            k: (np.concatenate([it.arrays[k] for it in group])
                if len(group) > 1 else first.arrays[k])
            for k in first.arrays
        }
        blob = canonical_rows(cats)
        header = row_structure_header(cats)
        digests_all = digest_rows(blob, header)
        uniq_rows = None
        inverse = None
        if self.dedup and total > 1:
            # Duplicate collapse by DIGEST, not by the raw 300+-byte row
            # blob: the cache keys rows by this digest anyway (so
            # digest-equal IS the plane's identity — collapsing by it
            # adds no failure mode the keying doesn't already have), and
            # np.unique over 16-byte rows is ~24x cheaper than over the
            # full canonical bytes (1.5 ms vs 36 ms at a 1.5k x 43
            # batch) — the row plane's collapse is CHEAPER than
            # collapse_rows, not dearer.
            darr = np.frombuffer(b"".join(digests_all), np.uint8)
            _, first_idx, inv = np.unique(
                darr.reshape(total, 16), axis=0,
                return_index=True, return_inverse=True,
            )
            if first_idx.shape[0] < total:
                uniq_rows = first_idx
                inverse = inv.reshape(-1).astype(np.int64)
                self.stats.dedup_batches += 1
                self.stats.dedup_rows_collapsed += total - first_idx.shape[0]
        if uniq_rows is None:
            uniq_rows = np.arange(total, dtype=np.int64)
            inverse = uniq_rows
        digests = (
            digests_all if uniq_rows.shape[0] == total
            else [digests_all[i] for i in uniq_rows]
        )
        ov = self.overload
        # Brownout stale-serve extends to row entries: while pressure is
        # past NOMINAL, an expired row still answers (marked degraded at
        # delivery, never re-filled) — the whole-request stale-serve
        # contract at row granularity.
        stale_s = (
            ov.stale_window_s
            if ov is not None and ov.stale_serve_active()
            else 0.0
        )
        servable = first.servable
        plan = rc.begin_rows(
            servable.name, servable.version, wanted_key, digests,
            stale_s=stale_s,
        )
        try:
            ctx = _RowContext()
            ctx.cache = rc
            ctx.plan = plan
            ctx.overload = ov
            ctx.n_slots = len(digests)
            ctx.inverse = inverse
            ctx.lead_slots = np.asarray(plan.lead, dtype=np.int64)
            ctx.n_cold = len(plan.lead)
            ctx.passthrough = ctx.n_cold == ctx.n_slots == total
            # All slots executed fresh by THIS batch (no cached rows, no
            # foreign flights): delivery can ride the normal completer
            # tail — including the quality feed — via a plain inverse
            # scatter, exactly like the dedup path it subsumes.
            ctx.all_fresh = not plan.hits and not plan.waiters
            if ctx.passthrough:
                # Execution == the original batch: pad/fuse straight from
                # the concat this plan already built (never a second
                # concat).
                ctx.exec_arrays = cats
            elif ctx.n_cold:
                rows = uniq_rows[ctx.lead_slots]
                ctx.exec_arrays = {
                    k: np.ascontiguousarray(v[rows]) for k, v in cats.items()
                }
            else:
                ctx.exec_arrays = None
            rc.note_rows(servable.name, total, ctx.n_cold)
        except BaseException as exc:
            # The flights begin_rows registered must not outlive a failed
            # plan — a foreign batch joining them later would hang on a
            # fill that can never land (begin_rows' own atomicity guard
            # covers only its internal loop).
            rc.abort_rows(plan, exc)
            raise
        return ctx

    def _complete_rows_only(self, group: list[_WorkItem], row_ctx) -> None:
        """Completer task for a batch with ZERO cold rows: assemble every
        request's outputs from cached hits and foreign in-flight fills —
        the device, the bucket ladder, and the dispatch pipeline are
        never touched."""
        self._finish_row_batch(group, row_ctx, None)

    def _finish_row_batch(
        self, group: list[_WorkItem], row_ctx, host: dict | None,
        timeline: "_Timeline | None" = None,
    ) -> None:
        """Deliver a row-cache batch once every foreign fill it joined has
        resolved. Never blocks a completer thread: when foreign waiters
        are still in flight, delivery re-enters from the LAST waiter's
        done-callback (on the resolving leader's thread) — deadlock-free
        by construction, whatever the completer pool's size."""
        pending = [f for f in row_ctx.plan.waiters.values() if not f.done()]
        if not pending:
            self._deliver_row_batch(group, row_ctx, host, timeline)
            return
        lock = threading.Lock()
        state = {"left": len(pending)}

        def _on_done(_f):
            with lock:
                state["left"] -= 1
                if state["left"]:
                    return
            try:
                self._deliver_row_batch(group, row_ctx, host, timeline)
            except Exception as exc:  # noqa: BLE001 — waiters must resolve
                for it in group:
                    if not it.future.done():
                        try:
                            it.future.set_exception(exc)
                        except InvalidStateError:
                            pass

        for f in pending:
            f.add_done_callback(_on_done)

    def _deliver_row_batch(
        self, group: list[_WorkItem], row_ctx, host: dict | None,
        timeline: "_Timeline | None" = None,
    ) -> None:
        """Scatter (device + cached + foreign-filled) rows back into every
        request's original slice and resolve the futures. A request any
        of whose rows rode a FAILED foreign fill gets that error (its
        batchmates still deliver); a request served any stale (brownout)
        row is marked degraded via the future side-channel the service
        reads after the wait. Cache-assembled batches are deliberately
        NOT fed to the quality plane: like whole-request cache hits,
        their non-cold rows are served — not freshly predicted — scores
        (the passthrough case rides the normal completer tail and is
        sketched there)."""
        try:
            full, failed_rows, row_errors = row_ctx.assemble(host)
        except Exception as exc:  # noqa: BLE001 — every waiter must resolve
            for it in group:
                if not it.future.done():
                    try:
                        it.future.set_exception(exc)
                    except InvalidStateError:
                        pass
            return
        stale = row_ctx.plan.stale_slots
        stale_rows = (
            np.isin(row_ctx.inverse, np.fromiter(stale, np.int64))
            if stale else None
        )
        ov = row_ctx.overload
        off = 0
        for it in group:
            sl = slice(off, off + it.n)
            off += it.n
            if failed_rows is not None and failed_rows[sl].any():
                bad = int(row_ctx.inverse[sl][failed_rows[sl]][0])
                exc = row_errors.get(bad) or next(iter(row_errors.values()))
                if not it.future.done():
                    try:
                        it.future.set_exception(exc)
                    except InvalidStateError:
                        pass
                continue
            if stale_rows is not None and stale_rows[sl].any():
                # Degraded marker: the service thread reads this after
                # the future resolves (it cannot be set from here — the
                # contextvar lives in the RPC's context) and forwards it
                # as the x-dts-degraded trailing metadata / header.
                it.future.dts_degraded = "stale"
                if ov is not None:
                    ov.note_brownout_serve()
                if it.span is not None:
                    it.span.attrs["brownout_stale_rows"] = True
                    it.span.annotate(
                        "overload.stale_serve",
                        rows=int(stale_rows[sl].sum()),
                    )
            sliced = {k: v[sl] for k, v in full.items()}
            try:
                if it.future.cancelled():
                    continue
                if timeline is not None:
                    timeline.resolve(it, sliced)
                else:
                    it.future.set_result(sliced)
            except InvalidStateError:
                pass
        if timeline is not None:
            timeline.flush()

    def _run_stage(
        self,
        sid: int | None,
        group: list[_WorkItem],
        total: int,
        bucket: int,
        wanted: set | None,
        wanted_key: tuple | None,
        topk: int,
        n_valid: int | None,
        fused: dict | None,
        batched: dict | None,
        phases: list | None = None,
        scatter: "np.ndarray | None" = None,
        ring_bufs: list | None = None,
        row_ctx: "_RowContext | None" = None,
        prune: bool = False,
    ) -> None:
        """Device stage for one assembled batch: execute, issue the async
        D2H readback, register in flight, hand off to a completer. Runs on
        the dispatch thread (pipelined mode) or inline on the batcher
        thread (sid None from the fallback path), or on a handler thread for
        a direct crossing (sid None, the item `direct`: it holds one of
        `_dispatch_pending`, given back here as a staged group's is).
        `phases` is the batch's
        tracing sink (started in _dispatch with the pad phase); the device-
        stage phases and fault annotations land in it here and are
        replayed onto every member request's span."""
        pending_closed = sid is None and not group[0].direct
        # Whether this batch is in flight: until then the wedge clock's
        # `_dispatching_*` are this stage's to clear, after it the next's.
        registered = False
        util = None  # assigned once the batch passes the early-out checks
        util_handed_off = False
        # Elastic run_fn completion protocol (parallel/elastic.py): the
        # dispatch below mints a per-batch issue token naming the split it
        # routed to; the completer's finally closes it (note_complete) —
        # the per-split in-flight accounting that is the hitless-switch
        # drain barrier. Captured here so a run_fn detached mid-flight
        # still gets its token back.
        run_fn_cap = self._run_fn
        run_token = None
        run_handed = False

        def release_bufs():
            # Pre-completion exit (shed, all-cancelled, device-stage
            # failure): the buffers were never handed to a completer, and
            # no async upload is in flight past this frame, so they are
            # safe to recycle here.
            if self.buffer_ring is not None and ring_bufs:
                self.buffer_ring.release(ring_bufs)

        def sink_ctx():
            # Fresh context per use: collect_phases is a generator context
            # manager (single-shot), and this stage enters the sink twice
            # (device stage, readback issue).
            return (
                tracing.collect_phases(phases)
                if phases is not None else _NULL_CTX
            )

        try:
            if sid is not None:
                with self._cv:
                    if self._staged_groups.pop(sid, None) is None:
                        release_bufs()
                        if row_ctx is not None:
                            # Shed while staged: foreign batches waiting
                            # on this batch's cold rows must fail now.
                            row_ctx.abort(DeviceWedgedError(
                                "batch shed while staged for dispatch"
                            ))
                        return  # shed by the circuit breaker while queued
                    self._staged_candidates -= total
            if all(it.future.cancelled() for it in group):
                release_bufs()
                if row_ctx is not None:
                    row_ctx.abort(CoalescedLeaderCancelled(
                        "row fill leader batch was cancelled before dispatch"
                    ))
                return  # every waiter gave up; skip the device work
            all_warm = all(it.warmup for it in group)
            window = self.inflight_window
            if window and not all_warm:
                # The k-deep in-flight window: keep issuing while fewer
                # than k batches are executing-or-awaiting-readback; at k,
                # wait for a completion (notified from _complete's
                # finally). Bounded waits, and a wedged readback breaks
                # the gate — the jit call would queue behind the wedged
                # device anyway, and the breaker owns that failure mode.
                with self._cv:
                    def window_open() -> bool:
                        return (
                            len(self._inflight) < window or self._stopping
                            or bool(self._wedged_for(time.perf_counter()))
                        )

                    if not window_open():
                        self.stats.inflight_window_waits += 1
                        self._wait("window", 0.005, until=window_open)
            with self._cv:
                # An all-warmup group is exempt from the wedge clock:
                # hot-load warmup (warmup_via_queue during a version
                # rollout) legitimately compiles for minutes here, and
                # tripping the breaker then would shed live traffic during
                # every rollout. A live request coalesced into the group
                # re-arms the clock.
                self._dispatching_since = (
                    None if all_warm else time.perf_counter()
                )
                if self.recovery is not None:
                    # The group now entering the device stage — what a
                    # wedge-triggered quarantine capture must replay.
                    self._dispatching_group = None if all_warm else group
            servable = group[0].servable
            stage_t0 = time.perf_counter()
            # Utilization ledger: captured here (detachable mid-flight,
            # the overload/cache precedent) and handed to the completer so
            # the depth gauge's inc/dec stay paired even if the plane is
            # swapped while this batch is in flight. Warmup batches are
            # compile time, not device occupancy.
            util = None if all_warm else self.utilization
            if util is not None:
                util.depth_inc()
            ov = self.overload  # capture: detachable mid-flight (bench A/B)
            if ov is not None:
                # Feed the controller the group's measured queue waits —
                # the controlled variable of the adaptive admission loop.
                # Warmup items are exempt (their waits include compiles).
                waits = [
                    stage_t0 - it.enqueue_t for it in group if not it.warmup
                ]
                if waits:
                    ov.note_queue_waits(waits)
            if phases is not None:
                # Queue wait is per-item (each enqueued at its own time);
                # attached directly, not through the shared batch sink. The
                # stamps are the timeline's: `req.queue` + `req.assemble`.
                for it in group:
                    if it.span is not None:
                        it.span.add_interval(
                            "batch.queue_wait", it.enqueue_t, stage_t0
                        )
            with sink_ctx():
                # Named fault site (faults.py): delay/error/wedge the device
                # stage of this batch — the stuck-device scenario the circuit
                # breaker and deadline tests drive deterministically. Inside
                # the sink so an injected fault annotates the member spans.
                faults.fire("batcher.dispatch")
                if faults.active() and faults.get().has_site("device_lost"):
                    # Recovery-plane chaos site: fired once per member
                    # request with that request's content digest as the
                    # key — a keyless rule kills any batch (device died),
                    # a keyed rule deterministically kills exactly the
                    # batches carrying one request's bytes (the poison
                    # the bisection isolates). The has_site gate keeps
                    # ordinary chaos runs from paying the digests.
                    for it in group:
                        faults.fire(
                            "device_lost", key=poison_fault_key(it.arrays)
                        )
                with request_trace.span("batch.dispatch"):
                    if fused is not None:
                        outputs = self._execute_fused(
                            fused, bucket, wanted_key, topk, n_valid,
                            prune=prune,
                        )
                        self.stats.fused_batches += 1
                    else:
                        outputs = self._execute(  # async dispatch
                            servable, batched,
                            out_keys=wanted_key, topk=topk, n_valid=n_valid,
                            prune=prune,
                        )
                # Phases by count, beside `batch.dispatch`'s: one take of
                # the trace's lock a batch for all that apply.
                counted = []
                for k in self._kernel_kinds.get(servable, ()):
                    setattr(self.stats, k.counter, getattr(self.stats, k.counter) + 1)
                    counted.append((k.phase, 0.0, 1))
                if group[0].direct:
                    self.stats.direct_batches += 1
                    counted.append(("batch.direct", 0.0, 1))
                if counted:
                    request_trace.add_many(counted)
            if run_fn_cap is not None and getattr(run_fn_cap, "elastic", False):
                # Same thread, synchronous: the token names the split the
                # dispatch above routed to. It travels to the completer
                # and closes there (or in this frame's finally on a
                # pre-handoff failure).
                run_token = run_fn_cap.take_issue_token()
            # A counting step's counters (names, device int32), or None: what
            # the model was built with decides, and the completer records
            # them once a batch. A shadow execution's are dropped with it.
            step_stats = None
            if servable.model.step_stats:
                counts = outputs.pop(STEP_STATS_KEY, None)
                if counts is not None:
                    step_stats = (servable.model.step_stats, counts)
            if topk:
                if prune:
                    self.stats.prune_batches += 1
                else:
                    self.stats.topk_batches += 1
                # Top-k / prune outputs ARE the fetch (the score vector is
                # reconstructed host-side from the pairs).
                fetch = dict(outputs)
            else:
                fetch = {
                    k: v for k, v in outputs.items()
                    # int8-wire scale/min sidecars always ride the fetch:
                    # a filtered request's quantized score is undecodable
                    # without them (restore_outputs_host strips them).
                    if wanted is None or k in wanted or is_wire_sidecar(k)
                }
            shadow_fetch = None
            integ = self.integrity
            if (
                integ is not None
                and run_fn_cap is None
                and not all_warm
                and integ.want_shadow()
            ):
                # Shadow verification (ISSUE 20): re-execute the SAME
                # jitted entry over the same inputs (host buffers, uploaded
                # afresh by each _execute call) and hand both device results
                # to the completer for a host-side bit-identity compare.
                # Any divergence is hardware miscomputation (same
                # program, same input, one device): OutputCorruptError
                # there captures the group for replay via the recovery
                # cycle. Custom run_fn paths are ineligible (their
                # entries may legitimately not be bit-stable); all-warmup
                # groups carry no scores worth verifying.
                if batched is not None:
                    shadow_in = batched
                else:
                    # Fused-assembler batch: rebuild the generic padded
                    # equivalent from the same host parts the native
                    # packer consumed. The generic entry shares the
                    # fused path's compiled executable over a
                    # bit-identical combined buffer (pinned by
                    # tests/test_batcher.py), so the compare stays
                    # apples to apples — and cross-checks the native
                    # assembler against the reference pad+pack besides.
                    # Plain np.empty, not the buffer ring: this buffer
                    # dies with the dispatch frame.
                    shadow_in = {}
                    for k, parts in fused["parts"].items():
                        dt = parts[0].dtype
                        if any(p.dtype != dt for p in parts):
                            dt = np.result_type(*(p.dtype for p in parts))
                        buf = np.empty(
                            (bucket,) + parts[0].shape[1:], dt
                        )
                        off = 0
                        for p in parts:
                            buf[off : off + p.shape[0]] = p
                            off += p.shape[0]
                        buf[off:] = 0  # padding rows
                        shadow_in[k] = buf
                with sink_ctx():
                    with request_trace.span("batch.shadow_dispatch"):
                        shadow_outputs = self._execute(
                            servable, shadow_in,
                            out_keys=wanted_key, topk=topk, n_valid=n_valid,
                            prune=prune,
                        )
                shadow_fetch = {k: shadow_outputs[k] for k in fetch}
            # What a full-fp32 all-outputs readback of this batch would
            # have moved: the baseline the compaction win is charged
            # against. Traced row bytes when the default jit entry served
            # the batch; the f32-equivalent of the fetch for custom
            # run_fns (their dropped outputs are unknowable here).
            rb = self._out_row_bytes.get(servable)
            if rb is not None and rb[0]:
                full_bytes = rb[0] * bucket
            else:
                # Custom run_fn outputs may be arbitrary array-likes; only
                # count what exposes a shape.
                full_bytes = sum(
                    int(np.prod(shape)) * 4
                    for v in fetch.values()
                    if (shape := getattr(v, "shape", None)) is not None
                )
            issue_t0 = time.perf_counter()
            # Start the device->host readback now; the completer thread
            # then finds the bytes already (or sooner) on host.
            with tracing.annotation("readback.issue"):
                for v in fetch.values():
                    if hasattr(v, "copy_to_host_async"):
                        v.copy_to_host_async()
                if shadow_fetch is not None:
                    for v in shadow_fetch.values():
                        if hasattr(v, "copy_to_host_async"):
                            v.copy_to_host_async()
                if step_stats is not None:
                    step_stats[1].copy_to_host_async()
            with sink_ctx():
                request_trace.add(
                    "readback.issue", time.perf_counter() - issue_t0
                )

            self.stats.batches += 1
            self.stats.requests += len(group)
            self.stats.candidates += total
            self.stats.padded_candidates += bucket
            self.stats.bytes_download_full_f32 += int(full_bytes)

            meta = None
            if topk:
                meta = {
                    ("prune_n" if prune else "topk_n"): n_valid,
                    "score_key": servable.model.score_output,
                }
            # Readback + distribution off-thread: this thread moves on to
            # the next batch immediately, pipelining device work. The batch
            # is registered in-flight first so a readback that never
            # returns is visible to the circuit breaker.
            with self._cv:
                self._inflight_seq += 1
                batch_id = self._inflight_seq
                if not all(it.warmup for it in group):
                    self._inflight[batch_id] = time.perf_counter()
                    if self.recovery is not None:
                        # Same register site as the wedge clock: a
                        # quarantine capture replays exactly the groups
                        # the stuck readbacks strand.
                        self._inflight_groups[batch_id] = group
                    # Per-bucket in-flight accounting + high-water mark
                    # (pipeline_stats / dts_tpu_pipeline_*): same locked
                    # register site as the wedge clock, popped together
                    # in _complete's finally.
                    self._inflight_buckets[bucket] = (
                        self._inflight_buckets.get(bucket, 0) + 1
                    )
                    self.stats.inflight_peak = max(
                        self.stats.inflight_peak, len(self._inflight)
                    )
                # Wedge accounting moves from "dispatching" to "in flight"
                # atomically. Clearing only in the finally below would leave
                # a window where the completer has already resolved this
                # batch's futures while _dispatching_since still shows the
                # dispatch start — a submit racing that window would read a
                # long-finished dispatch as a wedged device.
                self._dispatching_since = None
                self._dispatching_group = None
                registered = True
                if not pending_closed:
                    # Clamped at zero: a quarantine capture resets the
                    # pending count while abandoned stage calls may still
                    # be queued behind a wedged worker — their eventual
                    # decrements must not drive it negative.
                    self._dispatch_pending = max(self._dispatch_pending - 1, 0)
                    group[0].direct = False
                    pending_closed = True
                self._cv.notify_all()
            if phases is not None:
                _replay_group_phases(group, phases)
                phases = None  # a later submit() failure must not re-replay
            self._completers.submit(
                self._complete, batch_id, group, fetch, issue_t0, meta, scatter,
                stage_t0, util=util, bucket=bucket, ring_bufs=ring_bufs,
                row_ctx=row_ctx, run_token=run_token,
                run_fn=run_fn_cap if run_token is not None else None,
                shadow=shadow_fetch, step_stats=step_stats,
            ).add_done_callback(
                lambda f, g=group: self._guard_worker_future(f, g, "completer")
            )
            util_handed_off = True
            run_handed = True
        except Exception as exc:  # propagate to every waiter, keep serving
            # Ring buffers are deliberately NOT recycled on a device-stage
            # failure: an async H2D transfer may still be reading them, so
            # they fall to GC instead (the ring just allocates fresh ones).
            if phases is not None:
                # The spans must show the phases (and any injected-fault
                # annotation) that led to the failure BEFORE the waiters
                # unblock and finish their root spans.
                _replay_group_phases(group, phases)
            if row_ctx is not None:
                # Close the row flights whatever happens next: even when
                # the recovery plane replays this group (re-planning its
                # rows fresh), foreign batches riding the OLD flights
                # must not hang on a fill that will never land.
                row_ctx.abort(exc)
            rec = self.recovery  # capture: detachable mid-flight
            if rec is not None and rec.take_group(group, exc):
                # Device-fatal failure with the recovery plane armed: the
                # controller owns these items now (quarantine -> reinit ->
                # replay); their futures resolve from the replay path —
                # or with a distinct poisoned/budget-exhausted status —
                # never from this frame.
                pass
            else:
                for it in group:
                    if not it.future.done():
                        it.future.set_exception(exc)
        finally:
            if util is not None and not util_handed_off:
                # A device-stage failure never reaches _complete: close
                # the gauge here so in_flight cannot drift upward.
                util.depth_dec()
            if run_token is not None and not run_handed:
                # A minted-but-never-handed-off token (post-dispatch
                # failure before the completer submit) must close here,
                # or the elastic drain barrier holds open forever.
                try:
                    run_fn_cap.note_complete(run_token)
                except Exception:  # noqa: BLE001 — accounting, never fatal
                    pass
            with self._cv:
                if not registered:
                    # Once in flight, the pending count is back and the next
                    # stage may have begun on another thread (a direct
                    # crossing, or the dispatch thread after one): the clock
                    # would be that stage's.
                    self._dispatching_since = None
                    self._dispatching_group = None
                if not pending_closed:
                    self._dispatch_pending = max(self._dispatch_pending - 1, 0)
                    group[0].direct = False
                self._cv.notify_all()

    def _complete(
        self, batch_id: int, group: list[_WorkItem], outputs,
        issue_t0: float | None = None, meta: dict | None = None,
        scatter: "np.ndarray | None" = None,
        stage_t0: float | None = None,
        util=None, bucket: int = 0,
        ring_bufs: list | None = None,
        row_ctx: "_RowContext | None" = None,
        run_token=None, run_fn=None,
        shadow: dict | None = None,
        step_stats: tuple | None = None,
    ) -> None:
        phases: list | None = (
            [] if tracing.enabled() and any(it.span is not None for it in group)
            else None
        )
        trace_ctx = (
            tracing.collect_phases(phases) if phases is not None else _NULL_CTX
        )
        taken_by_recovery = False
        timeline = None
        delivery = contextlib.ExitStack()  # holds the `batch.deliver` span
        try:
            with trace_ctx:
                # Named fault sites (faults.py): a readback that stalls or
                # dies — inside the sink so chaos annotates member spans —
                # and the recovery plane's executor_abort (the executable
                # aborted after dispatch; classified device-fatal).
                faults.fire("readback")
                faults.fire("executor_abort")
                # The fetch: the copy is already in flight (issued at
                # dispatch), so this measures the residual WAIT, not a full
                # synchronous transfer.
                wait_t0 = time.perf_counter()
                with tracing.annotation("readback.wait"):
                    host = {k: np.asarray(v) for k, v in outputs.items()}
                done_t = time.perf_counter()
                waited = done_t - wait_t0
                request_trace.add("readback.wait", waited)
                if step_stats is not None:
                    # Phases BY COUNT (`/monitoring?section=phases`: `count`
                    # is the sum, `total_ms` stays 0), copied beside the
                    # scores by the readback the dispatch stage started.
                    request_trace.add_many(tuple(
                        (name, 0.0, int(n))
                        for name, n in zip(step_stats[0], np.asarray(step_stats[1]))
                    ))
            # Everything from here to the last set_result is this batch's
            # delivery: one span, closed in the finally below.
            delivery.enter_context(request_trace.span("batch.deliver"))
            if stage_t0 is not None and issue_t0 is not None:
                timeline = _Timeline(stage_t0, issue_t0, done_t)
            integ = self.integrity  # capture: detachable mid-flight
            if (
                integ is not None
                and faults.active()
                and (
                    faults.get().has_site("readback_bitflip")
                    or faults.get().has_site("score_nan")
                )
            ):
                # Chaos injection BEFORE the shadow compare and screen:
                # the corrupted bytes must be exactly what those layers
                # would have received from a sick readback path.
                host = _inject_readback_corruption(host, group)
            if integ is not None and shadow is not None:
                # Shadow verification: bit-identity compare of the two
                # executions' raw host bytes, BEFORE widen/scatter (any
                # post-processing is deterministic host numpy — comparing
                # the rawest form localizes blame to the device/readback
                # path). Raises OutputCorruptError on divergence: the
                # except below hands the group to recovery for replay.
                keys = sorted(host)
                integ.shadow_compare(
                    [host[k] for k in keys],
                    [np.asarray(shadow[k]) for k in keys],
                )
            downloaded = sum(v.nbytes for v in host.values())
            total_n = sum(it.n for it in group)
            ov = self.overload  # capture: detachable mid-flight (bench A/B)
            if stage_t0 is not None and not any(it.warmup for it in group):
                # What a batch takes to cross the pipeline, stage start to
                # readback done: _holds_open weighs it against the gap
                # between arrivals. Compiles are not crossings.
                with self._cv:
                    self._traversal_s = _smoothed(
                        self._traversal_s, done_t - stage_t0
                    )
            if (
                ov is not None
                and stage_t0 is not None
                and not any(it.warmup for it in group)
            ):
                # Per-candidate service time (dispatch start -> readback
                # done): the EWMA estimate that prices backlogs for the
                # doomed-work refusal and the retry-after hint. Warmup
                # batches are excluded (compile time is not service time).
                ov.note_batch(total_n, done_t - stage_t0)
            if util is not None and stage_t0 is not None:
                # THE interval append the utilization plane is built on:
                # one (stage-start, readback-issued, readback-done) triple
                # per batch closes the preceding idle gap, extends the
                # busy union, and feeds the windowed gap waterfall.
                util.note_batch(
                    stage_t0, issue_t0 if issue_t0 is not None else done_t,
                    done_t, bucket=bucket, candidates=total_n,
                    d2h_wait_s=waited,
                )
            window = max(done_t - issue_t0 if issue_t0 is not None else waited, waited)
            # The closing span of the pair: `readback.wait` over
            # `readback.window` is the completers' blocked share.
            request_trace.add("readback.window", window)
            with self._cv:  # counters race across completer threads otherwise
                self.stats.bytes_downloaded += downloaded
                self.stats.readback_window_s += window
                self.stats.readback_blocked_s += waited
            if meta is not None and "prune_n" in meta:
                # Cascade stage-1 prune: widen the wire-dtype arrays to
                # f32 and hand all three through — the orchestrator does
                # the survivor gather/scatter. The per-item slice below
                # passes the k-length pairs through untouched (k < n) and
                # trims the bucket-length stage-1 vector to the request's
                # own rows (single solo request by construction).
                host = {
                    "survivor_scores":
                        host["survivor_scores"].astype(np.float32),
                    "survivor_indices": host["survivor_indices"],
                    "stage1_scores": host["stage1_scores"].astype(np.float32),
                }
            elif meta is not None:
                # Top-k reconstruction: scatter the k (score, index) pairs
                # back into a full-length f32 vector (single-request group
                # by construction).
                host = topk_restore_host(
                    host["topk_scores"], host["topk_indices"],
                    int(meta["topk_n"]), meta["score_key"],
                )
            elif self._wire_dt is not None:
                # Wire-dtype outputs widen back to float32 HERE, so every
                # downstream consumer (codec encode, Classify/Regress,
                # response assembly) transparently sees the signature dtype.
                # Gated on the knob: with the float32 wire, a model whose
                # outputs are GENUINELY half-precision (imported graphs
                # declaring DT_HALF/DT_BFLOAT16) must pass through
                # untouched, exactly as before this pipeline existed.
                host = restore_outputs_host(host)
            if scatter is not None:
                # Dedup scatter: the executable saw only the batch's unique
                # rows; fan their scores back out to every original row
                # position, so the per-request slices below are exactly
                # what an uncollapsed execution would have produced.
                host = {k: v[scatter] for k, v in host.items()}
            if row_ctx is not None:
                # Row-cache fill: close the plan's lead flights from the
                # executed rows (post-widen, post-sidecar-consume — the
                # exact bytes delivery slices) and wake every foreign
                # batch waiting on them.
                with (
                    tracing.collect_phases(phases) if phases is not None
                    else _NULL_CTX
                ), request_trace.span("cache.row_fill"):
                    row_ctx.fill_from_host(host)
            if phases is not None:
                # Attach the readback phases before the waiters unblock —
                # a root span must already hold its full tree when the RPC
                # handler finishes (and records) it.
                _replay_group_phases(group, phases)
                phases = None  # a set_result failure must not re-replay
            if row_ctx is not None and not row_ctx.passthrough:
                if row_ctx.all_fresh:
                    # Every delivered score came from THIS execution (the
                    # batch merely held intra-batch duplicates): scatter
                    # through the inverse map and ride the normal tail —
                    # including the quality feed — exactly like the dedup
                    # path this plan subsumes.
                    host = {k: v[row_ctx.inverse] for k, v in host.items()}
                else:
                    # Mixed fresh/cached batch: delivery scatters device +
                    # cached + foreign-filled rows back into each
                    # request's slice (and may defer on still-in-flight
                    # foreign fills). The quality plane is deliberately
                    # skipped — the assembled vector mixes fresh and
                    # cache-served scores, and the plane's contract
                    # sketches only fresh ones (cache hits are excluded
                    # the same way).
                    self._finish_row_batch(group, row_ctx, host, timeline)
                    return
            screened: dict[int, str] = {}
            if integ is not None and integ.config.screen:
                # Readback sanity screen (ISSUE 20 layer 2): per-request
                # slices of the score output, post-widen/post-scatter —
                # the exact bytes delivery hands each waiter. A failing
                # ROW fails only its own request (the poisoned-input
                # per-item precedent); batchmates deliver normally.
                skey = group[0].servable.model.score_output
                sarr = host.get(skey)
                if sarr is not None:
                    soff = 0
                    for idx, it in enumerate(group):
                        row = sarr[soff : soff + it.n]
                        soff += it.n
                        if it.warmup:
                            continue
                        reason = integ.screen_reason(row)
                        if reason is not None:
                            screened[idx] = reason
                            integ.note_screen_trip(reason)
            q = self.quality  # capture: detachable mid-flight (bench A/B)
            if screened:
                # A batch with ANY screened row never feeds the quality
                # plane — the readback is suspect wholesale, and sketching
                # corrupt scores would poison the drift baselines.
                q = None
            if q is not None and meta is None:
                # Quality-plane feed, BEFORE the waiters unblock so a
                # drift exemplar's `quality.drift` annotation is already
                # on the span when the RPC handler finishes (and the tail
                # sampler force-keeps) it. Top-k-compacted batches (meta)
                # are excluded: topk_restore_host back-fills 0.0 off the
                # head, so the full vector is not the model's prediction
                # over the request — sketching it (or joining labels
                # against the synthetic zeros) would poison the
                # distribution, and sketching only the head would bias
                # it high by construction.
                try:
                    self._observe_quality(q, group, host)
                except Exception:  # noqa: BLE001 — the observability
                    pass           # plane must never fail a batch
            off = 0
            for idx, it in enumerate(group):
                sliced = {k: v[off : off + it.n] for k, v in host.items()}
                off += it.n
                try:
                    if it.future.cancelled():
                        continue
                    if idx in screened:
                        it.future.set_exception(IntegrityScreenError(
                            f"readback screen failed this request's rows: "
                            f"{screened[idx]}"
                        ))
                    elif timeline is not None:
                        timeline.resolve(it, sliced)
                    else:
                        it.future.set_result(sliced)
                except InvalidStateError:
                    # A service-deadline cancel can land between the check
                    # and set_result; that waiter is gone, but its race must
                    # not poison co-batched requests via the except below.
                    pass
            if timeline is not None:
                timeline.flush()
            if integ is not None:
                # Screen-trip burst -> recovery escalation, AFTER delivery:
                # the tripped rows already failed individually; the cycle
                # (trigger "output_corrupt") reinits the executor before
                # the next batch inherits the sick output path.
                integ.maybe_escalate_screen(self.recovery)
        except Exception as exc:
            if phases is not None:
                _replay_group_phases(group, phases)
            if row_ctx is not None:
                # Idempotent after a successful fill (the flights are
                # already popped); on a readback failure it fails the
                # foreign batches waiting on this batch's rows.
                row_ctx.abort(exc)
            rec = self.recovery  # capture: detachable mid-flight
            if rec is not None and rec.take_group(group, exc):
                # Device-fatal readback failure: the recovery plane owns
                # these items (replay resolves their futures).
                taken_by_recovery = True
            else:
                for it in group:
                    if not it.future.done():
                        it.future.set_exception(exc)
        finally:
            delivery.close()
            if util is not None:
                util.depth_dec()
            if run_token is not None and run_fn is not None:
                # Close the elastic per-split in-flight registration: THIS
                # is the drain barrier's release point — readback done (or
                # failed), the old split's batch is no longer in flight.
                try:
                    run_fn.note_complete(run_token)
                except Exception:  # noqa: BLE001 — accounting, never fatal
                    pass
            # Recycle the padded-batch buffers: the readback finished, so
            # the H2D upload that read them is long done — the only point
            # in the batch lifecycle where reuse is provably safe. The
            # EXCEPTION is a device-fatal failure the recovery plane took:
            # a lost/wedged device may still hold async references into
            # these host buffers, so they leak to GC (the _run_stage
            # failure-path precedent) — the _HostBufferRing recycle
            # contract extension the replay path relies on.
            if (
                self.buffer_ring is not None and ring_bufs
                and not taken_by_recovery
            ):
                self.buffer_ring.release(ring_bufs)
            # The breaker closes itself here: once the stuck (or healthy)
            # readback finishes, the wedge condition clears with it — and
            # any coalescer free-riding the busy pipeline (or a dispatch
            # thread waiting on the in-flight window) is woken, since
            # capacity just opened up.
            with self._cv:
                self._inflight_groups.pop(batch_id, None)
                if self._inflight.pop(batch_id, None) is not None:
                    left = self._inflight_buckets.get(bucket, 0) - 1
                    if left > 0:
                        self._inflight_buckets[bucket] = left
                    else:
                        self._inflight_buckets.pop(bucket, None)
                self._cv.notify_all()

    @staticmethod
    def _observe_quality(q, group: list[_WorkItem], host: dict) -> None:
        """Feed the quality plane one observation per non-warmup member
        request: the model's score output sliced per item EXACTLY like
        the result delivery below it (post-widen, post-dedup-scatter, so
        the sketched scores are the scores clients receive). Requests
        whose output filter dropped the score output contribute nothing
        — there is no score to sketch."""
        score_key = group[0].servable.model.score_output
        scores = host.get(score_key)
        if scores is None:
            return
        off = 0
        for it in group:
            s = scores[off : off + it.n]
            off += it.n
            if it.warmup:
                continue  # compile traffic is not a prediction signal
            q.observe(
                it.servable.name, it.servable.version, s,
                lane=it.criticality, span=it.span, arrays=it.arrays,
                trace_id=it.span.trace_id if it.span is not None else None,
            )
