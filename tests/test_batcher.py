"""Dynamic batcher tests: bucket ladder, padding-neutrality, coalescing,
error propagation (SURVEY.md §7 step 3)."""

import threading
import time

import jax
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import ModelConfig, Servable, build_model, ctr_signatures
from distributed_tf_serving_tpu.serving import BatchTooLargeError, DynamicBatcher, bucket_for
from distributed_tf_serving_tpu.serving.batcher import fold_ids_host

CFG = ModelConfig(
    num_fields=8, vocab_size=1009, embed_dim=4, mlp_dims=(16,), num_cross_layers=1,
    compute_dtype="float32",
)


@pytest.fixture(scope="module")
def servable():
    model = build_model("dcn", CFG)
    return Servable(
        name="DCN", version=1, model=model,
        params=model.init(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(CFG.num_fields),
    )


def make_arrays(n, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "feat_ids": rng.randint(0, 1 << 40, size=(n, CFG.num_fields)).astype(np.int64),
        "feat_wts": rng.rand(n, CFG.num_fields).astype(np.float32),
    }


def reference_scores(servable, arrays):
    batch = {
        "feat_ids": fold_ids_host(arrays["feat_ids"], CFG.vocab_size),
        "feat_wts": arrays["feat_wts"],
    }
    return np.asarray(servable.model.apply(servable.params, batch)["prediction_node"])


def test_bucket_ladder():
    buckets = (32, 64, 128)
    assert bucket_for(1, buckets) == 32
    assert bucket_for(32, buckets) == 32
    assert bucket_for(33, buckets) == 64
    assert bucket_for(128, buckets) == 128
    with pytest.raises(BatchTooLargeError):
        bucket_for(129, buckets)


def test_fold_ids_exact_mod():
    """Host folding must be exact int64 mod, not int32 truncation."""
    big = np.array([[(1 << 40) + 5]], np.int64)
    assert fold_ids_host(big, 1009)[0, 0] == ((1 << 40) + 5) % 1009


def test_padding_neutral(servable):
    """Padded-bucket execution must score identically to the raw batch."""
    batcher = DynamicBatcher(buckets=(32, 64), max_wait_us=0).start()
    try:
        arrays = make_arrays(19)  # padded to 32
        got = batcher.submit(servable, arrays).result(timeout=30)["prediction_node"]
        want = reference_scores(servable, arrays)
        np.testing.assert_allclose(got, want, rtol=1e-6)
        assert got.shape == (19,)
    finally:
        batcher.stop()


def test_coalescing_merges_concurrent_requests(servable):
    """Many small concurrent requests should land in fewer device batches,
    each still getting exactly its own slice back."""
    batcher = DynamicBatcher(buckets=(64, 256), max_wait_us=20_000).start()
    try:
        n_req = 16
        arrays = [make_arrays(4, seed=s) for s in range(n_req)]
        futs = []
        start = threading.Barrier(n_req)

        def submit(i):
            start.wait()
            futs.append((i, batcher.submit(servable, arrays[i])))

        threads = [threading.Thread(target=submit, args=(i,)) for i in range(n_req)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, fut in futs:
            got = fut.result(timeout=30)["prediction_node"]
            np.testing.assert_allclose(got, reference_scores(servable, arrays[i]), rtol=1e-6)
        assert batcher.stats.batches < n_req  # coalescing actually happened
        assert batcher.stats.requests == n_req
    finally:
        batcher.stop()


class _LazyReadback:
    """Device-array stand-in whose host readback (np.asarray) blocks —
    emulates the async-dispatch/blocking-fetch split of a real jax.Array so
    the pipeline (inflight readbacks) can be held busy deterministically."""

    def __init__(self, n, release: threading.Event):
        self.n = n
        self.release = release

    def __array__(self, dtype=None, copy=None):
        self.release.wait(timeout=30)
        return np.zeros(self.n, np.float32)


def test_pipeline_aware_fill_extends_coalescing(servable):
    """With the dispatch pipeline saturated (>= pipeline_depth batches in
    flight), coalescing must keep filling past max_wait — the trickle of
    requests that previously dispatched one near-empty batch each should
    land in a single fuller batch (VERDICT r2: requests_per_batch 3.67/8)."""
    release = threading.Event()

    def slow_readback_run(servable_, arrays):
        bucket = next(iter(arrays.values())).shape[0]
        return {"prediction_node": _LazyReadback(bucket, release)}

    batcher = DynamicBatcher(
        buckets=(64,), max_wait_us=0, run_fn=slow_readback_run,
        pipeline_depth=2, completion_workers=4,
    ).start()
    try:
        # Two lone requests fill the pipeline (each dispatches immediately:
        # inflight below depth), their readbacks parked on `release`.
        # Staggered on the dispatch counter — submitted back-to-back they
        # could coalesce into ONE batch and never saturate the pipeline.
        first = []
        for s in (0, 1):
            first.append(batcher.submit(servable, make_arrays(4, seed=s)))
            deadline = time.perf_counter() + 5
            while batcher.stats.batches < s + 1 and time.perf_counter() < deadline:
                time.sleep(0.002)
            assert batcher.stats.batches == s + 1
        # Now trickle requests: with max_wait_us=0 each would previously
        # dispatch alone; pipeline-aware fill must hold them together.
        trickled = []
        for s in range(2, 8):
            trickled.append(batcher.submit(servable, make_arrays(4, seed=s)))
            time.sleep(0.01)
        assert batcher.stats.batches == 2  # still riding the busy pipeline
        release.set()
        for f in first + trickled:
            assert f.result(timeout=30)["prediction_node"].shape == (4,)
        assert batcher.stats.batches <= 4  # 2 pipeline-fillers + ~1 coalesced
        assert batcher.stats.fill_waits > 0
        assert batcher.stats.requests == 8
    finally:
        release.set()
        batcher.stop()


def test_idle_pipeline_does_not_delay_dispatch(servable):
    """The fill extension must apply ONLY when the pipeline is busy: a lone
    request on an idle batcher still dispatches within ~max_wait."""
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=1000, pipeline_depth=2).start()
    try:
        t0 = time.perf_counter()
        batcher.submit(servable, make_arrays(4)).result(timeout=30)
        assert time.perf_counter() - t0 < 5  # jit compile dominates, not waiting
        assert batcher.stats.fill_waits == 0
    finally:
        batcher.stop()


def test_oversized_request_rejected(servable):
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0).start()
    try:
        with pytest.raises(BatchTooLargeError):
            batcher.submit(servable, make_arrays(33))
    finally:
        batcher.stop()


def test_error_propagates_and_batcher_survives(servable):
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0).start()
    try:
        bad = {"feat_ids": make_arrays(4)["feat_ids"]}  # missing feat_wts -> apply KeyError
        with pytest.raises(Exception):
            batcher.submit(servable, bad).result(timeout=30)
        # Batcher thread must still be alive and serving.
        good = batcher.submit(servable, make_arrays(4)).result(timeout=30)
        assert good["prediction_node"].shape == (4,)
    finally:
        batcher.stop()


def test_stop_rejects_new_work_and_drains(servable):
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=50_000).start()
    futs = [batcher.submit(servable, make_arrays(4, seed=s)) for s in range(3)]
    batcher.stop()
    # Everything enqueued before stop() must resolve (no waiter left hanging
    # behind the shutdown sentinel) ...
    for f in futs:
        assert f.result(timeout=30)["prediction_node"].shape == (4,)
    # ... and new work is refused outright rather than silently dropped.
    with pytest.raises(RuntimeError, match="stopped"):
        batcher.submit(servable, make_arrays(4))


def test_occupancy_stats(servable):
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0).start()
    try:
        batcher.submit(servable, make_arrays(19)).result(timeout=30)
        assert batcher.stats.padded_candidates == 32
        assert batcher.stats.candidates == 19
        assert 0 < batcher.stats.mean_occupancy < 1
    finally:
        batcher.stop()


def test_input_cache_correctness_and_hits(servable):
    """Repeat content must hit the device-input cache and still score
    exactly; distinct content must never false-hit (the digest keys the
    device array, so a collision would silently serve wrong scores)."""
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0).start()
    try:
        a = make_arrays(8, seed=1)
        b = make_arrays(8, seed=2)
        got_a1 = batcher.submit(servable, a).result()["prediction_node"]
        h0, m0 = batcher.input_cache.hits, batcher.input_cache.misses
        got_a2 = batcher.submit(servable, a).result()["prediction_node"]
        assert batcher.input_cache.hits > h0  # repeat content skipped upload
        assert batcher.input_cache.misses == m0
        got_b = batcher.submit(servable, b).result()["prediction_node"]
        assert batcher.input_cache.misses > m0  # fresh content is a miss
        np.testing.assert_array_equal(got_a1, got_a2)
        np.testing.assert_allclose(got_a1, reference_scores(servable, a), rtol=1e-5)
        np.testing.assert_allclose(got_b, reference_scores(servable, b), rtol=1e-5)
        assert batcher.input_cache.bytes_skipped > 0
    finally:
        batcher.stop()


def test_input_cache_lru_eviction(servable):
    """Capacity bounds device memory: oldest entries fall out, and a
    re-submission after eviction re-uploads (miss) with correct results."""
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0, input_cache_entries=2).start()
    try:
        payloads = [make_arrays(8, seed=s) for s in range(3)]
        for p in payloads:
            batcher.submit(servable, p).result()
        assert len(batcher.input_cache._lru) <= 2
        m0 = batcher.input_cache.misses
        got = batcher.submit(servable, payloads[0]).result()["prediction_node"]
        assert batcher.input_cache.misses > m0  # was evicted -> fresh upload
        np.testing.assert_allclose(got, reference_scores(servable, payloads[0]), rtol=1e-5)
    finally:
        batcher.stop()


def test_input_cache_disabled_with_run_fn(servable):
    """A custom run_fn (the sharded-mesh executor) owns device placement;
    the batcher must not interpose its own device arrays."""
    def run_fn(sv, arrays):
        return sv.model.apply(sv.params, {
            "feat_ids": fold_ids_host(arrays["feat_ids"], CFG.vocab_size),
            "feat_wts": arrays["feat_wts"],
        })

    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0, run_fn=run_fn).start()
    try:
        assert batcher.input_cache is None
        got = batcher.submit(servable, make_arrays(6)).result()["prediction_node"]
        assert got.shape == (6,)
    finally:
        batcher.stop()


def test_input_cache_adaptive_bypass(servable):
    """Unique-only traffic must stop paying the digest: after probe_window
    misses with ~no hits the cache flips to pass-through (and results stay
    correct)."""
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0).start()
    try:
        # Shrink for the test: the combined-transfer path does ONE group
        # lookup per batch (not one per input), so 5 unique batches are 5
        # misses.
        batcher.input_cache.probe_window = 4
        for s in range(5):
            batcher.submit(servable, make_arrays(8, seed=100 + s)).result()
        assert batcher.input_cache.bypassed
        assert not batcher.input_cache._lru  # device refs dropped
        p = make_arrays(8, seed=200)
        got = batcher.submit(servable, p).result()["prediction_node"]
        np.testing.assert_allclose(got, reference_scores(servable, p), rtol=1e-5)
    finally:
        batcher.stop()


def test_input_cache_bypass_is_regime_aware(servable):
    """The probe window SLIDES: a unique phase after a hot repeated phase
    still flips to bypass (round-3 weak #3: the one-shot probe kept paying
    the digest because lifetime hit rate stayed high), and after
    reprobe_every pass-through lookups a re-probe window re-engages the
    cache when traffic turns repetitive again."""
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0).start()
    try:
        cache = batcher.input_cache
        cache.probe_window = 4
        cache.reprobe_every = 3
        hot = make_arrays(8, seed=7)
        for _ in range(12):  # repeated phase: high global hit rate
            batcher.submit(servable, hot).result()
        assert not cache.bypassed and cache.hits >= 8
        for s in range(5):  # unique phase: a cold window must still fire
            batcher.submit(servable, make_arrays(8, seed=300 + s)).result()
        assert cache.bypassed and cache.bypass_cycles == 1
        # 2 more bypassed lookups reach reprobe_every=3 -> probing resumes;
        # repeated traffic then re-engages the cache.
        for s in range(2):
            batcher.submit(servable, make_arrays(8, seed=400 + s)).result()
        assert not cache.bypassed
        for _ in range(4):
            batcher.submit(servable, hot).result()
        assert not cache.bypassed  # 3 hits / 4 lookups: window stays warm
        h0 = cache.hits
        for _ in range(3):
            batcher.submit(servable, hot).result()
        assert cache.hits > h0  # serving from the cache again
    finally:
        batcher.stop()


def test_input_cache_pack_tag_disambiguates():
    """Same raw bytes packed under DIFFERENT transforms (one servable
    u24-packs ids, another serves them raw) must occupy distinct cache
    entries — the digest is computed pre-pack, so without the tag a hit
    would hand one servable the other's packed layout."""
    from distributed_tf_serving_tpu.serving.batcher import DeviceInputCache

    cache = DeviceInputCache()
    raw = np.arange(12, dtype=np.int32).reshape(3, 4)
    packed = cache.get_or_put(
        "feat_ids", raw,
        pack=lambda a: np.ascontiguousarray(a.view(np.uint8).reshape(3, 4, 4)[..., :3]),
        pack_tag="u24",
    )
    plain = cache.get_or_put("feat_ids", raw.copy(), pack=None, pack_tag="")
    assert np.asarray(packed).dtype == np.uint8
    assert np.asarray(plain).dtype == np.int32  # not the u24 entry
    assert cache.misses == 2 and cache.hits == 0
    # and the tagged entry still HITS for its own transform
    again = cache.get_or_put(
        "feat_ids", raw.copy(), pack=lambda a: (_ for _ in ()).throw(AssertionError("hit must skip pack")),
        pack_tag="u24",
    )
    assert cache.hits == 1
    np.testing.assert_array_equal(np.asarray(again), np.asarray(packed))


def test_prepare_inputs_copies_frozen_view_over_writable_base(servable):
    """writeable=False over a writable base is NOT immutable — the copy
    must still happen (only protobuf-bytes-backed arrays may pass through)."""
    from distributed_tf_serving_tpu.serving.batcher import prepare_inputs

    base = np.random.RandomState(0).rand(4, CFG.num_fields).astype(np.float32)
    frozen = base.view()
    frozen.setflags(write=False)
    out = prepare_inputs(servable.model, {"feat_wts": frozen})
    base[0, 0] = 99.0  # caller mutates the base after submit
    assert out["feat_wts"][0, 0] != 99.0  # batcher's copy is isolated

    proto_backed = np.frombuffer(base.tobytes(), np.float32).reshape(base.shape)
    out2 = prepare_inputs(servable.model, {"feat_wts": proto_backed})
    assert out2["feat_wts"].base is not None  # pass-through, no copy


def test_warmup_arrays_signature_driven():
    """Warmup batches come from the servable's signature, so optional
    inputs (DLRM dense_features) are included — a DLRM warmup must not
    KeyError, and queue-path warmup must compile through the batcher
    thread."""
    dlrm_cfg = ModelConfig(
        num_fields=8, vocab_size=1009, embed_dim=4, mlp_dims=(16,),
        bottom_mlp_dims=(8, 4), num_dense_features=5, compute_dtype="float32",
    )
    model = build_model("dlrm", dlrm_cfg)
    sv = Servable(
        name="DLRM", version=1, model=model,
        params=model.init(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(dlrm_cfg.num_fields, with_dense=5),
    )
    arrays = DynamicBatcher.warmup_arrays(sv, 16)
    assert set(arrays) == {"feat_ids", "feat_wts", "dense_features"}
    assert arrays["feat_ids"].dtype == np.int64  # wire dtype, folded on submit
    assert arrays["dense_features"].shape == (16, 5)

    batcher = DynamicBatcher(buckets=(16, 32), max_wait_us=0).start()
    try:
        batcher.warmup(sv)  # direct path (pre-start)
        batcher.warmup_via_queue(sv)  # live path (hot-load)
    finally:
        batcher.stop()


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_warmup_runs_each_bucket_twice(servable, monkeypatch, backend):
    """The ladder warm-up executes a bucket once an output selection live
    traffic hits (all outputs, score-only) and leaves one jit variant each
    in the entry's cache, whatever backend jax reports: there is one
    executable a (layout, outputs, top-k), not a pair."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    batcher = DynamicBatcher(buckets=(16, 32), max_wait_us=0)
    calls = []
    execute = batcher._execute

    def counting(sv, arrays, **kwargs):
        calls.append((arrays["feat_ids"].shape[0], kwargs.get("out_keys")))
        return execute(sv, arrays, **kwargs)

    monkeypatch.setattr(batcher, "_execute", counting)
    batcher.warmup(servable)
    score_only = (servable.model.score_output,)
    assert calls == [(16, None), (16, score_only), (32, None), (32, score_only)]
    fn, _, combined = batcher.jit_entry(servable)
    assert combined
    variants = fn.__defaults__[-1]  # the entry's `_cache`: key -> jitted fn
    assert len(variants) == 4
    assert sorted(key[0][0] for key in variants) == [16, 16, 32, 32]  # rows
    assert {key[1] for key in variants} == {None, score_only}


# ------------------------------------------------- overload / wedge defense


def _blocking_run_fn(release: threading.Event, calls: list):
    """run_fn stand-in for a wedged device: every dispatch records itself
    then blocks until released."""

    def run_fn(servable, batched):
        calls.append(batched["feat_ids"].shape[0])
        release.wait(timeout=30)
        n = batched["feat_ids"].shape[0]
        return {"prediction_node": np.zeros((n,), np.float32)}

    return run_fn


def test_wedged_device_circuit_breaker(servable):
    """A dispatch stuck past breaker_timeout_s must fail NEW requests fast
    (<1s, not the 120s RPC deadline), shed the backlog, and close the
    breaker by itself once the stuck batch completes (VERDICT.md round-1
    item 6)."""
    from distributed_tf_serving_tpu.serving import DeviceWedgedError

    import time

    release = threading.Event()
    calls: list = []
    batcher = DynamicBatcher(
        buckets=(32,), max_wait_us=0,
        run_fn=_blocking_run_fn(release, calls),
        breaker_timeout_s=5.0,
    ).start()
    try:
        stuck = batcher.submit(servable, make_arrays(4))  # wedges the loop
        # Wait until the wedge is actually dispatched (loaded CI hosts make
        # fixed sleeps race the breaker threshold), then queue the backlog.
        deadline = time.perf_counter() + 10
        while not calls and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert calls, "dispatch never started"
        queued = batcher.submit(servable, make_arrays(4, seed=1))  # backlog
        # Backdate the dispatch clock instead of sleeping the threshold
        # away: real elapsed time would race this test's own submits on a
        # loaded 1-core host (the backlog submit must land BEFORE the
        # breaker opens, the probe below AFTER).
        with batcher._cv:
            assert batcher._dispatching_since is not None
            batcher._dispatching_since -= batcher.breaker_timeout_s + 1

        t0 = time.perf_counter()
        with pytest.raises(DeviceWedgedError):
            batcher.submit(servable, make_arrays(4, seed=2))
        assert time.perf_counter() - t0 < 1.0  # fail-fast, no deadline burn
        with pytest.raises(DeviceWedgedError):
            queued.result(timeout=1)  # backlog shed with the same error

        release.set()  # device un-wedges
        assert stuck.result(timeout=30)["prediction_node"].shape == (4,)
        # Breaker closed by itself: new work flows again.
        ok = batcher.submit(servable, make_arrays(4, seed=3))
        assert ok.result(timeout=30)["prediction_node"].shape == (4,)
    finally:
        release.set()
        batcher.stop()


def test_queue_overload_sheds_resource_exhausted(servable):
    """Backlog past queue_capacity_candidates is refused at admission
    instead of queueing past any deadline."""
    from distributed_tf_serving_tpu.serving import QueueOverloadError

    release = threading.Event()
    calls: list = []
    batcher = DynamicBatcher(
        buckets=(4,), max_wait_us=0,  # capacity clamps to >= buckets[-1]
        run_fn=_blocking_run_fn(release, calls),
        breaker_timeout_s=None,  # isolate the capacity bound
        queue_capacity_candidates=8,
    ).start()
    try:
        import time

        first = batcher.submit(servable, make_arrays(4))  # dispatched, blocks
        time.sleep(0.2)  # let the loop pop it off the queue
        q1 = batcher.submit(servable, make_arrays(4, seed=1))
        q2 = batcher.submit(servable, make_arrays(4, seed=2))  # queue now full
        with pytest.raises(QueueOverloadError):
            batcher.submit(servable, make_arrays(4, seed=3))
        release.set()
        for f in (first, q1, q2):
            assert f.result(timeout=30)["prediction_node"].shape == (4,)
    finally:
        release.set()
        batcher.stop()


def test_cancelled_item_never_dispatched(servable):
    """A waiter that abandons its deadline (future.cancel) must not turn
    into a zombie dispatch delaying everyone behind it."""
    release = threading.Event()
    calls: list = []
    batcher = DynamicBatcher(
        buckets=(32,), max_wait_us=0,
        run_fn=_blocking_run_fn(release, calls),
        breaker_timeout_s=None,
    ).start()
    try:
        import time

        first = batcher.submit(servable, make_arrays(4))
        time.sleep(0.2)
        abandoned = batcher.submit(servable, make_arrays(8, seed=1))
        assert abandoned.cancel()
        release.set()
        assert first.result(timeout=30)["prediction_node"].shape == (4,)
        ok = batcher.submit(servable, make_arrays(4, seed=2))
        assert ok.result(timeout=30)["prediction_node"].shape == (4,)
        assert 8 not in calls  # the cancelled item's batch never ran
    finally:
        release.set()
        batcher.stop()


def test_exact_fill_fast_path_copies_caller_array(servable):
    """Mutating a submitted array after submit() must not race the async
    device upload (round-1 advisor finding): the exact-bucket-fill fast
    path must copy, not alias."""
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0, input_cache_entries=0).start()
    try:
        arrays = make_arrays(32)  # exactly fills the bucket
        want = reference_scores(servable, arrays)
        fut = batcher.submit(servable, arrays)
        arrays["feat_wts"][:] = -1e9  # caller mutates immediately after submit
        got = fut.result(timeout=30)["prediction_node"]
        np.testing.assert_allclose(got, want, rtol=1e-6)
    finally:
        batcher.stop()


def test_warmup_compile_does_not_trip_breaker(servable):
    """Hot-load warmup (warmup_via_queue) legitimately spends a long time
    compiling on the batcher thread; the wedge clock must not count it, or
    every version rollout would shed live traffic."""
    import time

    def slow_warmup_run(servable, batched):
        time.sleep(0.8)  # far past the breaker threshold below
        n = batched["feat_ids"].shape[0]
        return {"prediction_node": np.zeros((n,), np.float32)}

    batcher = DynamicBatcher(
        buckets=(8, 32), max_wait_us=0,
        run_fn=slow_warmup_run,
        breaker_timeout_s=0.3,
    ).start()
    try:
        t = threading.Thread(
            target=lambda: batcher.warmup_via_queue(servable, buckets=(8, 32)),
            daemon=True,
        )
        t.start()
        time.sleep(0.5)  # inside the first slow warmup dispatch
        # A live submit during warmup must be accepted, not DeviceWedged.
        fut = batcher.submit(servable, make_arrays(4))
        assert fut.result(timeout=30)["prediction_node"].shape == (4,)
        t.join(timeout=30)
        assert not t.is_alive()
    finally:
        batcher.stop()


# ------------------------------------------------- the native batch assembler
#
# One pass from the requests' arrays to the upload's words (hostops.cc
# assemble_batch) for every combined layout: the benchmark's three
# configurations at tiny tables, and the batches that must stay generic.

_NATIVE_CONFIGS = {
    # kind, fields, extra config, the ids' packed form
    "dcn_v2_ref43": ("dcn_v2", 43, {}, "feat_ids int32/32b x1"),
    "dlrm_mlperf": ("dlrm", 26, {"bottom_mlp_dims": (16, 4)}, "feat_ids int32/24b x4"),
    "dlrm_dcnv2_mlperf": (
        "dlrm_dcnv2", 214,
        {"bottom_mlp_dims": (16, 4), "cross_low_rank": 4, "multi_hot_sizes": (
            3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100, 27, 10, 3, 1, 1)},
        "feat_ids int32/24b x4",
    ),
}


def _native_servable(name, **model_overrides):
    import dataclasses

    kind, fields, extra, _ = _NATIVE_CONFIGS[name]
    cfg = ModelConfig(
        num_fields=fields, vocab_size=1009, embed_dim=4, mlp_dims=(16,),
        num_cross_layers=1, compute_dtype="bfloat16", **extra,
    )
    model = build_model(kind, cfg)
    if model_overrides:
        model = dataclasses.replace(model, **model_overrides)
    return Servable(
        name=name, version=1, model=model,
        params=jax.jit(model.init)(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(
            fields, with_dense=cfg.num_dense_features if model.takes_dense else None
        ),
    )


def _native_payload(sv, n, seed):
    rng = np.random.RandomState(seed)
    cfg = sv.model.config
    out = {
        "feat_ids": rng.randint(0, 1 << 40, size=(n, cfg.num_fields)).astype(np.int64),
        "feat_wts": rng.rand(n, cfg.num_fields).astype(np.float32),
    }
    if sv.model.takes_dense:
        out["dense_features"] = rng.randn(n, cfg.num_dense_features).astype(np.float32)
    return out


def _phase_counts(before):
    from distributed_tf_serving_tpu.utils.tracing import request_trace

    now = request_trace.snapshot()
    return {
        p: now.get(p, {}).get("count", 0) - before.get(p, {}).get("count", 0)
        for p in ("batch.pad", "batch.cache", "batch.fusedpack", "batch.dispatch")
    }


def _score_burst(sv, payloads, **batcher_kwargs):
    """The payloads submitted together (so they coalesce), then one alone:
    scores in order, the batcher's stats, the spans the batches emitted."""
    from distributed_tf_serving_tpu.utils.tracing import request_trace

    batcher = DynamicBatcher(buckets=(16, 64), max_wait_us=20000, **batcher_kwargs).start()
    try:
        before = request_trace.snapshot()
        futures = [batcher.submit(sv, p) for p in payloads]
        scores = [f.result(timeout=120)["prediction_node"] for f in futures]
        scores.append(batcher.submit(sv, payloads[0]).result(timeout=120)["prediction_node"])
        return np.concatenate(scores), batcher.stats, _phase_counts(before), batcher
    finally:
        batcher.stop()


@pytest.mark.parametrize("name", sorted(_NATIVE_CONFIGS))
def test_every_batch_takes_the_native_assembler(name, monkeypatch):
    """Each of the benchmark's configurations, at tiny tables: every batch is
    assembled natively, `batch.cache` is emitted once a batch with
    `batch.fusedpack` inside it and `batch.pad` never, /monitoring names the
    assembler, and the scores are the generic path's to the last bit."""
    from distributed_tf_serving_tpu import native
    from distributed_tf_serving_tpu.ops import transfer

    if not native.ensure():
        pytest.skip("native hostops unavailable")
    if name == "dcn_v2_ref43":
        # The configuration's table has 1 << 27 rows, past what three bytes
        # hold; a tiny table takes the same int32/32b id form this way.
        monkeypatch.setattr(transfer, "U24_MAX", 1 << 8)
    sv = _native_servable(name)
    payloads = [_native_payload(sv, n, seed=n) for n in (5, 13, 1, 22)]
    got, stats, spans, batcher = _score_burst(sv, payloads)
    assert stats.batches >= 2 and stats.fused_batches == stats.batches
    assert spans == {
        "batch.pad": 0, "batch.cache": stats.batches,
        "batch.fusedpack": stats.batches, "batch.dispatch": stats.batches,
    }
    assert batcher.assemblers() == {f"{name}:1": "native"}
    assert _NATIVE_CONFIGS[name][3] in batcher.upload_formats()[f"{name}:1"]
    monkeypatch.setattr(native, "available", lambda: False)
    want, stats, spans, batcher = _score_burst(sv, payloads)
    assert stats.fused_batches == 0 and spans["batch.fusedpack"] == 0
    assert spans["batch.pad"] == spans["batch.cache"] == stats.batches
    assert batcher.assemblers() == {f"{name}:1": "generic: no native library"}
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("case", ["dedup collapse", "x64 model", "compress_transfer off", "custom run_fn"])
def test_batches_the_native_assembler_leaves_to_the_generic_path(case):
    """What must stay generic does, by what the code observes, with the
    same scores; a batch the dedup screen found all-unique rides native."""
    from distributed_tf_serving_tpu import native

    if not native.ensure():
        pytest.skip("native hostops unavailable")
    plain = _native_servable("dlrm_mlperf")
    payloads = [_native_payload(plain, n, seed=n) for n in (5, 13)]
    want, stats, _, _ = _score_burst(plain, payloads)
    assert stats.fused_batches == stats.batches
    sv, kwargs, why = plain, {}, None
    if case == "dedup collapse":
        kwargs = {"dedup": True}
        payloads = payloads + [payloads[0]]  # a duplicate of every row of one
        want = np.concatenate([want[:18], want[:5], want[18:]])
    elif case == "x64 model":
        sv, why = _native_servable("dlrm_mlperf", needs_x64=True), "x64 model"
    elif case == "compress_transfer off":
        kwargs, why = {"compress_transfer": False}, "compress_transfer off"
    else:
        def run_fn(servable, arrays):
            return servable.model.apply(servable.params, arrays)

        kwargs, why = {"run_fn": run_fn}, "custom run_fn"
    got, stats, spans, batcher = _score_burst(sv, payloads, **kwargs)
    if case == "dedup collapse":
        # The burst collapses (generic, from the unique rows); the lone
        # request after it has nothing to collapse and rides native.
        assert stats.dedup_batches >= 1
        assert 0 < stats.fused_batches < stats.batches
        assert spans["batch.pad"] == stats.batches - stats.fused_batches
        np.testing.assert_array_equal(got, want)
    else:
        assert stats.fused_batches == 0 and spans["batch.fusedpack"] == 0
        assert spans["batch.pad"] == stats.batches
        if why != "custom run_fn":
            assert batcher.assemblers() == {"dlrm_mlperf:1": f"generic: {why}"}
        if case == "compress_transfer off":
            np.testing.assert_allclose(got, want, atol=1e-2)  # f32 weights, not bf16
        else:
            np.testing.assert_allclose(got, want, rtol=2e-2, atol=1e-3)


def test_holds_open_weighs_the_pipeline_and_the_load():
    """The rule alone: saturated always holds; an empty pipeline never; a
    natively assembled batch also behind a busy dispatch thread, and behind
    a batch in flight once requests arrive as fast as batches cross."""
    batcher = DynamicBatcher(buckets=(16,), pipeline_depth=2)
    assert not batcher._holds_open(True, 0) and not batcher._holds_open(False, 0)
    assert batcher._holds_open(False, 2) and batcher._holds_open(True, 2)
    assert not batcher._holds_open(False, 1)  # the collector pads beside the stage
    assert not batcher._holds_open(True, 1)  # in flight, no load measured yet
    batcher._arrival_gap_s, batcher._traversal_s = 0.005, 0.002
    assert not batcher._holds_open(True, 1)  # a lone request overlaps the flight
    batcher._arrival_gap_s = 0.002
    assert batcher._holds_open(True, 1) and not batcher._holds_open(False, 1)
    batcher._arrival_gap_s, batcher._dispatch_pending = 0.005, 1
    assert batcher._holds_open(True, 1) and not batcher._holds_open(False, 1)


@pytest.mark.parametrize("loaded", [True, False])
def test_native_batches_fill_while_a_batch_is_in_flight_under_load(monkeypatch, loaded):
    """One natively assembled batch parked in flight (its readback blocked),
    then a trickle: under load (arrivals as fast as crossings) the trickle
    waits for the flight and lands in ONE batch; below it each request
    dispatches at once, beside the flight, as before."""
    from distributed_tf_serving_tpu import native

    if not native.ensure():
        pytest.skip("native hostops unavailable")
    sv = _native_servable("dlrm_mlperf")
    release = threading.Event()
    batcher = DynamicBatcher(buckets=(16, 64), max_wait_us=0, pipeline_depth=8)
    batcher.warmup(sv)  # no compile inside a stage: a busy dispatch thread holds too
    batcher.start()
    real = batcher._execute_fused
    parked = []

    def execute(ctx, bucket, *args, **kwargs):
        if not parked:  # the first batch alone: its readback blocks
            parked.append(bucket)
            return {"prediction_node": _LazyReadback(bucket, release)}
        return real(ctx, bucket, *args, **kwargs)

    monkeypatch.setattr(batcher, "_execute_fused", execute)
    keys = ("prediction_node",)
    try:
        first = batcher.submit(sv, _native_payload(sv, 4, 0), output_keys=keys)
        deadline = time.perf_counter() + 10
        while not batcher._inflight and time.perf_counter() < deadline:
            time.sleep(0.002)
        assert len(batcher._inflight) == 1 and batcher.stats.batches == 1
        trickled = []
        for s in range(1, 5):
            with batcher._cv:  # what the load averages would have settled at
                batcher._traversal_s = 0.05
                batcher._arrival_gap_s = 0.01 if loaded else 1.0
                batcher._last_arrival_t = None
            trickled.append(batcher.submit(sv, _native_payload(sv, 4, s), output_keys=keys))
            if loaded:
                time.sleep(0.03)
            else:  # answered while the first is still parked in flight
                assert trickled[-1].result(timeout=60)["prediction_node"].shape == (4,)
        assert not first.done()
        assert batcher.stats.batches == (1 if loaded else 5)
        release.set()
        for f in [first] + trickled:
            assert f.result(timeout=60)["prediction_node"].shape == (4,)
        assert batcher.stats.batches == (2 if loaded else 5)
        assert batcher.stats.fused_batches == batcher.stats.batches
        assert (batcher.stats.fill_waits > 0) == loaded
    finally:
        release.set()
        batcher.stop()


def test_native_batch_stays_open_while_the_dispatch_thread_is_in_a_stage(monkeypatch):
    """With the assembly on the dispatch thread a batch closed behind a
    running stage would only wait staged, closed to later arrivals: the
    collector keeps it open until the stage ends, whatever the load."""
    from distributed_tf_serving_tpu import native

    if not native.ensure():
        pytest.skip("native hostops unavailable")
    sv = _native_servable("dlrm_mlperf")
    batcher = DynamicBatcher(buckets=(16, 64), max_wait_us=0, pipeline_depth=8).start()
    real = batcher._execute_fused
    in_stage, leave = threading.Event(), threading.Event()

    def execute(ctx, bucket, *args, **kwargs):
        if not in_stage.is_set():  # the first batch's stage, held open
            in_stage.set()
            leave.wait(timeout=30)
        return real(ctx, bucket, *args, **kwargs)

    monkeypatch.setattr(batcher, "_execute_fused", execute)
    keys = ("prediction_node",)
    try:
        futures = [batcher.submit(sv, _native_payload(sv, 4, 0), output_keys=keys)]
        assert in_stage.wait(timeout=30)
        for s in range(1, 4):
            futures.append(batcher.submit(sv, _native_payload(sv, 4, s), output_keys=keys))
            time.sleep(0.03)
        with batcher._cv:
            assert batcher._dispatch_pending == 1  # nothing staged behind the stage
        leave.set()
        for f in futures:
            assert f.result(timeout=60)["prediction_node"].shape == (4,)
        assert batcher.stats.batches == 2 and batcher.stats.fused_batches == 2
    finally:
        leave.set()
        batcher.stop()


# ------------------------------------------------- the direct crossing (ISSUE 42)


def _below_the_threshold(batcher, parked=True):
    """A started batcher whose collector has parked, with the two load
    averages as a trickle leaves them: arrivals a second apart, a crossing
    of a millisecond."""
    deadline = time.perf_counter() + 10
    while parked and not batcher._collector_parked and time.perf_counter() < deadline:
        time.sleep(0.002)
    with batcher._cv:
        assert batcher._collector_parked == parked
        batcher._arrival_gap_s, batcher._traversal_s = 1.0, 0.001
        batcher._last_arrival_t = None
    return batcher


def _stage_threads(batcher, monkeypatch):
    """The names of the threads that ran a device stage, in order."""
    names = []
    real = batcher._run_stage

    def run_stage(*args, **kwargs):
        names.append(threading.current_thread().name)
        return real(*args, **kwargs)

    monkeypatch.setattr(batcher, "_run_stage", run_stage)
    return names


def _direct_servable(path):
    from distributed_tf_serving_tpu import native

    if not native.ensure():
        pytest.skip("native hostops unavailable")
    return _native_servable("dlrm_mlperf", **({"needs_x64": True} if path == "generic" else {}))


@pytest.mark.parametrize("path", ["native", "generic"])
def test_a_lone_blocking_request_crosses_direct(monkeypatch, path):
    """Below the threshold, the pipeline empty: the submitting thread closes
    the batch and runs its stage itself. The dispatch thread never runs, the
    collector never wakes, and the scores are the queued path's to the bit."""
    from distributed_tf_serving_tpu.utils.tracing import request_trace

    sv = _direct_servable(path)
    batcher = DynamicBatcher(buckets=(16, 64), max_wait_us=200_000)
    batcher.warmup(sv)
    batcher.start()
    threads = _stage_threads(batcher, monkeypatch)
    payloads = [_native_payload(sv, n, seed=n) for n in (5, 13, 16)]
    keys = ("prediction_node",)
    try:
        want = [batcher.submit(sv, p, output_keys=keys).result(timeout=60) for p in payloads]
        assert batcher.stats.direct_batches == 0 and batcher.stats.batches == 3
        assert set(threads) == {"batch-dispatch_0"}
        del threads[:]
        _below_the_threshold(batcher)
        before = request_trace.snapshot()
        me = threading.current_thread().name
        for p, w in zip(payloads, want):
            t0 = time.perf_counter()
            fut = batcher.submit(sv, p, output_keys=keys, _may_block=True)
            assert time.perf_counter() - t0 < 0.1  # no coalesce window (0.2 s) was waited
            np.testing.assert_array_equal(fut.result(timeout=60)["prediction_node"], w["prediction_node"])
            with batcher._cv:
                assert batcher._dispatch_pending == 0 and batcher._direct_item is None
                batcher._last_arrival_t = None  # the trickle, not this loop's pace
        assert threads == [me] * 3
        assert batcher.stats.direct_batches == 3 and batcher.stats.batches == 6
        assert (batcher.stats.fused_batches == 6) == (path == "native")
        now = request_trace.snapshot()
        assert now["batch.direct"]["count"] - before["batch.direct"]["count"] == 3
        assert now["batch.dispatch"]["count"] - before["batch.dispatch"]["count"] == 3
        # The collector slept through all three.
        assert now["wait.queue_empty"]["count"] == before["wait.queue_empty"]["count"]
    finally:
        batcher.stop()


def _bar_state(bar, batcher):
    if bar == "a stage pending":
        batcher._dispatch_pending = 1
    elif bar == "another crossing under way":
        batcher._direct_item = object()
    elif bar == "the pipeline full":
        batcher._inflight.update({-1: 0.0, -2: 0.0})
    elif bar == "the queue holds an item":
        batcher._items.append(object())
    elif bar == "the collector has a batch open":
        batcher._collector_parked = False
    elif bar == "gap equals crossing":
        batcher._arrival_gap_s = batcher._traversal_s
    elif bar == "gap unknown":
        batcher._arrival_gap_s = None
    elif bar == "crossing unknown":
        batcher._traversal_s = None
    elif bar == "stopping":
        batcher._stopping = True
    else:
        raise AssertionError(bar)


@pytest.mark.parametrize("bar", [
    "a stage pending", "another crossing under way", "the pipeline full",
    "the queue holds an item", "the collector has a batch open",
    "gap equals crossing", "gap unknown", "crossing unknown", "stopping",
    "a solo item", "a warm-up item", "a bisection half",
])
def test_what_keeps_a_request_off_the_direct_crossing(servable, bar):
    """The rule alone, on the state the batcher keeps: one batch in flight
    is no obstacle; each of these is."""
    import dataclasses
    from concurrent.futures import Future

    from distributed_tf_serving_tpu.serving.batcher import _WorkItem

    batcher = DynamicBatcher(buckets=(16,), pipeline_depth=2)
    item = _WorkItem(
        servable=servable, arrays=make_arrays(4), n=4, future=Future(),
        enqueue_t=time.perf_counter(), output_keys=None,
    )
    with batcher._cv:
        batcher._collector_parked = True
        batcher._arrival_gap_s, batcher._traversal_s = 0.006, 0.003
        assert batcher._crosses_direct_locked(item)
        batcher._inflight[-1] = 0.0  # on the device: the stage is free
        assert batcher._crosses_direct_locked(item)
        del batcher._inflight[-1]
        kind = {"a solo item": {"solo": True}, "a warm-up item": {"warmup": True},
                "a bisection half": {"bisect_key": 1}}.get(bar)
        if kind is not None:
            item = dataclasses.replace(item, **kind)
        else:
            _bar_state(bar, batcher)
        assert not batcher._crosses_direct_locked(item)


@pytest.mark.parametrize("case", ["may not block", "solo", "warm-up", "past deadline"])
def test_submits_that_stay_off_the_direct_crossing(servable, monkeypatch, case):
    """Through submit(): a caller that did not say it may block (the asyncio
    transport, a direct user) queues, as a solo and a warm-up item do; a
    request whose deadline has passed is shed as _take sheds one."""
    from distributed_tf_serving_tpu.serving.batcher import RequestDeadlineError

    batcher = _below_the_threshold(DynamicBatcher(buckets=(16, 64), max_wait_us=0).start())
    threads = _stage_threads(batcher, monkeypatch)
    kwargs = {
        "may not block": {},
        "solo": {"_may_block": True, "_solo": True},
        "warm-up": {"_may_block": True, "_warmup": True},
        "past deadline": {"_may_block": True, "deadline_s": -0.001},
    }[case]
    try:
        fut = batcher.submit(servable, make_arrays(7), **kwargs)
        if case == "past deadline":
            with pytest.raises(RequestDeadlineError):
                fut.result(timeout=30)
            assert batcher.stats.deadline_sheds == 1 and batcher.stats.batches == 0
            with batcher._cv:
                assert batcher._queued_candidates == 0 and batcher._dispatch_pending == 0
                assert batcher._direct_item is None
        else:
            assert fut.result(timeout=60)["prediction_node"].shape == (7,)
            assert threads == ["batch-dispatch_0"]
        assert batcher.stats.direct_batches == 0
    finally:
        batcher.stop()


@pytest.mark.parametrize("second", ["native", "generic", "solo"])
def test_what_arrives_during_a_direct_stage_queues_until_it_ends(monkeypatch, second):
    """One request in its direct stage, held there: the next ones see a
    stage running and queue. A natively assembled batch stays open for the
    stage (_holds_open); a generic-path batch and a solo item close at once
    and wait the crossing out before their _dispatch. Either way no stage
    starts beside the direct one, and all of them are answered after it."""
    sv = _direct_servable("generic" if second == "generic" else "native")
    batcher = DynamicBatcher(buckets=(16, 64), max_wait_us=0, pipeline_depth=8)
    batcher.warmup(sv)
    batcher.start()
    _below_the_threshold(batcher)
    name = "_execute" if second == "generic" else "_execute_fused"
    real = getattr(batcher, name)
    in_stage, leave = threading.Event(), threading.Event()
    running, beside = [0], []

    def execute(*args, **kwargs):
        running[0] += 1
        beside.append(running[0])
        try:
            if not in_stage.is_set():  # the direct stage, held open
                in_stage.set()
                leave.wait(timeout=30)
            return real(*args, **kwargs)
        finally:
            running[0] -= 1

    monkeypatch.setattr(batcher, name, execute)
    threads = _stage_threads(batcher, monkeypatch)
    keys = ("prediction_node",)
    futures = []

    def first():
        futures.append(batcher.submit(sv, _native_payload(sv, 4, 0), output_keys=keys, _may_block=True))

    handler = threading.Thread(target=first, name="handler")
    try:
        handler.start()
        assert in_stage.wait(timeout=30)
        later = []
        for s in range(1, 4):
            with batcher._cv:
                batcher._last_arrival_t = None  # the averages stay a trickle's
            later.append(batcher.submit(
                sv, _native_payload(sv, 4, s), output_keys=keys, _may_block=True,
                _solo=second == "solo",
            ))
            time.sleep(0.03)
        with batcher._cv:
            assert batcher._dispatch_pending == 1  # the direct stage's, nothing staged
            assert batcher._direct_item is not None and not batcher._staged_groups
        assert threads == ["handler"] and batcher.stats.batches == 0
        assert not any(f.done() for f in later)
        leave.set()
        handler.join(timeout=30)
        for f in futures + later:
            assert f.result(timeout=60)["prediction_node"].shape == (4,)
        assert batcher.stats.direct_batches == 1
        # Held open, the three ride one batch; closed early, the generic
        # path's first goes alone and a solo item always does.
        assert batcher.stats.batches == {"native": 2, "generic": 3, "solo": 4}[second]
        assert set(threads[1:]) == {"batch-dispatch_0"}
        assert max(beside) == 1
    finally:
        leave.set()
        batcher.stop()


class _Escape(BaseException):
    """What no `except Exception` catches."""


@pytest.mark.parametrize("where", ["the stage", "the assembly", "past every except"])
def test_an_error_in_a_direct_crossing_fails_that_request_alone(servable, monkeypatch, where):
    """The request gets the error the queued path would give it, the pending
    count comes back, nothing is reported dead, and the next request, direct
    again, is answered."""
    batcher = _below_the_threshold(DynamicBatcher(buckets=(16, 64), max_wait_us=0).start())
    error = _Escape("boom") if where == "past every except" else RuntimeError("boom")
    failed = []

    def failing_once(real):
        def call(*args, **kwargs):
            if not failed:
                failed.append(threading.current_thread().name)
                raise error
            return real(*args, **kwargs)
        return call

    # Whichever assembler takes the batch, its stage goes through one of two.
    for name in ("_fused_ctx",) if where == "the assembly" else ("_execute", "_execute_fused"):
        monkeypatch.setattr(batcher, name, failing_once(getattr(batcher, name)))
    try:
        bad = batcher.submit(servable, make_arrays(7), _may_block=True)
        with pytest.raises(type(error), match="boom"):
            bad.result(timeout=30)
        assert failed == [threading.current_thread().name]
        with batcher._cv:
            assert batcher._dispatch_pending == 0 and batcher._direct_item is None
            assert batcher._dispatching_since is None and not batcher._inflight
            assert batcher._dead is None
            batcher._last_arrival_t = None
        arrays = make_arrays(9, seed=1)
        got = batcher.submit(servable, arrays, _may_block=True).result(timeout=60)
        np.testing.assert_allclose(got["prediction_node"], reference_scores(servable, arrays), rtol=1e-6)
        assert batcher.stats.direct_batches == 1 and batcher.stats.batches == 1
    finally:
        batcher.stop()


@pytest.mark.parametrize("phases, want", [
    ({"batch.dispatch": {"count": 200, "total_ms": 300.0},
      "batch.direct": {"count": 150, "total_ms": 0.0}}, 75.0),
    # A server that crossed nothing direct (a closed loop) reads 0 ...
    ({"batch.dispatch": {"count": 200, "total_ms": 300.0},
      "batch.direct": {"count": 0, "total_ms": 0.0}}, 0.0),
    # ... the commit before ISSUE 42, which has no such phase, nothing ...
    ({"batch.dispatch": {"count": 200, "total_ms": 300.0}}, None),
    # ... and so does a window without a batch.
    ({"batch.dispatch": {"count": 0, "total_ms": 0.0},
      "batch.direct": {"count": 0, "total_ms": 0.0}}, None),
])
def test_direct_batch_pct_on_a_made_up_window(phases, want):
    """benchmark/layers/direct_batch_pct.py over a window's phase deltas."""
    import os
    import sys

    layers = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "layers")
    sys.path.insert(0, layers)
    try:
        from benchmark.common import load_module

        read = load_module(os.path.join(layers, "direct_batch_pct.py"), "bench_layer_direct").read
    finally:
        sys.path.remove(layers)
    got = read({"phases": phases})
    assert got is None if want is None else got == pytest.approx(want)


def test_a_started_batcher_shows_the_direct_phase_at_zero():
    """`batch.direct` is on /monitoring?section=phases from start(), so a
    scraper tells a server that crosses nothing direct from one that
    cannot; a phase that stands at count 0 has a mean of 0."""
    from distributed_tf_serving_tpu.utils import tracing

    trace = tracing.PhaseTrace()
    trace.add_many((("batch.direct", 0.0, 0),))
    assert trace.snapshot()["batch.direct"] == {"total_ms": 0.0, "count": 0, "mean_us": 0.0}
    batcher = DynamicBatcher(buckets=(16,)).start()
    try:
        assert "batch.direct" in tracing.request_trace.snapshot()
    finally:
        batcher.stop()
