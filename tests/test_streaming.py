"""Streamed sub-batch Predict + continuous-batching pipeline (ISSUE 9):
the PredictStream RPC end to end (service generator, both transports,
UDS), the client's incremental out-of-order merge, partial-failure
degradation with the scoreboard, deadline expiry mid-stream, the k-deep
in-flight window, the buffer ring, and the [batching] /
[transport] config sections."""

import asyncio
import pathlib
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
grpc = pytest.importorskip("grpc")

from distributed_tf_serving_tpu import codec, faults
from distributed_tf_serving_tpu.client import (
    ShardedPredictClient,
    StreamingMerger,
    build_predict_request,
)
from distributed_tf_serving_tpu.models import (
    ModelConfig,
    Servable,
    ServableRegistry,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.proto import serving_apis_pb2 as apis
from distributed_tf_serving_tpu.proto.service_grpc import PredictionServiceStub
from distributed_tf_serving_tpu.serving.batcher import DynamicBatcher, fold_ids_host
from distributed_tf_serving_tpu.serving.server import create_server
from distributed_tf_serving_tpu.serving.service import (
    PredictionServiceImpl,
    ServiceError,
)
from distributed_tf_serving_tpu.utils.config import (
    BatchingConfig,
    TransportConfig,
    load_config,
)

CFG = ModelConfig(
    num_fields=8, vocab_size=1009, embed_dim=4, mlp_dims=(16,),
    num_cross_layers=1, compute_dtype="float32",
)


@pytest.fixture(scope="module")
def servable():
    model = build_model("dcn", CFG)
    return Servable(
        name="DCN", version=1, model=model,
        params=model.init(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(CFG.num_fields),
    )


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


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


def make_stack(servable, **batcher_kw):
    registry = ServableRegistry()
    registry.load(servable)
    kw = dict(buckets=(32, 64, 128), max_wait_us=0)
    kw.update(batcher_kw)
    batcher = DynamicBatcher(**kw).start()
    return registry, batcher, PredictionServiceImpl(registry, batcher)


def drain_stream(gen):
    """Consume a predict_stream generator -> (merged scores, chunk list)."""
    chunks = list(gen)
    total = chunks[0].total
    merger = StreamingMerger(total)
    for c in chunks:
        merger.add(c.offset, codec.to_ndarray(c.outputs["prediction_node"]))
    return merger.result(), chunks


# --------------------------------------------------- StreamingMerger unit


def test_merger_out_of_order_scatter():
    m = StreamingMerger(10)
    m.add(6, np.arange(6, 10, dtype=np.float32))
    assert not m.complete and m.missing_ranges() == ((0, 6),)
    m.add(0, np.arange(0, 3, dtype=np.float32))
    m.add(3, np.arange(3, 6, dtype=np.float32))
    assert m.complete and m.chunks == 3
    np.testing.assert_array_equal(m.result(), np.arange(10, dtype=np.float32))


def test_merger_rejects_overlap_and_out_of_bounds():
    m = StreamingMerger(8)
    m.add(0, np.zeros(4, np.float32))
    with pytest.raises(ValueError, match="overlaps"):
        m.add(2, np.zeros(3, np.float32))
    with pytest.raises(ValueError, match="outside"):
        m.add(6, np.zeros(4, np.float32))
    with pytest.raises(ValueError, match="missing"):
        m.result()


# ----------------------------------------------------- service generator


def test_stream_plan_split_and_clamp(servable):
    _reg, batcher, impl = make_stack(servable)
    try:
        assert impl._stream_plan(100, None) == [(0, 100)]  # off by default
        impl.stream_chunk_candidates = 32
        assert impl._stream_plan(100, None) == [
            (0, 32), (32, 32), (64, 32), (96, 4)
        ]
        assert impl._stream_plan(100, 50) == [(0, 50), (50, 50)]  # override
        # A 1-candidate override on a big request clamps to <= 64 chunks.
        plan = impl._stream_plan(1000, 1)
        assert len(plan) <= impl._STREAM_MAX_CHUNKS
        assert sum(c for _o, c in plan) == 1000
    finally:
        batcher.stop()


def test_streamed_bit_identical_and_out_of_order(servable):
    """The tentpole acceptance shape: streamed sub-batch results merge to
    EXACTLY the unary scores even when readbacks complete out of order
    (first batch's D2H delayed past its siblings')."""
    _reg, batcher, impl = make_stack(
        servable, pipeline_depth=4, inflight_window=4, buffer_ring=True,
    )
    try:
        arrays = make_arrays(100, seed=3)
        req = build_predict_request(
            arrays, "DCN", output_filter=("prediction_node",)
        )
        unary = codec.to_ndarray(
            impl.predict(req).outputs["prediction_node"]
        )
        # Delay exactly the FIRST batch's readback: its chunk must flush
        # AFTER its siblings (out-of-order arrival) and the merge must
        # still be bit-identical.
        faults.get().add("readback", "delay", delay_s=0.4, count=1)
        merged, chunks = drain_stream(impl.predict_stream(req, chunk=32))
        assert len(chunks) == 4
        assert [c.final for c in chunks].count(True) == 1
        assert chunks[-1].final
        offsets = [c.offset for c in chunks]
        assert offsets != sorted(offsets), (
            f"chunks arrived in offset order {offsets} despite the "
            "first readback being delayed — not completion-ordered"
        )
        assert np.array_equal(merged, unary)
        assert batcher.stats.inflight_peak >= 2  # sub-batches pipelined
    finally:
        batcher.stop()


def test_stream_single_chunk_when_disabled(servable):
    """stream_chunk_candidates=0 and no override: the stream degenerates
    to ONE chunk (new behavior off by default), still bit-identical."""
    _reg, batcher, impl = make_stack(servable)
    try:
        arrays = make_arrays(40, seed=5)
        req = build_predict_request(
            arrays, "DCN", output_filter=("prediction_node",)
        )
        unary = codec.to_ndarray(impl.predict(req).outputs["prediction_node"])
        merged, chunks = drain_stream(impl.predict_stream(req))
        assert len(chunks) == 1 and chunks[0].final
        assert chunks[0].offset == 0 and chunks[0].count == 40
        assert np.array_equal(merged, unary)
    finally:
        batcher.stop()


def test_stream_deadline_expires_mid_stream(servable):
    """A deadline expiring while sub-batches are still pending aborts the
    stream DEADLINE_EXCEEDED and withdraws the remaining work."""
    _reg, batcher, impl = make_stack(servable, pipeline_depth=2)
    try:
        # Every dispatch stalls well past the deadline.
        faults.get().add("batcher.dispatch", "delay", delay_s=1.0)
        req = build_predict_request(
            make_arrays(100, seed=7), "DCN",
            output_filter=("prediction_node",),
        )
        t0 = time.perf_counter()
        with pytest.raises(ServiceError) as exc_info:
            for _chunk in impl.predict_stream(req, deadline_s=0.3, chunk=32):
                pass
        assert exc_info.value.code == "DEADLINE_EXCEEDED"
        assert time.perf_counter() - t0 < 5.0  # gave up at the deadline
    finally:
        batcher.stop()


def test_stream_arena_mode_identical_chunks(servable):
    """response_arena=True (reused encode scratch + ONE reused chunk
    message per stream) must serialize chunk-for-chunk identical wire
    bytes to the allocate-per-chunk default.

    The `final` flag is normalized out of the comparison: it rides
    whichever chunk is EMITTED last, and emission order is completion
    order — nondeterministic by design (a cold jit cache or scheduler
    jitter legitimately reorders the two runs). Each run is separately
    required to mark exactly one chunk final."""
    _reg, batcher, impl = make_stack(servable)
    try:
        impl.stream_chunk_candidates = 16
        arrays = make_arrays(60, seed=11)
        req = build_predict_request(
            arrays, "DCN", output_filter=("prediction_node",)
        )

        def by_offset(stream):
            chunks = {}
            finals = 0
            for c in stream:
                finals += bool(c.final)
                c.final = False  # order-dependent: compared separately
                chunks[c.offset] = c.SerializeToString()
            return chunks, finals

        plain, finals_plain = by_offset(impl.predict_stream(req))
        impl.response_arena = True
        arena, finals_arena = by_offset(impl.predict_stream(req))
        assert finals_plain == 1 and finals_arena == 1
        assert plain.keys() == arena.keys()
        for off in plain:
            assert plain[off] == arena[off]
    finally:
        batcher.stop()


# ------------------------------------------------------- wire transports


def test_stream_over_grpc_tcp_and_uds(servable, tmp_path):
    """PredictStream over a real socket, TCP and Unix-domain: chunked,
    final-flagged, bit-identical to unary over the same channel."""
    _reg, batcher, impl = make_stack(servable, pipeline_depth=4)
    impl.stream_chunk_candidates = 32
    uds = str(tmp_path / "dts.sock")
    server, port = create_server(impl, "127.0.0.1:0", uds_path=uds)
    server.start()
    try:
        arrays = make_arrays(90, seed=13)
        req = build_predict_request(
            arrays, "DCN", output_filter=("prediction_node",)
        )
        results = {}
        for target in (f"127.0.0.1:{port}", f"unix:{uds}"):
            with grpc.insecure_channel(target) as ch:
                stub = PredictionServiceStub(ch)
                unary = codec.to_ndarray(
                    stub.Predict(req, timeout=30).outputs["prediction_node"]
                )
                chunks = list(stub.PredictStream(req, timeout=30))
                assert len(chunks) == 3
                assert sum(c.count for c in chunks) == 90
                assert sum(1 for c in chunks if c.final) == 1
                merger = StreamingMerger(90)
                for c in chunks:
                    merger.add(
                        c.offset,
                        codec.to_ndarray(c.outputs["prediction_node"]),
                    )
                assert np.array_equal(merger.result(), unary)
                results[target] = merger.result()
        tcp, unix = results.values()
        assert np.array_equal(tcp, unix)
    finally:
        server.stop(0)
        batcher.stop()


def test_uds_refused_next_to_tls(servable, tmp_path):
    """The UDS listener is plaintext: binding it next to a TLS-secured
    TCP port would open an unauthenticated local side door — refused at
    create_server (before any port binds)."""
    _reg, batcher, impl = make_stack(servable)
    try:
        with pytest.raises(ValueError, match="plaintext"):
            create_server(
                impl, "127.0.0.1:0", credentials=object(),
                uds_path=str(tmp_path / "dts.sock"),
            )
    finally:
        batcher.stop()


def test_stream_chunk_metadata_override(servable):
    """x-dts-stream-chunk metadata overrides the server default split."""
    _reg, batcher, impl = make_stack(servable)
    server, port = create_server(impl, "127.0.0.1:0")
    server.start()
    try:
        req = build_predict_request(
            make_arrays(64, seed=17), "DCN",
            output_filter=("prediction_node",),
        )
        with grpc.insecure_channel(f"127.0.0.1:{port}") as ch:
            stub = PredictionServiceStub(ch)
            chunks = list(stub.PredictStream(
                req, timeout=30, metadata=(("x-dts-stream-chunk", "16"),)
            ))
        assert [c.count for c in chunks].count(16) == 4
    finally:
        server.stop(0)
        batcher.stop()


def test_streamed_client_partial_failure_with_scoreboard(servable):
    """Client-side incremental merge under a dead backend: the failed
    shard degrades the merge (missing_ranges) instead of failing the
    request, and the scoreboard records the failure — the resilience
    semantics predict() has, preserved on the streamed path."""
    _reg, batcher, impl = make_stack(servable)
    server, port = create_server(impl, "127.0.0.1:0")
    server.start()
    good = f"127.0.0.1:{port}"
    bad = "127.0.0.1:1"  # never answers; the fault fails it instantly
    faults.get().add("client.rpc", "error", code="UNAVAILABLE", key=bad)

    async def run():
        async with ShardedPredictClient(
            [good, bad], "DCN", partial_results=True, scoreboard=True,
            stream_chunk_candidates=16, timeout_s=10.0,
        ) as client:
            arrays = make_arrays(80, seed=19)
            result = await client.predict_streamed(arrays)
            snap = client.scoreboard.snapshot()
            return result, snap, client.stream_stats()

    try:
        result, snap, stream_stats = asyncio.run(run())
        assert result.degraded
        assert result.missing_ranges == ((40, 80),)  # shard 1 = host `bad`
        assert result.scores.shape == (40,)
        want = reference_scores(servable, make_arrays(80, seed=19))[:40]
        np.testing.assert_allclose(result.scores, want, rtol=1e-6)
        assert snap["backends"][bad]["failures"] >= 1
        assert stream_stats["streamed_shards"] == 1  # the good shard
        assert stream_stats["stream_chunks"] >= 3
        assert stream_stats["first_score_p50_ms"] is not None
    finally:
        server.stop(0)
        batcher.stop()


def test_streamed_client_matches_unary_end_to_end(servable):
    _reg, batcher, impl = make_stack(servable, pipeline_depth=4)
    server, port = create_server(impl, "127.0.0.1:0")
    server.start()

    async def run():
        async with ShardedPredictClient(
            [f"127.0.0.1:{port}"], "DCN", stream_chunk_candidates=32,
        ) as client:
            arrays = make_arrays(100, seed=23)
            unary = await client.predict(arrays, sort_scores=True)
            streamed = await client.predict_streamed(arrays, sort_scores=True)
            return unary, streamed

    try:
        unary, streamed = asyncio.run(run())
        assert np.array_equal(unary, streamed)
    finally:
        server.stop(0)
        batcher.stop()


# ------------------------------------------- continuous-batching pipeline


class _LazyReadback:
    """Device-array stand-in whose host readback blocks until released —
    holds batches 'in flight' deterministically (test_batcher precedent)."""

    def __init__(self, n, release: threading.Event):
        self.n = n
        self.release = release

    def __array__(self, dtype=None, copy=None):
        assert self.release.wait(timeout=30)
        return np.zeros(self.n, np.float32)


def test_solo_items_never_coalesce(servable):
    """_solo submits (streamed sub-batches) each become their OWN device
    batch even inside a wide-open coalescing window."""
    batcher = DynamicBatcher(buckets=(32, 256), max_wait_us=50_000).start()
    try:
        futs = [
            batcher.submit(servable, make_arrays(8, seed=s), _solo=True)
            for s in range(4)
        ]
        for f in futs:
            f.result(timeout=30)
        assert batcher.stats.batches == 4
        assert batcher.stats.requests == 4
    finally:
        batcher.stop()


def test_inflight_window_bounds_issuance():
    """inflight_window=1: with batch 1's readback held open, batch 2 is
    NOT issued (peak stays 1, a window wait is recorded); releasing the
    readback lets the pipeline drain."""
    release = threading.Event()

    def run_fn(sv, arrays):
        n = next(iter(arrays.values())).shape[0]
        return {"prediction_node": _LazyReadback(n, release)}

    registry = ServableRegistry()
    model = build_model("dcn", CFG)
    sv = Servable(
        name="DCN", version=1, model=model,
        params=model.init(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(CFG.num_fields),
    )
    registry.load(sv)
    batcher = DynamicBatcher(
        buckets=(32,), max_wait_us=0, run_fn=run_fn,
        pipeline_depth=2, inflight_window=1,
    ).start()
    try:
        futs = [
            batcher.submit(sv, make_arrays(8, seed=s), _solo=True)
            for s in range(3)
        ]
        deadline = time.perf_counter() + 5
        while not batcher.stats.inflight_window_waits and \
                time.perf_counter() < deadline:
            time.sleep(0.01)
        with batcher._cv:
            assert len(batcher._inflight) <= 1
        assert batcher.stats.inflight_window_waits >= 1
        release.set()
        for f in futs:
            f.result(timeout=30)
        assert batcher.stats.inflight_peak == 1
        assert batcher.pipeline_stats()["in_flight"] == 0
    finally:
        release.set()
        batcher.stop()


def test_buffer_ring_reuses_and_stays_correct(servable):
    """Ring-recycled padded buffers must never change scores: sequential
    distinct payloads score identically to the reference while the ring
    reports reuse. The ring serves the generic pad path (the native
    assembler writes the upload's words and pads no array), taken here
    with the one-buffer upload off."""
    batcher = DynamicBatcher(
        buckets=(32, 64), max_wait_us=0, buffer_ring=True,
        compress_transfer=False,
    ).start()
    try:
        for s in range(6):
            arrays = make_arrays(20, seed=100 + s)
            got = batcher.submit(servable, arrays).result(timeout=30)[
                "prediction_node"
            ]
            np.testing.assert_allclose(
                got, reference_scores(servable, arrays), rtol=1e-6
            )
        snap = batcher.buffer_ring.snapshot()
        assert snap["reuses"] > 0
        assert snap["allocs"] <= 4  # 2 inputs x <= 2 bucket geometries
    finally:
        batcher.stop()


def test_per_bucket_inflight_accounting():
    """pipeline_stats' per-bucket occupancy tracks live batches and
    drains back to empty."""
    release = threading.Event()

    def run_fn(sv, arrays):
        n = next(iter(arrays.values())).shape[0]
        return {"prediction_node": _LazyReadback(n, release)}

    registry = ServableRegistry()
    model = build_model("dcn", CFG)
    sv = Servable(
        name="DCN", version=1, model=model,
        params=model.init(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(CFG.num_fields),
    )
    registry.load(sv)
    batcher = DynamicBatcher(
        buckets=(32, 64), max_wait_us=0, run_fn=run_fn,
        pipeline_depth=4, inflight_window=4,
    ).start()
    try:
        futs = [
            batcher.submit(sv, make_arrays(8, seed=s), _solo=True)
            for s in range(2)
        ]
        deadline = time.perf_counter() + 5
        while time.perf_counter() < deadline:
            stats = batcher.pipeline_stats()
            if stats["per_bucket_in_flight"].get(32, 0) == 2:
                break
            time.sleep(0.01)
        assert batcher.pipeline_stats()["per_bucket_in_flight"] == {32: 2}
        release.set()
        for f in futs:
            f.result(timeout=30)
        assert batcher.pipeline_stats()["per_bucket_in_flight"] == {}
    finally:
        release.set()
        batcher.stop()


# --------------------------------------------------------------- config


def test_batching_and_transport_sections_parse(tmp_path):
    cfg = tmp_path / "c.toml"
    cfg.write_text(
        """
[batching]
pipeline_depth = 4
inflight_window = 8
buffer_ring = true
stream_chunk_candidates = 1024

[transport]
uds_path = "/tmp/dts.sock"
response_arena = true
"""
    )
    out = load_config(cfg)
    b, t = out["batching"], out["transport"]
    assert (b.pipeline_depth, b.inflight_window, b.buffer_ring,
            b.stream_chunk_candidates) == (4, 8, True, 1024)
    assert (t.uds_path, t.response_arena) == ("/tmp/dts.sock", True)


def test_batching_config_validation():
    with pytest.raises(ValueError, match="non-negative"):
        BatchingConfig(pipeline_depth=-1)
    with pytest.raises(ValueError, match="HBM"):
        BatchingConfig(inflight_window=1000)
    with pytest.raises(ValueError, match="host:port"):
        TransportConfig(uds_path="localhost:9999")
    with pytest.raises(ValueError, match="AF_UNIX"):
        TransportConfig(uds_path="/" + "x" * 200)
    # Defaults are all-off (the acceptance criterion's contract).
    b = BatchingConfig()
    assert (b.pipeline_depth, b.inflight_window, b.buffer_ring,
            b.stream_chunk_candidates) == (0, 0, False, 0)
    t = TransportConfig()
    assert (t.uds_path, t.response_arena) == ("", False)


def test_preset_configs_carry_sections():
    root = pathlib.Path(__file__).resolve().parent.parent
    for name in ("latency.toml", "throughput.toml"):
        out = load_config(root / "configs" / name)
        # pipeline_depth now lives in [batching] (2 = historical value);
        # every NEW knob defaults off in the shipped presets.
        assert out["batching"].pipeline_depth == 2
        assert out["batching"].inflight_window == 0
        assert out["batching"].buffer_ring is False
        assert out["batching"].stream_chunk_candidates == 0
        assert out["transport"].uds_path == ""
        assert out["transport"].response_arena is False


# ----------------------------------------------------------- codec arena


def test_encode_arena_equivalence_and_reuse():
    from distributed_tf_serving_tpu.codec import EncodeArena, from_ndarray

    arena = EncodeArena()
    rng = np.random.RandomState(0)
    strided = rng.rand(64, 8).astype(np.float32)[::2]  # non-contiguous
    plain = from_ndarray(strided).SerializeToString()
    via_arena = from_ndarray(strided, arena=arena).SerializeToString()
    assert plain == via_arena
    # Second encode of the same geometry reuses the backing buffer.
    before = arena.grows
    from_ndarray(strided, arena=arena)
    assert arena.grows == before and arena.reuses > 0
    # widen_f32 matches astype.
    import ml_dtypes

    half = rng.rand(33).astype(ml_dtypes.bfloat16)
    np.testing.assert_array_equal(
        arena.widen_f32(half), half.astype(np.float32)
    )


def test_example_decode_arena_reuse():
    from distributed_tf_serving_tpu.codec import EncodeArena
    from distributed_tf_serving_tpu.serving.example_codec import (
        decode_input,
        make_example,
    )

    arena = EncodeArena()
    inp = apis.Input()
    for i in range(3):
        inp.example_list.examples.append(
            make_example(range(i, i + CFG.num_fields))
        )
    plain = decode_input(inp, CFG.num_fields)
    via = decode_input(inp, CFG.num_fields, arena=arena)
    np.testing.assert_array_equal(plain["feat_ids"], via["feat_ids"])
    np.testing.assert_array_equal(plain["feat_wts"], via["feat_wts"])
    # Same geometry decodes reuse the arena's backing storage.
    before = arena.grows
    decode_input(inp, CFG.num_fields, arena=arena)
    assert arena.grows == before
