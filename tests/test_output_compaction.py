"""Output-compaction + async-readback pipeline tests (ISSUE 1 tentpole):

- wire-dtype downcast happens ON-DEVICE, the completer widens back to f32,
  and the bytes_downloaded counter proves the D2H link carried the compact
  encoding (>=4x under the full-fp32 all-outputs baseline for score-only
  fetches at bf16);
- score parity <=1e-2 relative at bf16, bit-exact at the float32 fallback;
- the readback is two spans, readback.issue (dispatch side) and
  readback.wait (completer side), with and without the dispatch thread;
- top-k compaction returns the exact score head with indices, reconstructed
  to the full-length response vector;
- the float32 wire is the full-precision fallback, bit-exact whichever
  thread dispatches.
"""

import jax
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import (
    ModelConfig,
    Servable,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.serving import DynamicBatcher
from distributed_tf_serving_tpu.serving.batcher import fold_ids_host
from distributed_tf_serving_tpu.utils.tracing import PhaseTrace, request_trace

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


def golden(servable, arrays):
    batch = {
        "feat_ids": fold_ids_host(arrays["feat_ids"], CFG.vocab_size),
        "feat_wts": arrays["feat_wts"],
    }
    return np.asarray(servable.model.apply(servable.params, batch)["prediction_node"])


def test_bf16_wire_parity_and_byte_reduction(servable):
    """bf16 wire scores parity <=1e-2 relative; the bytes_downloaded
    counter must show >=4x under the full-fp32 all-outputs baseline for a
    score-only fetch (2 f32 outputs -> 1 bf16 output = 4x)."""
    batcher = DynamicBatcher(
        buckets=(32,), max_wait_us=0, output_wire_dtype="bfloat16"
    ).start()
    try:
        arrays = make_arrays(32)
        got = batcher.submit(
            servable, arrays, output_keys=("prediction_node",)
        ).result(timeout=30)["prediction_node"]
        assert got.dtype == np.float32  # widened transparently on the host
        want = golden(servable, arrays)
        np.testing.assert_allclose(got, want, rtol=1e-2)
        stats = batcher.stats
        # Baseline: prediction_node + logits, f32 -> 8 B/row over the
        # padded bucket. Actual: score-only bf16 -> 2 B/row.
        assert stats.bytes_download_full_f32 == 32 * 2 * 4
        assert stats.bytes_downloaded == 32 * 2
        assert stats.download_compaction_ratio >= 4.0
    finally:
        batcher.stop()


def test_f32_wire_is_exact(servable):
    """The float32 wire through the dispatch thread must be bit-identical to
    inline dispatch (same executables — the pipeline only changes which
    thread runs them, never the numerics)."""
    pipelined = DynamicBatcher(
        buckets=(32,), max_wait_us=0, output_wire_dtype="float32"
    ).start()
    legacy = DynamicBatcher(
        buckets=(32,), max_wait_us=0, output_wire_dtype="float32",
        pipelined_dispatch=False,
    ).start()
    try:
        arrays = make_arrays(19, seed=3)
        got = pipelined.submit(servable, arrays).result(timeout=30)["prediction_node"]
        ref = legacy.submit(servable, arrays).result(timeout=30)["prediction_node"]
        np.testing.assert_array_equal(got, ref)
    finally:
        pipelined.stop()
        legacy.stop()


def test_unknown_wire_dtype_rejected():
    with pytest.raises(ValueError, match="wire dtype"):
        DynamicBatcher(buckets=(32,), output_wire_dtype="float8")


def test_readback_span_split(servable):
    """The readback records readback.issue + readback.wait, and the overlap
    counters track a window at least as long as the blocked time."""
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0).start()
    try:
        request_trace.reset()
        batcher.submit(servable, make_arrays(8)).result(timeout=30)
        phases = request_trace.snapshot()
        assert "readback.issue" in phases
        assert "readback.wait" in phases
        stats = batcher.stats
        assert stats.readback_window_s >= stats.readback_blocked_s > 0
        assert 0.0 <= stats.readback_overlap_fraction <= 1.0
    finally:
        batcher.stop()
        request_trace.reset()


def test_sync_fallback_path(servable):
    """pipelined_dispatch=False + float32 wire is inline dispatch at full
    precision: no dispatch thread, the device stage runs on the batching
    thread, the fetch is still issued at dispatch and awaited by a
    completer, exact scores."""
    batcher = DynamicBatcher(
        buckets=(32,), max_wait_us=0,
        output_wire_dtype="float32", pipelined_dispatch=False,
    ).start()
    try:
        assert batcher._dispatcher is None
        request_trace.reset()
        arrays = make_arrays(16, seed=5)
        got = batcher.submit(servable, arrays).result(timeout=30)["prediction_node"]
        np.testing.assert_allclose(got, golden(servable, arrays), rtol=1e-6)
        phases = request_trace.snapshot()
        assert "readback.issue" in phases and "readback.wait" in phases
        stats = batcher.stats
        assert stats.readback_window_s >= stats.readback_blocked_s > 0
        assert stats.bytes_downloaded > 0
    finally:
        batcher.stop()
        request_trace.reset()


def test_topk_compaction_exact_head(servable):
    """Top-k compaction: a score-only single-request batch returns the
    exact top-k scores at their original indices, zeros elsewhere, and the
    D2H bytes are the k pairs, not the score vector."""
    k = 4
    batcher = DynamicBatcher(
        buckets=(64,), max_wait_us=0, output_top_k=k,
    ).start()
    try:
        arrays = make_arrays(48, seed=9)
        got = batcher.submit(
            servable, arrays, output_keys=("prediction_node",)
        ).result(timeout=30)["prediction_node"]
        want = golden(servable, arrays)
        assert got.shape == (48,)
        top = np.argsort(want)[-k:]
        np.testing.assert_allclose(got[top], want[top], rtol=1e-5)
        others = np.setdiff1d(np.arange(48), top)
        assert np.all(got[others] == 0.0)  # off-head = explicitly unranked
        assert batcher.stats.topk_batches == 1
        # k bf16/f32 scores + k int32 indices, NOT 64 rows of outputs.
        assert batcher.stats.bytes_downloaded == k * 4 + k * 4
    finally:
        batcher.stop()


def test_topk_skips_coalesced_groups(servable):
    """Top-k over a coalesced group would mix candidates across requests:
    a multi-request group must ride the full-vector path and each request
    still gets its own exact slice. Dispatched as a fabricated group so the
    coalescing outcome is deterministic, not timing-dependent."""
    import time
    from concurrent.futures import Future

    from distributed_tf_serving_tpu.serving.batcher import _WorkItem, prepare_inputs

    batcher = DynamicBatcher(
        buckets=(64,), max_wait_us=0, output_top_k=4,
    )
    try:
        arrays = [make_arrays(8, seed=20 + s) for s in range(2)]
        group = [
            _WorkItem(
                servable=servable,
                arrays=prepare_inputs(servable.model, a, fold_ids=False),
                n=8,
                future=Future(),
                enqueue_t=time.perf_counter(),
                output_keys=("prediction_node",),
            )
            for a in arrays
        ]
        batcher._dispatch(group, 16)
        for it, a in zip(group, arrays):
            got = it.future.result(timeout=30)["prediction_node"]
            np.testing.assert_allclose(got, golden(servable, a), rtol=1e-5)
            assert np.all(got > 0)  # full vector: no zeroed tail
        assert batcher.stats.topk_batches == 0
        assert batcher.stats.batches == 1
    finally:
        batcher.stop()


def test_output_selection_traced_into_entry(servable):
    """A score-only fetch must not download the logits tensor: actual
    bytes track the single output, while the full-f32 baseline charges
    both declared outputs."""
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0).start()
    try:
        batcher.submit(
            servable, make_arrays(32), output_keys=("prediction_node",)
        ).result(timeout=30)
        assert batcher.stats.bytes_downloaded == 32 * 4  # one f32 vector
        assert batcher.stats.bytes_download_full_f32 == 32 * 8  # both outputs
    finally:
        batcher.stop()


def test_phase_trace_add():
    tr = PhaseTrace()
    tr.add("x", 0.5)
    tr.add("x", 0.25)
    snap = tr.snapshot()
    assert snap["x"]["count"] == 2
    assert snap["x"]["total_ms"] == 750.0


def test_codec_roundtrips_wire_dtypes_bit_exact():
    """The wire dtypes survive the tensor codec bit-exactly (satellite:
    compacted-output dtype/shape round-trip)."""
    import ml_dtypes

    from distributed_tf_serving_tpu import codec

    for dt in (ml_dtypes.bfloat16, np.float16):
        arr = np.random.RandomState(0).rand(7, 3).astype(np.float32).astype(dt)
        for use_content in (True, False):
            back = codec.to_ndarray(codec.from_ndarray(arr, use_tensor_content=use_content))
            assert back.dtype == arr.dtype and back.shape == arr.shape
            np.testing.assert_array_equal(
                back.view(np.uint16), arr.view(np.uint16)
            )


def test_executor_compacts_outputs(servable):
    """ShardedExecutor mirrors the batcher's on-device downcast; the
    batcher completer widens back to f32 with <=1e-2 parity."""
    from distributed_tf_serving_tpu.parallel import ShardedExecutor, make_mesh

    mesh = make_mesh(1)
    # build_stack wires ONE cfg.output_wire_dtype into both: the executor
    # downcasts on-device, the batcher completer widens back.
    ex = ShardedExecutor(mesh, output_wire_dtype="bfloat16")
    batcher = DynamicBatcher(
        buckets=(32,), max_wait_us=0, run_fn=ex, output_wire_dtype="bfloat16"
    ).start()
    try:
        arrays = make_arrays(32, seed=11)
        got = batcher.submit(servable, arrays).result(timeout=60)["prediction_node"]
        assert got.dtype == np.float32
        np.testing.assert_allclose(got, golden(servable, arrays), rtol=1e-2)
    finally:
        batcher.stop()
