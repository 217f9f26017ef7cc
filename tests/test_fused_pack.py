"""Native fused batch assembly (hostops.cc pack_batch_u24_bf16): the final
padded [u24 ids | bf16 wts] word buffer must be BIT-identical to the
generic path's pad -> fold -> pack_host_combined pipeline for every input
mix (wide int64/f32, compact int32/bf16, coalesced mixtures, padding), and
the serving path must produce identical scores with the fused path on or
off."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import ml_dtypes

from distributed_tf_serving_tpu import native
from distributed_tf_serving_tpu.client import compact_payload
from distributed_tf_serving_tpu.models import (
    ModelConfig,
    Servable,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.ops.transfer import pack_host_combined
from distributed_tf_serving_tpu.serving import DynamicBatcher

F = 8
VOCAB = 1 << 10  # power of two (the common config); non-pow2 covered below
CFG = ModelConfig(
    num_fields=F, vocab_size=VOCAB, embed_dim=4, mlp_dims=(16,),
    num_cross_layers=1, compute_dtype="bfloat16",
)
SPEC = {"feat_ids": "u24", "feat_wts": "bf16"}

pytestmark = pytest.mark.skipif(
    not native.ensure(), reason="native hostops unavailable"
)


def _wide(n, seed):
    rng = np.random.RandomState(seed)
    return {
        "feat_ids": rng.randint(0, 1 << 40, size=(n, F)).astype(np.int64),
        "feat_wts": rng.rand(n, F).astype(np.float32),
    }


def _reference_buffer(parts, bucket, vocab):
    """The generic pipeline, spelled out: fold every part to int32, pad
    into the bucket, spec-pack, concatenate."""
    ids = np.zeros((bucket, F), np.int32)
    wts = np.zeros((bucket, F), np.float32)
    off = 0
    for p in parts:
        n = p["feat_ids"].shape[0]
        ids[off:off + n] = native.fold_ids(
            p["feat_ids"].astype(np.int64), vocab
        )
        w = p["feat_wts"]
        wts[off:off + n] = (
            w.astype(np.float32) if w.dtype == ml_dtypes.bfloat16 else w
        )
        off += n
    return pack_host_combined({"feat_ids": ids, "feat_wts": wts}, SPEC)


@pytest.mark.parametrize("vocab", [VOCAB, 1009])
def test_buffer_bit_identical(vocab):
    parts = [_wide(5, 1), _wide(3, 2)]
    bucket = 16
    got = native.pack_batch_u24_bf16(
        [p["feat_ids"] for p in parts], [p["feat_wts"] for p in parts],
        F, bucket, vocab,
    )
    want = _reference_buffer(parts, bucket, vocab)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("sizes,bucket", [
    ((1,), 4), ((1, 2, 3), 8), ((3, 0, 7, 1), 16), ((5, 6), 11), ((2, 9, 1, 1), 13),
    ((7, 7, 7, 7, 3), 32), ((31,), 32), ((1, 1, 1, 1, 1, 1, 1), 7),
])
def test_buffer_bit_identical_at_rows_off_the_planes(sizes, bucket):
    """Requests that start at rows no multiple of 4 (or of 2), a bucket
    that does not fill its last plane, an empty request: a word of the
    buffer holds rows of up to four requests, and is still the generic
    path's."""
    parts = [_wide(n, 10 + i) for i, n in enumerate(sizes)]
    parts[-1] = compact_payload(parts[-1], VOCAB) if sizes[-1] else parts[-1]
    got = native.pack_batch_u24_bf16(
        [p["feat_ids"] for p in parts], [p["feat_wts"] for p in parts],
        F, bucket, VOCAB,
    )
    want = _reference_buffer(parts, bucket, VOCAB)
    assert got.dtype == want.dtype == np.uint32
    np.testing.assert_array_equal(got, want)


def test_buffer_bit_identical_compact_and_mixed():
    wide = _wide(4, 3)
    compact = compact_payload(_wide(6, 4), VOCAB)
    assert compact["feat_ids"].dtype == np.int32
    assert compact["feat_wts"].dtype == ml_dtypes.bfloat16
    for parts in ([compact], [wide, compact], [compact, wide]):
        bucket = 16
        got = native.pack_batch_u24_bf16(
            [p["feat_ids"] for p in parts], [p["feat_wts"] for p in parts],
            F, bucket, VOCAB,
        )
        want = _reference_buffer(parts, bucket, VOCAB)
        np.testing.assert_array_equal(got, want)


def _make_servable():
    model = build_model("dcn_v2", CFG)
    return Servable(
        name="DCN", version=1, model=model,
        params=jax.jit(model.init)(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(F),
    )


def _serve_scores(monkeypatch, fused: bool, payloads):
    if not fused:
        monkeypatch.setattr(native, "available", lambda: False)
    sv = _make_servable()
    batcher = DynamicBatcher(buckets=(16, 32), max_wait_us=0).start()
    try:
        outs = [
            batcher.submit(sv, p).result(timeout=60)["prediction_node"]
            for p in payloads
        ]
        return np.concatenate(outs), batcher.stats.fused_batches
    finally:
        batcher.stop()


def test_serving_scores_identical_fused_vs_generic(monkeypatch):
    payloads = [_wide(5, 7), compact_payload(_wide(9, 8), VOCAB), _wide(16, 9)]
    fused_scores, fused_count = _serve_scores(monkeypatch, True, payloads)
    assert fused_count == len(payloads)  # every batch took the native path
    generic_scores, generic_count = _serve_scores(monkeypatch, False, payloads)
    assert generic_count == 0
    # Same bytes -> same executable -> identical scores, not just close.
    np.testing.assert_array_equal(fused_scores, generic_scores)


def test_fused_path_content_cache_hits():
    sv = _make_servable()
    batcher = DynamicBatcher(buckets=(16,), max_wait_us=0).start()
    try:
        p = _wide(10, 11)
        a = batcher.submit(sv, p).result(timeout=60)["prediction_node"]
        h0 = batcher.input_cache.hits
        b = batcher.submit(sv, p).result(timeout=60)["prediction_node"]
        assert batcher.input_cache.hits == h0 + 1  # one group lookup hit
        np.testing.assert_array_equal(a, b)
        assert batcher.stats.fused_batches == 2
    finally:
        batcher.stop()


def test_generic_path_survives_non_fusable_group():
    """A servable outside the fused layout (f32 compute: no bf16 spec) must
    silently take the generic path with correct results."""
    cfg = ModelConfig(
        num_fields=F, vocab_size=VOCAB, embed_dim=4, mlp_dims=(16,),
        num_cross_layers=1, compute_dtype="float32",
    )
    model = build_model("dcn_v2", cfg)
    sv = Servable(
        name="D32", version=1, model=model,
        params=jax.jit(model.init)(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(F),
    )
    batcher = DynamicBatcher(buckets=(16,), max_wait_us=0).start()
    try:
        p = _wide(6, 12)
        got = batcher.submit(sv, p).result(timeout=60)["prediction_node"]
        assert batcher.stats.fused_batches == 0
        ref = {
            "feat_ids": native.fold_ids(p["feat_ids"], VOCAB),
            "feat_wts": p["feat_wts"],
        }
        want = np.asarray(model.apply(sv.params, ref)["prediction_node"])
        np.testing.assert_allclose(got, want, rtol=1e-5)
    finally:
        batcher.stop()
