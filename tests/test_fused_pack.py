"""Native batch assembly (hostops.cc assemble_batch): the final padded word
buffer must be BIT-identical to the generic path's pad -> fold ->
pack_host_combined pipeline for every combined layout and input mix (wide
int64/f32, compact int32/bf16, coalesced mixtures, a third raw input,
padding), and the serving path must produce identical scores with the native
path on or off."""

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
from distributed_tf_serving_tpu.ops.transfer import combined_layout, pack_host_combined
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


def _wide(n, seed, fields=F, dense=False):
    rng = np.random.RandomState(seed)
    out = {
        "feat_ids": rng.randint(0, 1 << 40, size=(n, fields)).astype(np.int64),
        "feat_wts": rng.rand(n, fields).astype(np.float32),
    }
    if dense:
        out["dense_features"] = rng.randn(n, 13).astype(np.float32)
    return out


def _reference(parts, bucket, vocab, spec):
    """The generic pipeline, spelled out: fold every part's ids to int32,
    pad every input into the bucket, spec-pack into the one buffer. Returns
    the buffer and the padded batch's layout."""
    padded = {}
    for key in parts[0]:
        cols = [p[key] for p in parts]
        if key == "feat_ids":
            cols = [native.fold_ids(c.astype(np.int64), vocab) for c in cols]
        elif key == "feat_wts":
            cols = [c.astype(np.float32) for c in cols]  # bf16 -> f32: exact
        out = np.zeros((bucket,) + cols[0].shape[1:], cols[0].dtype)
        off = 0
        for c in cols:
            out[off:off + c.shape[0]] = c
            off += c.shape[0]
        padded[key] = out
    return pack_host_combined(padded, spec), combined_layout(padded, spec)


def _assemble(parts, bucket, vocab, spec):
    want, layout = _reference(parts, bucket, vocab, spec)
    got, _ns = native.assemble_batch(
        layout, {k: [p[k] for p in parts] for k in parts[0]},
        {"feat_ids": vocab},
    )
    assert got.dtype == want.dtype == np.uint32
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("vocab", [VOCAB, 1009])
def test_buffer_bit_identical(vocab):
    _assemble([_wide(5, 1), _wide(3, 2)], 16, vocab, SPEC)


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
    _assemble(parts, bucket, VOCAB, SPEC)


def test_buffer_bit_identical_compact_and_mixed():
    wide = _wide(4, 3)
    compact = compact_payload(_wide(6, 4), VOCAB)
    assert compact["feat_ids"].dtype == np.int32
    assert compact["feat_wts"].dtype == ml_dtypes.bfloat16
    for parts in ([compact], [wide, compact], [compact, wide]):
        _assemble(parts, 16, VOCAB, SPEC)


# The benchmark's three layouts: DCN-v2 (a table past 2**24 rows: ids ride as
# int32/32b) and the two DLRM families (a third, raw float32 input).
LAYOUTS = {
    "dcn_v2_ref43": (43, False, {"feat_wts": "bf16"}),
    "dlrm_mlperf": (26, True, SPEC),
    "dlrm_dcnv2_mlperf": (214, True, SPEC),
}
# parts x fills: one part, three, seven with an empty one; a full bucket, rows
# no multiple of 4, one row.
GROUPS = {
    "1-full": ((64,), 64), "1-odd": ((37,), 64), "1-one": ((1,), 16),
    "3-full": ((20, 33, 11), 64), "3-odd": ((5, 21, 9), 64),
    "7-full": ((9, 0, 17, 8, 12, 7, 11), 64), "7-odd": ((3, 0, 1, 6, 2, 5, 4), 32),
}


@pytest.mark.parametrize("vocab", [1 << 20, 1000003])
@pytest.mark.parametrize("group", sorted(GROUPS))
@pytest.mark.parametrize("config", sorted(LAYOUTS))
def test_buffer_bit_identical_for_the_cells_layouts(config, group, vocab):
    """Every other part is a compact-wire one (int32 ids, bf16 weights), so
    a group of more than one part mixes the part dtypes; the dense input is
    float32 as the wire decodes it."""
    fields, dense, spec = LAYOUTS[config]
    sizes, bucket = GROUPS[group]
    parts = []
    for i, n in enumerate(sizes):
        part = _wide(n, 100 + i, fields, dense)
        if i % 2:
            part.update(compact_payload(
                {k: part[k] for k in ("feat_ids", "feat_wts")}, vocab
            ))
        parts.append(part)
    _assemble(parts, bucket, vocab, spec)


@pytest.mark.parametrize("dtype", [np.uint8, np.int8, np.float16, np.uint16, np.uint32])
def test_buffer_bit_identical_raw_widths(dtype):
    """Raw inputs of 1, 2 and 4 bytes with a trailing shape of their own
    ride as they are, beside folded ids that are not u24-packed."""
    rng = np.random.RandomState(5)
    parts = [
        {
            "feat_ids": rng.randint(-(1 << 40), 1 << 40, size=(n, 3)).astype(np.int64),
            "side": rng.randint(0, 200, size=(n, 2, 5)).astype(dtype),
        }
        for n in (6, 1, 10)
    ]
    _assemble(parts, 19, 1 << 26, {})


def test_assembler_refuses_what_it_cannot_read():
    parts = {"feat_ids": [np.zeros((3, 4), np.int64)]}
    layout = (4, (("feat_ids", 32, (4,), "int32"),))
    with pytest.raises(ValueError, match="cannot travel"):
        native.assemble_batch(layout, parts)  # int64, and no fold named
    with pytest.raises(ValueError, match="exceed bucket"):
        native.assemble_batch((2, layout[1]), parts, {"feat_ids": 7})
    with pytest.raises(ValueError, match="shape"):
        native.assemble_batch(
            (4, (("feat_ids", 32, (5,), "int32"),)), parts, {"feat_ids": 7}
        )
    got, _ns = native.assemble_batch(layout, parts, {"feat_ids": 7})
    np.testing.assert_array_equal(got, np.zeros(16, np.uint32))


def _make_servable(kind="dcn_v2", cfg=CFG, name="DCN"):
    model = build_model(kind, cfg)
    return Servable(
        name=name, version=1, model=model,
        params=jax.jit(model.init)(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(cfg.num_fields),
    )


def _serve_scores(monkeypatch, fused: bool, payloads, sv=None):
    if not fused:
        monkeypatch.setattr(native, "available", lambda: False)
    sv = sv or _make_servable()
    batcher = DynamicBatcher(buckets=(16, 32), max_wait_us=0).start()
    try:
        outs = [
            batcher.submit(sv, p).result(timeout=60)["prediction_node"]
            for p in payloads
        ]
        assert batcher.stats.batches == len(payloads)
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


def test_float32_weights_ride_the_native_path_as_raw_words(monkeypatch):
    """A servable whose weights are not bf16-packed (f32 compute) was outside
    the one layout the assembler knew; its layout (u24 ids, float32/32b
    weights) is one more the general assembler takes, with the generic
    path's scores."""
    cfg = ModelConfig(
        num_fields=F, vocab_size=VOCAB, embed_dim=4, mlp_dims=(16,),
        num_cross_layers=1, compute_dtype="float32",
    )
    sv = _make_servable(cfg=cfg, name="D32")
    payloads = [_wide(6, 12)]
    got, fused_count = _serve_scores(monkeypatch, True, payloads, sv)
    assert fused_count == 1
    generic, _ = _serve_scores(monkeypatch, False, payloads, sv)
    np.testing.assert_array_equal(got, generic)
    p = payloads[0]
    ref = {
        "feat_ids": native.fold_ids(p["feat_ids"], VOCAB),
        "feat_wts": p["feat_wts"],
    }
    want = np.asarray(sv.model.apply(sv.params, ref)["prediction_node"])
    np.testing.assert_allclose(got, want, rtol=1e-5)
