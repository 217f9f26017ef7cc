"""Model zoo tests: shapes, determinism, jit-compatibility, numerics.

Covers the model-runtime half of SURVEY.md §7 step 2: every family in the
zoo serves the reference contract (feat_ids/feat_wts [n,43] ->
prediction_node [n] in [0,1]) and is jittable with static shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import ModelConfig, build_model, model_kinds

CFG = ModelConfig(
    num_fields=43,
    vocab_size=997,  # prime, exercises modulo folding
    embed_dim=8,
    mlp_dims=(32, 16),
    bottom_mlp_dims=(16, 8),
    num_cross_layers=2,
    compute_dtype="float32",
)


def make_batch(n=12, num_fields=43, seed=0):
    rng = np.random.RandomState(seed)
    # ids stay below 2^31: jax runs with x64 disabled, and the serving layer
    # pre-folds 64-bit wire ids into the vocab in host numpy (see
    # serving/batcher.py) before they ever reach a model.
    return {
        "feat_ids": jnp.asarray(rng.randint(0, 1 << 30, size=(n, num_fields)), jnp.int32),
        "feat_wts": jnp.asarray(rng.rand(n, num_fields), jnp.float32),
    }


def test_all_families_registered():
    assert set(model_kinds()) >= {"dcn", "dcn_v2", "wide_deep", "deepfm", "two_tower", "dlrm"}


@pytest.mark.parametrize("kind", ["dcn", "dcn_v2", "wide_deep", "deepfm", "two_tower", "dlrm"])
def test_forward_contract(kind):
    model = build_model(kind, CFG)
    params = model.init(jax.random.PRNGKey(0))
    out = model.apply(params, make_batch())
    pred = np.asarray(out["prediction_node"])
    assert pred.shape == (12,)
    assert pred.dtype == np.float32
    assert np.all((pred >= 0) & (pred <= 1))
    assert np.all(np.isfinite(pred))


@pytest.mark.parametrize("kind", ["dcn", "dcn_v2", "wide_deep", "deepfm", "two_tower", "dlrm"])
def test_jit_matches_eager(kind):
    model = build_model(kind, CFG)
    params = model.init(jax.random.PRNGKey(1))
    batch = make_batch(8)
    eager = model.apply(params, batch)["prediction_node"]
    jitted = jax.jit(model.apply)(params, batch)["prediction_node"]
    np.testing.assert_allclose(np.asarray(eager), np.asarray(jitted), rtol=1e-6)


def test_deterministic_across_calls():
    model = build_model("dcn_v2", CFG)
    params = model.init(jax.random.PRNGKey(2))
    batch = make_batch(5)
    a = np.asarray(model.apply(params, batch)["prediction_node"])
    b = np.asarray(model.apply(params, batch)["prediction_node"])
    np.testing.assert_array_equal(a, b)


def test_rowwise_independence():
    """Scoring candidates together or separately must agree — the invariant
    candidate sharding relies on (concat-of-shards == full batch,
    DCNClient.java:161-164 merge semantics)."""
    model = build_model("dcn_v2", CFG)
    params = model.init(jax.random.PRNGKey(3))
    batch = make_batch(10)
    full = np.asarray(model.apply(params, batch)["prediction_node"])
    lo = np.asarray(
        model.apply(params, {k: v[:5] for k, v in batch.items()})["prediction_node"]
    )
    hi = np.asarray(
        model.apply(params, {k: v[5:] for k, v in batch.items()})["prediction_node"]
    )
    np.testing.assert_allclose(full, np.concatenate([lo, hi]), rtol=1e-5, atol=1e-7)


def test_bf16_close_to_f32():
    import dataclasses

    cfg32 = CFG
    cfg16 = dataclasses.replace(CFG, compute_dtype="bfloat16")
    m32, m16 = build_model("dcn_v2", cfg32), build_model("dcn_v2", cfg16)
    params = m32.init(jax.random.PRNGKey(4))  # same f32 params for both
    batch = make_batch(16)
    p32 = np.asarray(m32.apply(params, batch)["prediction_node"])
    p16 = np.asarray(m16.apply(params, batch)["prediction_node"])
    assert np.max(np.abs(p32 - p16)) < 0.05  # bf16 mantissa ~ 8 bits


def test_dlrm_dense_features_optional():
    model = build_model("dlrm", CFG)
    params = model.init(jax.random.PRNGKey(5))
    batch = make_batch(8)
    out1 = model.apply(params, batch)["prediction_node"]
    # Random dense features (constant inputs can land in an all-dead ReLU
    # region on toy widths; random rows make that vanishingly unlikely).
    batch["dense_features"] = jax.random.normal(
        jax.random.PRNGKey(9), (8, CFG.num_dense_features), jnp.float32
    )
    out2 = model.apply(params, batch)["prediction_node"]
    assert out1.shape == out2.shape == (8,)
    assert not np.allclose(np.asarray(out1), np.asarray(out2))  # dense must matter


def test_two_tower_user_fields_shared():
    """Same user fields + same item fields => same score regardless of row."""
    model = build_model("two_tower", CFG)
    params = model.init(jax.random.PRNGKey(6))
    batch = make_batch(3)
    # Make row 2 a copy of row 0.
    ids = np.asarray(batch["feat_ids"]).copy()
    wts = np.asarray(batch["feat_wts"]).copy()
    ids[2], wts[2] = ids[0], wts[0]
    out = np.asarray(
        model.apply(params, {"feat_ids": jnp.asarray(ids), "feat_wts": jnp.asarray(wts)})[
            "prediction_node"
        ]
    )
    assert out[0] == pytest.approx(out[2], rel=1e-6)


# ---------------------------------------------------- lane-packed tables

# (vocab, dim, pack factor the serving shape must have): every width that
# divides a lane row packs; 128 is a lane row already; 24 does not divide
# 128 and 1001 rows are not a multiple of 8, so both stay logical.
PACK_CASES = [
    (1024, 8, 16), (1024, 16, 8), (1024, 32, 4), (1024, 64, 2), (1024, 128, 1),
    (1024, 24, 1), (1001, 16, 1),
]


@pytest.mark.parametrize("table_dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("vocab,dim,pack", PACK_CASES)
def test_packed_lookup_is_bit_identical(vocab, dim, pack, table_dtype):
    from distributed_tf_serving_tpu.models.embeddings import (
        field_embed, pack_factor, pack_table, unpack_table,
    )
    from distributed_tf_serving_tpu.serving.batcher import fold_ids_host

    table = jax.random.normal(jax.random.PRNGKey(5), (vocab, dim), table_dtype)
    packed = pack_table(table, dim)
    assert pack_factor(vocab, dim) == pack
    assert packed.shape == (vocab // pack, dim * pack)
    assert pack_table(packed, dim) is packed  # packing twice changes nothing
    np.testing.assert_array_equal(np.asarray(unpack_table(packed, dim)), np.asarray(table))
    # A host array packs by reshape, no copy.
    host = np.asarray(table)
    assert pack == 1 or np.shares_memory(pack_table(host, dim), host)

    rng = np.random.RandomState(6)
    wire = rng.randint(0, 1 << 40, size=(9, 5)).astype(np.int64)  # beyond V: the host fold
    wire[0, :3] = [0, vocab - 1, vocab + 3]
    ids = np.concatenate([
        fold_ids_host(wire, vocab),
        np.asarray([[0, vocab - 1, vocab, vocab + 3, (1 << 31) - 1]], np.int32),  # the device fold
    ])
    wts = jnp.asarray(rng.rand(*ids.shape), jnp.float32)
    want = np.asarray(jnp.take(table, jnp.asarray(ids % vocab), axis=0))
    for cd in (jnp.float32, jnp.bfloat16):
        look = jax.jit(lambda t, cd=cd: field_embed(t, jnp.asarray(ids), wts, cd, dim))
        logical, served = look(table), look(packed)
        assert served.dtype == cd and served.shape == (*ids.shape, dim)
        np.testing.assert_array_equal(np.asarray(served), np.asarray(logical))
        np.testing.assert_array_equal(
            np.asarray(logical),
            np.asarray(jnp.asarray(want).astype(cd) * wts[..., None].astype(cd)),
        )


@pytest.mark.parametrize("kind", model_kinds())
def test_apply_on_packed_tree_equals_logical(kind):
    """Every registered family serves both trees, bit for bit: init drawn in
    the serving shape holds the values of the logical init, and pack_params
    of the logical tree is the same tree."""
    from distributed_tf_serving_tpu.models.embeddings import pack_params, unpack_params

    cfg = ModelConfig(
        num_fields=43, vocab_size=1024, embed_dim=8, mlp_dims=(32, 16),
        bottom_mlp_dims=(16, 8), num_cross_layers=2,
    )
    model = build_model(kind, cfg)
    logical = jax.jit(model.init)(jax.random.PRNGKey(7))
    drawn = jax.jit(lambda k: model.init(k, packed=True))(jax.random.PRNGKey(7))
    packed = pack_params(logical, cfg.embed_dim)
    assert logical["embedding"].shape == (1024, 8)
    assert packed["embedding"].shape == drawn["embedding"].shape == (64, 128)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)), packed, drawn
    )
    np.testing.assert_array_equal(
        np.asarray(unpack_params(packed, cfg.embed_dim)["embedding"]),
        np.asarray(logical["embedding"]),
    )
    batch = make_batch(16)
    want = jax.jit(model.apply)(logical, batch)
    got = jax.jit(model.apply)(packed, batch)
    assert set(got) == set(want)
    for key in want:
        np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want[key]))
