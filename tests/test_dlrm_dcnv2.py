"""The dlrm_dcnv2 family (MLPerf DLRM-DCNv2: embedding bags, a low-rank cross
network) at tiny sizes on the CPU: against the benchmark's plain reference,
the pooling's numerics, and one request down the served path."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import ModelConfig, build_model
from distributed_tf_serving_tpu.models.dcn import cross_apply
from distributed_tf_serving_tpu.models.embeddings import field_embed, pack_table

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BAGS = (3, 1, 2, 5)
PUBLISHED = (3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100, 27, 10, 3, 1, 1)


def tiny_config(bags=BAGS, **overrides) -> ModelConfig:
    return ModelConfig(**{
        "name": "M", "num_fields": sum(bags), "multi_hot_sizes": bags, "vocab_size": 4096,
        "embed_dim": 8, "bottom_mlp_dims": (16, 8), "mlp_dims": (32, 16),
        "num_cross_layers": 3, "cross_low_rank": 4, "compute_dtype": "float32", **overrides,
    })


def rows(n: int, config: ModelConfig, seed: int = 3, folded: bool = True) -> dict:
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 1 << 40, size=(n, config.num_fields), dtype=np.int64)
    return {
        "feat_ids": (ids % config.vocab_size).astype(np.int32) if folded else ids,
        "feat_wts": rng.random((n, config.num_fields), dtype=np.float32),
        "dense_features": rng.random((n, config.num_dense_features), dtype=np.float32),
    }


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "benchmark", "configs", "dlrm_dcnv2_mlperf", "reference.py")
    spec = importlib.util.spec_from_file_location("ref_dlrm_dcnv2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_float32_logits_match_the_plain_reference(reference):
    config = tiny_config()
    model = build_model("dlrm_dcnv2", config)
    params = model.init(jax.random.PRNGKey(7))
    batch = rows(37, config)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.logits(params, batch, BAGS))
        got = np.asarray(model.apply(params, batch)["logits"])
    assert want.shape == got.shape == (37,) and want.std() > 1e-2
    assert np.max(np.abs(want - got)) < 2e-6


def test_bfloat16_scores_within_the_benchmark_tolerance(reference):
    """bf16 matmul operands and activations, float32 accumulation: within the
    5e-3 the benchmark allows a served score (config.json `tolerance`)."""
    config = tiny_config(compute_dtype="bfloat16")
    model = build_model("dlrm_dcnv2", config)
    params = model.init(jax.random.PRNGKey(7))
    batch = rows(64, config)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.forward(params, batch, BAGS))
    got = np.asarray(model.apply(params, batch)["prediction_node"])
    assert np.max(np.abs(want - got)) < 5e-3


def test_pooling_accumulates_in_float32():
    """A bag's sum is rounded to bf16 ONCE. With a table and weights that bf16
    holds exactly every product is exact in float32, so the pooled value is
    the true sum rounded to nearest: relative error at most 2**-8. An
    accumulator kept in bf16 rounds after each of a bag's 100 rows and
    drifts several times further: it must fail the same limit."""
    bags, dim, n = (100, 1), 8, 16
    rng = np.random.default_rng(0)
    table = rng.random((4096, dim), dtype=np.float32).astype(ml_dtypes.bfloat16)
    ids = rng.integers(0, 4096, size=(n, sum(bags)), dtype=np.int32)
    wts = rng.random((n, sum(bags)), dtype=np.float32).astype(ml_dtypes.bfloat16)
    exact = table.astype(np.float64)[ids] * wts.astype(np.float64)[..., None]
    want = np.stack([exact[:, :100].sum(axis=1), exact[:, 100]], axis=1)
    got = np.asarray(
        field_embed(jnp.asarray(table), ids, jnp.asarray(wts), jnp.bfloat16, dim, bags)
    ).astype(np.float64)
    limit = 2.0**-8 * np.abs(want) + 1e-6
    assert got.shape == (n, 2, dim)
    assert np.all(np.abs(got - want) <= limit)
    drifting = np.zeros((n, dim), ml_dtypes.bfloat16)
    for f in range(100):
        drifting = drifting + (table[ids[:, f]] * wts[:, f, None])  # bf16 + bf16 -> bf16
    assert drifting.dtype == ml_dtypes.bfloat16
    assert np.any(np.abs(drifting.astype(np.float64) - want[:, 0]) > limit[:, 0])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bags_of_one_are_field_embed_as_it_was(dtype):
    """All-ones multi_hot_sizes take the one-id-a-field path: the same values
    bit for bit and the same lowered program, so no executable of the other
    families changes."""
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.standard_normal((512, 16), dtype=np.float32))
    ids = jnp.asarray(rng.integers(0, 512, size=(9, 6), dtype=np.int32))
    wts = jnp.asarray(rng.random((9, 6), dtype=np.float32))
    plain = lambda t, i, w: field_embed(t, i, w, jnp.dtype(dtype), 16)  # noqa: E731
    ones = lambda t, i, w: field_embed(t, i, w, jnp.dtype(dtype), 16, (1,) * 6)  # noqa: E731
    np.testing.assert_array_equal(np.asarray(plain(table, ids, wts)), np.asarray(ones(table, ids, wts)))
    text = [jax.jit(f).lower(table, ids, wts).as_text() for f in (plain, ones)]
    assert text[0].replace("jit__lambda_", "jit_f") == text[1].replace("jit__lambda_", "jit_f")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_packed_and_logical_tables_pool_alike(dtype):
    rng = np.random.default_rng(2)
    logical = rng.standard_normal((4096, 8), dtype=np.float32)
    batch = rows(13, tiny_config())
    got = [
        np.asarray(field_embed(
            jnp.asarray(t), batch["feat_ids"], batch["feat_wts"], jnp.dtype(dtype), 8, BAGS))
        for t in (logical, pack_table(logical, 8))
    ]
    assert pack_table(logical, 8).shape == (256, 128) and got[0].shape == (13, len(BAGS), 8)
    np.testing.assert_array_equal(got[0], got[1])


def test_low_rank_cross_layer_is_the_full_layer_of_the_product():
    rng = np.random.default_rng(3)
    d, r = 40, 4
    v = rng.standard_normal((d, r)).astype(np.float32) / d**0.5
    w = rng.standard_normal((r, d)).astype(np.float32) / r**0.5
    b = rng.standard_normal(d).astype(np.float32)
    x0 = jnp.asarray(rng.standard_normal((11, d)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        low = cross_apply([{"v": v, "w": w, "b": b}] * 2, x0, jnp.float32)
        full = cross_apply([{"w": v @ w, "b": b}] * 2, x0, jnp.float32)
    np.testing.assert_allclose(np.asarray(low), np.asarray(full), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bags,fields", [((3, 1, 2, 5), 12), ((3, 0, 8), 11)],
                         ids=["sum_is_not_num_fields", "an_empty_bag"])
def test_bags_that_do_not_tile_the_columns_are_refused_at_build(bags, fields):
    with pytest.raises(ValueError, match="multi_hot_sizes"):
        build_model("dlrm_dcnv2", tiny_config(bags, num_fields=fields))


def test_no_multi_hot_sizes_is_one_id_a_field():
    config = tiny_config((), num_fields=5)
    model = build_model("dlrm_dcnv2", config)
    params = model.init(jax.random.PRNGKey(0))
    assert params["cross"][0]["v"].shape == (6 * 8, 4)
    assert model.apply(params, rows(3, config))["prediction_node"].shape == (3,)


def test_toml_reads_the_two_model_keys(tmp_path):
    from distributed_tf_serving_tpu.utils.config import load_config

    (tmp_path / "s.toml").write_text(
        '[server]\nmodel_kind = "dlrm_dcnv2"\nnum_fields = 11\n'
        "[model]\nnum_fields = 11\nmulti_hot_sizes = [3, 1, 2, 5]\ncross_low_rank = 4\n"
        "embed_dim = 8\nbottom_mlp_dims = [16, 8]\n"
    )
    cfgs = load_config(str(tmp_path / "s.toml"))
    assert cfgs["model"].multi_hot_sizes == BAGS and cfgs["model"].cross_low_rank == 4
    model = build_model(cfgs["server"].model_kind, cfgs["model"])
    assert model.kind == "dlrm_dcnv2" and model.takes_dense


@pytest.fixture(scope="module")
def served():
    """The published 214 columns in 26 bags at tiny widths, behind the
    default batcher: bf16 compute, so ids and weights travel compressed."""
    from distributed_tf_serving_tpu.serving.server import build_stack
    from distributed_tf_serving_tpu.utils.config import ServerConfig

    config = tiny_config(PUBLISHED, compute_dtype="bfloat16")
    cfg = ServerConfig(
        model_kind="dlrm_dcnv2", model_name="M", num_fields=214, buckets=(16,), warmup=False
    )
    _registry, batcher, impl, servable, _mesh, _watcher = build_stack(cfg, model_config=config)
    yield batcher, impl, servable
    batcher.stop()


def test_a_padded_compressed_request_scores_like_model_apply(served):
    batcher, _impl, servable = served
    config = servable.model.config
    assert batcher.compress_transfer and servable.signature("").input_specs["dense_features"]
    arrays = rows(11, config, folded=False)  # 11 rows pad to the bucket of 16
    got = batcher.submit(servable, arrays).result(timeout=120)["prediction_node"]
    logical = jax.jit(servable.model.init)(jax.random.PRNGKey(0))
    batch = dict(arrays, feat_ids=(arrays["feat_ids"] % config.vocab_size).astype(np.int32))
    want = jax.jit(servable.model.apply)(logical, batch)["prediction_node"]
    assert got.shape == (11,)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-6, atol=1e-6)


def test_predict_answers_214_columns(served):
    from distributed_tf_serving_tpu import codec
    from distributed_tf_serving_tpu.client import build_predict_request

    batcher, impl, servable = served
    arrays = rows(5, servable.model.config, seed=9, folded=False)
    response = impl.predict(build_predict_request(arrays, "M"))
    scores = codec.to_ndarray(response.outputs["prediction_node"])
    direct = batcher.submit(servable, arrays).result(timeout=120)["prediction_node"]
    assert scores.shape == (5,)
    np.testing.assert_array_equal(scores, direct)


def test_runtime_block_reports_lookups_and_bags(served):
    _batcher, impl, servable = served
    startup = impl.runtime_stats()["startup"]
    assert startup["lookups_per_row"] == {"M:1": 214} and startup["bags"] == {"M:1": 26}
    assert servable.embedding_pack == 16  # embed_dim 8: sixteen logical rows a lane row
