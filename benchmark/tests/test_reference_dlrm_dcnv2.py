"""dlrm_dcnv2_mlperf: its plain reference against the program's model at the
configuration's widths (a 4096-row table), in float32 on the CPU, and its
step cost from the shapes."""

import json
import os

import numpy as np
import pytest

from benchmark.common import load_module, toml_text

HERE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "configs", "dlrm_dcnv2_mlperf")


@pytest.fixture()
def config():
    with open(os.path.join(HERE, "config.json")) as f:
        return json.load(f)


def test_reference_matches_the_program_in_float32(config, tmp_path):
    import jax
    import jax.numpy as jnp

    from distributed_tf_serving_tpu.models import build_model
    from distributed_tf_serving_tpu.utils.config import load_config

    from benchmark import traffic

    config["toml"]["model"].update(vocab_size=4096, compute_dtype="float32")
    (tmp_path / "server.toml").write_text(toml_text(config))
    cfgs = load_config(str(tmp_path / "server.toml"))
    model = build_model(cfgs["server"].model_kind, cfgs["model"])
    params = model.init(jax.random.PRNGKey(0))
    arrays = traffic.fresh_rows(np.random.default_rng(5), 37, config["toml"]["model"])
    batch = dict(arrays, feat_ids=(arrays["feat_ids"] % 4096).astype(np.int32))
    reference = load_module(os.path.join(HERE, "reference.py"), "ref_dlrm_dcnv2_mlperf")
    assert list(reference.MULTI_HOT_SIZES) == config["toml"]["model"]["multi_hot_sizes"]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.forward(params, batch))
        got = np.asarray(model.apply(params, {k: jnp.asarray(v) for k, v in batch.items()})["prediction_node"])
    assert want.shape == got.shape == (37,)
    assert np.max(np.abs(want - got)) < 2e-6
    assert 0.0 < want.min() and want.max() < 1.0 and want.std() > 1e-3


def test_step_cost_from_the_shapes(config):
    shape = config["toml"]["model"]
    cost = load_module(os.path.join(HERE, "cost.py"), "cost_dlrm_dcnv2_mlperf")
    flops, moved = cost.step_cost(shape, 1000, 2)
    assert flops / 1000 == pytest.approx(32.1e6, rel=0.01)
    assert flops / 1000 == 2 * (170_496 + 3 * 2 * 3456 * 512 + 5_243_136) + 2 * 214 * 128
    one, one_moved = cost.step_cost(shape, 1, 1)
    assert flops == 1000 * one
    # 214 rows of 512 bytes, 3 + 2 bytes an id and its weight, 13 dense, a score.
    assert one_moved - 4 * (one - 2 * 214 * 128) // 2 == 214 * (512 + 5) + 13 * 4 + 4
    assert moved > 1000 * 214 * 128 * 4


def test_published_shape(config):
    shape = config["toml"]["model"]
    assert sum(shape["multi_hot_sizes"]) == shape["num_fields"] == config["toml"]["server"]["num_fields"] == 214
    assert len(shape["multi_hot_sizes"]) == 26 and config["reduced"] == ["vocab_size"]
    assert shape["vocab_size"] * shape["embed_dim"] * 4 >= 8 << 30
