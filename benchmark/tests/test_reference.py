"""The two plain references against the program's models at a tiny size, in
float32 on the CPU: the same parameters, the same inputs, the same scores."""

import importlib.util
import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("name", ["dcn_v2_ref43", "dlrm_mlperf"])
def test_reference_matches_the_program_in_float32(name, tmp_path):
    import jax
    import jax.numpy as jnp

    from distributed_tf_serving_tpu.models import build_model
    from distributed_tf_serving_tpu.utils.config import load_config

    from benchmark import traffic
    from benchmark.common import toml_text

    with open(os.path.join(HERE, "configs", name, "config.json")) as f:
        config = json.load(f)
    config["toml"]["model"].update(vocab_size=4096, compute_dtype="float32")
    (tmp_path / "server.toml").write_text(toml_text(config))
    cfgs = load_config(str(tmp_path / "server.toml"))
    model = build_model(cfgs["server"].model_kind, cfgs["model"])
    params = model.init(jax.random.PRNGKey(0))
    arrays = traffic.fresh_rows(np.random.default_rng(5), 37, config["toml"]["model"])
    batch = dict(arrays, feat_ids=(arrays["feat_ids"] % 4096).astype(np.int32))
    reference = load(os.path.join(HERE, "configs", name, "reference.py"), "ref_" + name)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.forward(params, batch))
        got = np.asarray(model.apply(params, {k: jnp.asarray(v) for k, v in batch.items()})["prediction_node"])
    assert want.shape == got.shape == (37,)
    assert np.max(np.abs(want - got)) < 2e-6
    assert 0.0 < want.min() and want.max() < 1.0 and want.std() > 1e-3


@pytest.mark.parametrize("name,flops_row", [("dcn_v2_ref43", 3.28e6), ("dlrm_mlperf", 4.82e6)])
def test_step_cost_from_the_shapes(name, flops_row):
    with open(os.path.join(HERE, "configs", name, "config.json")) as f:
        config = json.load(f)["toml"]["model"]
    cost = load(os.path.join(HERE, "configs", name, "cost.py"), "cost_" + name)
    flops, moved = cost.step_cost(config, 1000, 2)
    assert flops / 1000 == pytest.approx(flops_row, rel=0.01)
    one, _ = cost.step_cost(config, 1, 1)
    assert flops == 1000 * one
    assert moved > 1000 * config["num_fields"] * config["embed_dim"] * 4
