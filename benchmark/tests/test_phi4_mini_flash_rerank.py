"""The phi4_mini_flash_rerank configuration's own files: the reference against
the program's family at tiny widths, the file's published numbers against its
served TOML, and `cost.py`'s counts."""

import json
import os

import numpy as np
import pytest

from benchmark import peaks
from benchmark.common import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark", "configs", "phi4_mini_flash_rerank")
with open(os.path.join(HERE, "config.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["toml"]["model"]
COST = load_module(os.path.join(HERE, "cost.py"), "cost_phi4")
# The catalog row's `config` (model-configs guide, architectures.jsonl), with
# the depth as this configuration cuts it.
CATALOG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
    "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
    "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064,
}


def test_the_file_holds_the_catalog_rows_numbers_and_serves_them():
    differs = {k for k, v in CATALOG.items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"]) == {"num_hidden_layers"}
    served = {
        "hidden_size": MODEL["embed_dim"], "intermediate_size": MODEL["mlp_dims"][0],
        **{k: MODEL[k] for k in ("layer_norm_eps", "num_attention_heads", "num_hidden_layers",
                                 "num_key_value_heads", "sliding_window", "vocab_size")},
    }
    assert served == {k: CONFIG[k] for k in served}
    assert MODEL["num_fields"] == CONFIG["toml"]["server"]["num_fields"] == 1024
    assert MODEL["ssm_expand"] * MODEL["embed_dim"] == 5120 and MODEL["ssm_state"] == 16
    assert {"d_state", "d_conv", "expand", "dt_rank", "differential_attention", "layer_plan"} <= set(
        CONFIG["assumed"])


def test_reference_matches_the_programs_family_at_tiny_widths():
    import jax

    from distributed_tf_serving_tpu.models import ModelConfig, build_model

    reference = load_module(os.path.join(HERE, "reference.py"), "ref_phi4")
    config = ModelConfig(
        num_fields=40, vocab_size=500, embed_dim=64, mlp_dims=(128,), num_hidden_layers=8,
        num_attention_heads=4, num_key_value_heads=2, sliding_window=16, compute_dtype="float32")
    model = build_model("phi4flash", config)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"feat_ids": rng.integers(0, 500, size=(3, 40)).astype(np.int32),
             "feat_wts": rng.random((3, 40), dtype=np.float32)}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.forward(p, b, 16))(params, batch))
        got = np.asarray(jax.jit(model.apply)(params, batch)["prediction_node"])
    assert np.max(np.abs(want - got)) < 1e-6


def test_step_cost_counts_the_served_step():
    flops, moved = COST.step_cost(MODEL, 8, 1)
    # 9 layers at all 8,192 positions at about 0.2 GFLOP a position and
    # layer, the tail at 8 positions: 16.6 TFLOP; 1.68 G weights at 2 bytes.
    assert flops == pytest.approx(16.6e12, rel=0.01) and moved == pytest.approx(3.40e9, rel=0.01)
    assert peaks.least_seconds(flops, moved, "TPU v5 lite")[1] == "compute"
    more_rows, _ = COST.step_cost(MODEL, 16, 2)
    assert more_rows == 2 * flops
    whole, _ = COST.step_cost({**MODEL, "num_hidden_layers": 32}, 8, 1)
    assert 1.8 < whole / flops < 2.0  # the skip saves more of a deeper stack
    scan_flops, scan_bytes = COST.scan_cost(MODEL, 8)
    assert COST.scan_layers(MODEL) == 5 and scan_flops == 5 * 8 * 1024 * 7 * 5120 * 16
    assert scan_bytes == 5 * 8 * 1024 * 4 * (3 * 5120 + 32)
    assert peaks.least_seconds(scan_flops, scan_bytes, "TPU v5 lite")[1] == "memory"
