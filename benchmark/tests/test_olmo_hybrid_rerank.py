"""The olmo_hybrid_rerank configuration's own files: the reference against the
program's family at tiny widths, the file's numbers against the catalog row
and its served TOML, `cost.py`'s counts against a hand count, and the new
reader on a made-up window."""

import json
import os

import numpy as np
import pytest

from benchmark import peaks
from benchmark.common import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark", "configs", "olmo_hybrid_rerank")
with open(os.path.join(HERE, "config.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["toml"]["model"]
COST = load_module(os.path.join(HERE, "cost.py"), "cost_olmo")
L, F = "linear_attention", "full_attention"
# The catalog row's `config` (model-configs guide, architectures.jsonl).
CATALOG = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
    "num_hidden_layers": 32, "num_attention_heads": 30, "num_key_value_heads": 30, "hidden_act": "silu",
    "max_position_embeddings": 65536, "attention_bias": False, "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "layer_types": [L, L, L, F] * 8, "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
}
REDUCED = {"num_hidden_layers"}


def test_the_file_holds_the_catalog_rows_numbers_and_serves_them():
    differs = {k for k, v in CATALOG.items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"]) == REDUCED
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == CONFIG["name"])
    assert set(entry["reduced"]) == REDUCED and len(entry["source"]) <= 200
    assert CONFIG["published"]["num_hidden_layers"] == CATALOG["num_hidden_layers"]
    served = {
        "hidden_size": MODEL["embed_dim"], "rms_norm_eps": MODEL["layer_norm_eps"],
        **{k: MODEL[k] for k in (
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "intermediate_size", "vocab_size",
            "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "linear_allow_neg_eigval")},
    }
    assert served == {k: CONFIG[k] for k in served}
    # every width, head count and the vocabulary as published; only the depth is cut, to two whole periods
    assert {k: served[k] for k in served if k != "num_hidden_layers"} == {
        k: CATALOG[k] for k in served if k != "num_hidden_layers"}
    assert MODEL["layer_types"] == CATALOG["layer_types"][:8] == [L, L, L, F] * 2 and MODEL["num_hidden_layers"] == 8
    assert MODEL["head_dim"] * MODEL["num_attention_heads"] == MODEL["embed_dim"] == 3840
    assert MODEL["mlp_dims"] == [MODEL["intermediate_size"]]
    assert MODEL["num_fields"] == CONFIG["toml"]["server"]["num_fields"] == 2048
    assert CONFIG["toml"]["server"]["buckets"] == [2, 4]
    assert "FOUR PIPELINE STAGES" in CONFIG["deployment"] and "WHOLE on its chip" in CONFIG["deployment"]
    assert {"wire", "head", "toml_keys", "norm_placement", "rotary", "linear_mixer", "rule_form", "precision",
            "last_position", "weights"} <= set(CONFIG["assumed"])
    assert 0 < CONFIG["tolerance"] < 1e-3 and "chip" in CONFIG["tolerance_why"]


def test_the_cell_is_where_the_issue_put_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] if c["config"] == CONFIG["name"])
    assert (cell["name"], cell["traffic"], cell["chips"]) == ("olmo_hybrid_rerank-bulk", "rerank_pairs_closed", 1)
    assert cell["name"] in next(m for m in bench["end_to_end"] if m["name"] == "cand_per_s")["workloads"]
    on = {m["name"] for m in bench["per_layer"] if cell["name"] in m.get("workloads", ())}
    phi4 = {m["name"] for m in bench["per_layer"] if "phi4_mini_flash_rerank-bulk" in m.get("workloads", ())}
    assert on == phi4 | {"attn_masked_score_pct.bulk", "delta_handovers_per_row.bulk"}
    new = next(m for m in bench["per_layer"] if m["name"] == "delta_handovers_per_row.bulk")
    assert new == {"name": "delta_handovers_per_row.bulk", "unit": "handovers/row", "better": "lower",
                   "source": "program_counter", "layer": "kernels", "moves": "cand_per_s", "workloads": [cell["name"]]}
    assert not any(c["chips"] == 4 for c in bench["workloads"])


def test_reference_matches_the_programs_family_at_tiny_widths():
    import jax

    from distributed_tf_serving_tpu.models import ModelConfig, build_model

    reference = load_module(os.path.join(HERE, "reference.py"), "ref_olmo")
    config = ModelConfig(
        num_fields=70, vocab_size=500, embed_dim=64, intermediate_size=96, num_hidden_layers=4,
        layer_types=(L, L, L, F), num_attention_heads=4, num_key_value_heads=2, head_dim=16, layer_norm_eps=1e-6,
        linear_num_key_heads=3, linear_num_value_heads=3, linear_key_head_dim=8, linear_value_head_dim=12,
        compute_dtype="float32")
    model = build_model("olmo_hybrid", config)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"feat_ids": rng.integers(0, 500, size=(3, 70)).astype(np.int32),
             "feat_wts": rng.random((3, 70), dtype=np.float32)}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.forward(p, b, head=16))(params, batch))
        got = np.asarray(jax.jit(model.apply)(params, batch)["prediction_node"])
    assert np.max(np.abs(want - got)) < 1e-6
    # the reference's defaults are the published sizes the configuration serves
    assert (reference.HEAD, reference.EPS, reference.NEG_EIGVAL) == (
        MODEL["head_dim"], MODEL["layer_norm_eps"], MODEL["linear_allow_neg_eigval"])
    assert list(reference.LAYER_TYPES) == CATALOG["layer_types"]


def test_step_cost_counts_the_served_step_by_hand():
    H, N, I = 3840, 2048, 11008
    lin_in = H * (2 * 2880 + 5760 + 2 * 30)  # q, k, v, b, a
    lin_out = 2 * H * 5760  # the output gate and W_o
    conv = 2 * 4 * 11520
    rule = 30 * 6 * 96 * 192  # S'k, the rank-one update, S'q a position and head
    mlp = 3 * H * I
    pair = 2 * 30 * (128 + 128)
    linear_layer = N * (2 * (lin_in + lin_out + mlp) + conv + rule)
    full_layer = N * 2 * (4 * H * H + mlp) + N * (N + 1) // 2 * pair
    # layer 7: keys and values at all positions, the query, the output and the MLP at one
    last = N * 2 * (2 * H * H) + 2 * (2 * H * H + mlp) + N * pair
    row = 6 * linear_layer + full_layer + last + 2 * H
    flops, moved = COST.step_cost(MODEL, 4, 1)
    assert flops == 4 * row and flops == pytest.approx(25.0e12, rel=0.01)
    weights = 6 * (lin_in + lin_out + 4 * 11520 + mlp) + 2 * (4 * H * H + mlp)
    state = 30 * 96 * 192 * 4
    assert weights == pytest.approx(1.665e9, rel=0.001) and state == 2_211_840
    assert moved == 4 * (N * (2 * H + 7) + 4 + 6 * 32 * 2 * state) + 2 * weights
    assert peaks.least_seconds(flops, moved, "TPU v5 lite")[1] == "compute"
    assert COST.step_cost(MODEL, 8, 2)[0] == 2 * flops and COST.handovers(2048) == 32 and COST.handovers(150) == 3
    rule_flops, rule_bytes = COST.delta_rule_cost(MODEL, 4)
    assert rule_flops == 4 * N * rule and 6 * rule_flops == pytest.approx(0.163e12, rel=0.01)
    assert rule_bytes == 4 * (N * 4 * (30 * (2 * 96 + 2 * 192) + 60) + 32 * 2 * state)
    # alone, the rule's recurrence is bound by the memory: a state of 2.2 MB in and out a chunk and row
    assert peaks.least_seconds(rule_flops, rule_bytes, "TPU v5 lite")[1] == "memory"
    full_flops, full_bytes = COST.full_attention_cost(MODEL, 4)
    assert full_flops == 4 * (N * 2 * 4 * H * H + N * (N + 1) // 2 * pair) and full_bytes == 2 * 4 * H * H + 4 * N * 8 * H
    assert 4 * N * (N + 1) // 2 * pair == pytest.approx(0.129e12, rel=0.01)
    assert COST.conv_cost(MODEL, 4) == (4 * N * conv, 4 * N * 8 * 11520)
    # a linear last layer: its rule at all positions, its gate, projection and MLP at one
    other, _ = COST.step_cost({**MODEL, "layer_types": [L, L, F, L, L, L, F, L]}, 4, 1)
    assert other == 4 * (6 * linear_layer + 2 * full_layer - (N - 1) * 2 * (lin_out + mlp) + 2 * H)


@pytest.mark.parametrize("counts,want", [((192 * 340, 340), 192.0), ((12288, 1), 12288.0), ((0, 0), None)])
def test_the_new_reader(counts, want):
    import sys

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "layers"))
    try:
        read = load_module(os.path.join(ROOT, "benchmark", "layers", "delta_handovers_per_row.py"), "reader_delta").read
    finally:
        sys.path.pop(0)
    names = ("delta.handovers", "delta.rows")
    ctx = {"phases": {n: {"count": c, "total_ms": 0.0} for n, c in zip(names, counts) if c}}
    assert read(ctx) == want
