"""The k_exaone_moe_rerank configuration's own files: the reference against
the program's family at tiny widths, the file's numbers against the catalog
row and its served TOML, `cost.py`'s counts against a hand count, and the new
reader on a made-up window."""

import json
import os

import numpy as np
import pytest

from benchmark import peaks
from benchmark.common import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark", "configs", "k_exaone_moe_rerank")
with open(os.path.join(HERE, "config.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["toml"]["model"]
COST = load_module(os.path.join(HERE, "cost.py"), "cost_exaone")
S, F = "sliding_attention", "full_attention"
# The catalog row's `config` (model-configs guide, architectures.jsonl).
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 6144,
    "intermediate_size": 18432, "layer_types": [S, S, S, F] * 12, "max_position_embeddings": 262144,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47, "model_type": "exaone_moe", "moe_intermediate_size": 2048,
    "mtp_layer_types": [F], "mtp_sliding_windows": [0], "n_group": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 8, "num_nextn_predict_layers": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "sliding_window": 128, "sliding_window_pattern": "LLLG",
    "sliding_windows": [128, 128, 128, 0] * 12, "tie_word_embeddings": False, "topk_group": 1,
    "vocab_size": 153600,
}
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size"}


def test_the_file_holds_the_catalog_rows_numbers_and_serves_them():
    differs = {k for k, v in CATALOG.items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"]) == REDUCED
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == CONFIG["name"])
    assert set(entry["reduced"]) == REDUCED
    # every key cut is stated with its published value, and no width is cut
    assert {k: CONFIG["published"][k] for k in REDUCED} == {k: CATALOG[k] for k in REDUCED}
    served = {
        "hidden_size": MODEL["embed_dim"], "num_experts": MODEL["experts_held"], "rms_norm_eps": MODEL["layer_norm_eps"],
        **{k: MODEL[k] for k in (
            "num_hidden_layers", "first_k_dense_replace", "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "moe_intermediate_size", "num_experts_per_tok", "routed_scaling_factor",
            "sliding_window", "vocab_size")},
    }
    assert served == {k: CONFIG[k] for k in served}
    assert MODEL["rope_theta"] == CATALOG["rope_parameters"]["rope_theta"]
    # the plan run is the published plan's first layers: a whole LLLG period and the one dense layer
    assert MODEL["layer_types"] == CATALOG["layer_types"][:5] == [S, S, S, F, S]
    assert CATALOG["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4 and MODEL["first_k_dense_replace"] == 1
    # the router keeps its published width; the share is a whole one of the deployment's 16 chips
    assert MODEL["num_experts"] == CATALOG["num_experts"] == 16 * MODEL["experts_held"]
    assert CATALOG["vocab_size"] == 8 * MODEL["vocab_size"]
    assert MODEL["num_fields"] == CONFIG["toml"]["server"]["num_fields"] == 2048
    assert CONFIG["toml"]["server"]["buckets"] == [2, 4]
    assert "16 chips share each layer" in CONFIG["deployment"]
    assert {"wire", "head", "toml_keys", "norm_placement", "rotary", "selection_bias", "precision",
            "last_position", "weights"} <= set(CONFIG["assumed"])
    assert 0 < CONFIG["tolerance"] < 1e-3 and "chip" in CONFIG["tolerance_why"]


def test_the_cell_is_where_the_issue_put_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "k_exaone_moe_rerank-bulk", "k_exaone_moe_rerank", "rerank_pairs_closed", 1)
    on = {m["name"] for m in bench["per_layer"] if cell["name"] in m.get("workloads", ())}
    pangu = {m["name"] for m in bench["per_layer"] if "pangu_ultra_moe_rerank-bulk" in m.get("workloads", ())}
    assert on == pangu | {"attn_masked_score_pct.bulk"} and bench["per_layer"][-1]["name"] == "attn_masked_score_pct.bulk"
    assert bench["per_layer"][-1]["workloads"] == [cell["name"]]


def test_reference_matches_the_programs_family_at_tiny_widths():
    import jax

    from distributed_tf_serving_tpu.models import ModelConfig, build_model

    reference = load_module(os.path.join(HERE, "reference.py"), "ref_exaone")
    config = ModelConfig(
        num_fields=40, vocab_size=500, embed_dim=64, intermediate_size=96, num_hidden_layers=5,
        first_k_dense_replace=1, layer_types=(S, S, S, F, S), sliding_window=8, num_attention_heads=8,
        num_key_value_heads=2, head_dim=16, rope_theta=1000000.0, moe_intermediate_size=32, num_experts=16,
        num_experts_per_tok=4, routed_scaling_factor=2.5, experts_held=4, first_expert_held=4,
        compute_dtype="float32")
    model = build_model("exaone_moe", config)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"feat_ids": rng.integers(0, 500, size=(3, 40)).astype(np.int32),
             "feat_wts": rng.random((3, 40), dtype=np.float32)}
    sizes = dict(first=4, top_k=4, scaling=2.5, window=8, head=16)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.forward(p, b, **sizes))(params, batch))
        got = np.asarray(jax.jit(model.apply)(params, batch)["prediction_node"])
    assert np.max(np.abs(want - got)) < 1e-6
    # the reference's defaults are the published sizes the configuration serves
    assert (reference.WINDOW, reference.HEAD, reference.TOP_K, reference.SCALING, reference.THETA, reference.EPS,
            reference.FIRST) == (
        MODEL["sliding_window"], MODEL["head_dim"], MODEL["num_experts_per_tok"], MODEL["routed_scaling_factor"],
        MODEL["rope_theta"], MODEL["layer_norm_eps"], MODEL["first_expert_held"])
    assert list(reference.LAYER_TYPES) == CATALOG["layer_types"]


def test_step_cost_counts_the_served_step_by_hand():
    H, L, W = 6144, 2048, 128
    attention = 2 * H * 8192 + 2 * H * 1024
    assert attention == 113_246_208
    pair = 2 * 64 * (128 + 128)  # q k' and p v over 128, 64 query heads
    window_pairs = W * (W + 1) // 2 + (L - W) * W
    full_pairs = L * (L + 1) // 2
    expert = 3 * H * 2048
    routed = H * 128 + expert + 8 * 8 / 128 * expert  # router, shared, the even share of the held
    # layers 0-3 at all positions (layer 0's MLP dense), layer 4's keys and values over its window and the rest at one
    row = (L * 2 * (4 * attention + 3 * H * 18432 + 3 * routed) + (3 * window_pairs + full_pairs) * pair
           + W * 2 * (2 * H * 1024) + 2 * (2 * H * 8192 + routed) + W * pair + 2 * H)
    flops, moved = COST.step_cost(MODEL, 4, 1)
    assert flops == 4 * row and flops == pytest.approx(16.2e12, rel=0.01)
    weights = 5 * attention + 3 * H * 18432 + 4 * (H * 128 + 9 * expert)
    assert moved == 4 * (L * (2 * H + 7) + 4) + 2 * weights and weights == pytest.approx(2.268e9, rel=0.01)
    assert peaks.least_seconds(flops, moved, "TPU v5 lite")[1] == "compute"
    assert COST.step_cost(MODEL, 8, 2)[0] == 2 * flops
    window_flops, window_bytes = COST.window_attention_cost(MODEL, 4)
    full_flops, full_bytes = COST.full_attention_cost(MODEL, 4)
    assert window_flops == 4 * (L * 2 * attention + window_pairs * pair) and window_bytes == 2 * attention + 4 * L * 8 * H
    assert full_flops - window_flops == 4 * (full_pairs - window_pairs) * pair and full_bytes == window_bytes
    assert (full_pairs - window_pairs) * pair * 4 == pytest.approx(0.24e12, rel=0.02)
    grouped_flops, grouped_bytes = COST.expert_cost(MODEL, 4096)
    assert grouped_flops == 4096 * 2 * expert and grouped_bytes == 2 * 8 * expert + 4096 * 8 * H
    # 512 tokens an expert: by the peaks just past the ridge; at pangu's 256 an expert, under it
    assert peaks.least_seconds(grouped_flops, grouped_bytes, "TPU v5 lite")[1] == "compute"
    assert peaks.least_seconds(*COST.expert_cost(MODEL, 2048), "TPU v5 lite")[1] == "memory"
    # a full last layer reads every position's keys and values
    longer, _ = COST.step_cost({**MODEL, "layer_types": [S, S, S, S, F]}, 4, 1)
    assert longer - flops == 4 * ((L - W) * (2 * 2 * H * 1024 + pair) + (window_pairs - full_pairs) * pair)


@pytest.mark.parametrize("counts,want", [((4_194_432 * 12, 2_860_352 * 12), 31.806), ((1000, 1000), 0.0), ((0, 0), None)])
def test_the_new_reader(counts, want):
    import sys

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "layers"))
    try:
        read = load_module(os.path.join(ROOT, "benchmark", "layers", "attn_masked_score_pct.py"), "reader_masked").read
    finally:
        sys.path.pop(0)
    names = ("attn.scores_computed", "attn.scores_seen")
    ctx = {"phases": {n: {"count": c, "total_ms": 0.0} for n, c in zip(names, counts) if c}}
    got = read(ctx)
    assert got is None if want is None else got == pytest.approx(want, abs=1e-3)
