"""The sdar_30b_a3b_rerank configuration's own files: the file's numbers
against the catalog row and its served TOML, its parameter arithmetic, the
cell's place in BENCHMARK.json, the reference at a tiny size against the
program's family through the harness's own loader, `cost.py`'s counts against
a hand count, and the new reader on nothing and on counters."""

import json
import os

import numpy as np
import pytest

from benchmark import peaks
from benchmark.common import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark", "configs", "sdar_30b_a3b_rerank")
with open(os.path.join(HERE, "config.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["toml"]["model"]
COST = load_module(os.path.join(HERE, "cost.py"), "cost_sdar_moe")
CELL = "sdar_30b_a3b_rerank-bulk"
# The catalog row's `config` (model-configs guide, architectures.jsonl).
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 6144, "max_position_embeddings": 32768, "max_window_layers": 48, "mlp_only_layers": [],
    "model_type": "sdar_moe", "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}
H, L, F, B = 2048, 2048, 768, 4
ATTENTION = 2 * H * 4096 + 2 * H * 512  # q and o; k and v
ROUTER, EXPERT, NORMS = H * 128, 3 * H * F, 2 * H + 2 * 128
LAYER = ATTENTION + ROUTER + 128 * EXPERT + NORMS
EMBEDDING = 151936 * H


def test_the_file_holds_the_catalog_rows_numbers_and_serves_them():
    assert all(key in CONFIG for key in CATALOG)  # a null is a key too
    differs = {k for k, v in CATALOG.items() if CONFIG[k] != v}
    assert differs == set(CONFIG["reduced"]) == {"num_hidden_layers"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] in CONFIG["source"]
    assert CONFIG["published"]["num_hidden_layers"] == 48 and CONFIG["num_hidden_layers"] == MODEL["num_hidden_layers"] == 5
    # no width is cut and no expert left out: every published size is the one served, under this package's names
    served = {
        "hidden_size": MODEL["embed_dim"], "rms_norm_eps": MODEL["layer_norm_eps"], "rope_theta": MODEL["rope_theta"],
        **{k: MODEL[k] for k in (
            "num_attention_heads", "num_key_value_heads", "head_dim", "num_experts", "num_experts_per_tok",
            "norm_topk_prob", "moe_intermediate_size", "vocab_size")},
    }
    assert served == {k: CONFIG[k] for k in served}
    assert (MODEL["experts_held"], MODEL["first_expert_held"]) == (128, 0) == (CATALOG["num_experts"], 0)  # the layer WHOLE
    assert MODEL["block_length"] == B and L % B == 0 and "block_length" in CONFIG["assumed"]
    assert MODEL["num_fields"] == CONFIG["toml"]["server"]["num_fields"] == L
    assert CONFIG["toml"]["server"]["model_kind"] == "sdar_moe" and CONFIG["toml"]["server"]["buckets"] == [2, 4, 8]
    assert MODEL["compute_dtype"] == MODEL["param_dtype"] == "bfloat16"
    assert "PIPELINE" in CONFIG["deployment"] and "WHOLE" in CONFIG["deployment"] and "FIRST" in CONFIG["deployment"]
    assert {"block_length", "one_pass", "head_norms", "wire", "head", "toml_keys", "rotary", "router", "weights",
            "last_position", "precision", "buckets", "ids"} <= set(CONFIG["assumed"])
    assert "not on a scorer's path" in CONFIG["assumed"]["one_pass"] and "as remembered" in CONFIG["assumed"]["head_norms"]
    assert "6144" in CONFIG["assumed"]["toml_keys"]  # intermediate_size names no size a layer reads
    assert 0 < CONFIG["tolerance"] < 1e-2 and "chip" in CONFIG["tolerance_why"]


def test_the_files_parameter_arithmetic():
    assert (ATTENTION, ROUTER, EXPERT, NORMS) == (18_874_368, 262_144, 4_718_592, 4_352)
    assert round(128 * EXPERT / 1e4) == 60398 and round(LAYER / 1e5) == 6231 and round(EMBEDDING / 1e5) == 3112
    assert all(text in CONFIG["deployment"] for text in ("18.87 M", "0.26 M", "4.719 M", "603.98 M", "623.1 M", "311.2 M"))
    total = 5 * LAYER + EMBEDDING
    assert round(total / 1e5) == 34268 and "3,426.8 M" in CONFIG["deployment"]
    assert 2 * total / 16e9 == pytest.approx(0.428, abs=0.001) and "42.8%" in CONFIG["deployment"]  # of the chip, in bfloat16
    assert round((48 * LAYER + 2 * EMBEDDING) / 1e8) == 305 and "30.5 B" in CONFIG["deployment"]  # the model whole
    assert round((6 * LAYER + EMBEDDING) / 1e5) == 40499 and "4,049.9 M" in CONFIG["deployment"]  # six layers: past the host
    published = CONFIG["published"]["parameters"]
    assert (published["a_layer_M"], published["this_configuration_M"], published["the_model_B"]) == (623.1, 3426.8, 30.5)
    # the MEAN load of an expert: 16,384 tokens x 8 / 128 = 1,024 a step (the seeded router skews it); the layout's bound in rows
    assert 8 * L * 8 // 128 == 1024 and 8 * L * 8 + 128 * 128 == 147456
    # what the mask keeps ahead: 3,072 of 2,101,248 a row and layer at all positions, 0.146%
    assert COST.seen_pairs(L, B) == 2_101_248 and L * (B - 1) // 2 == 3072
    assert 100 * 3072 / 2_101_248 == pytest.approx(0.146, abs=0.001)


def test_the_cell_is_where_the_issue_put_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("sdar_30b_a3b_rerank", "rerank_pairs_closed", 1)
    assert len(bench["workloads"]) >= 14 and len(bench["configs"]) >= 12
    assert not any(c["chips"] == 4 for c in bench["workloads"][:14])
    assert sum(c["config"] == "sdar_30b_a3b_rerank" for c in bench["workloads"]) == 1  # one cell, no second
    names = [m["name"] for m in bench["per_layer"]]
    new = bench["per_layer"][names.index("attn_ahead_score_pct.bulk")]
    assert (new["source"], new["layer"], new["moves"], new["unit"], new["better"], new["workloads"][0]) == (
        "program_counter", "kernels", "cand_per_s", "%", "higher", CELL)  # a later cell appends after it: nothing else is pinned
    assert names.index(new["name"]) > names.index("layout_fill_pct.bulk")  # appended after PR 60's
    upto = bench["per_layer"][:names.index(new["name"]) + 1]  # what this PR saw: a later PR appends after these
    on = {m["name"] for m in upto if CELL in m.get("workloads", ())}
    nemotron = {m["name"] for m in upto if "nemotron3_super_120b_rerank-bulk" in m.get("workloads", ())}
    # the routed sequence cells' metrics, less the Mamba-2 mixer's three, and the one this PR brings
    assert on == (nemotron - {"ssd_handovers_per_row.bulk", "pallas_ssd_pct.bulk", "pallas_conv_pct.bulk"}) | {new["name"]}
    assert {"held_assignments_per_token.bulk", "expert_load_skew.bulk", "pallas_grouped_pct.bulk", "expert_pad_rows_pct.bulk",
            "held_experts_hit_pct.bulk", "layout_fill_pct.bulk", "attn_masked_score_pct.bulk", "pallas_attention_pct.bulk",
            "fused_products_pct.bulk", "step_roofline", "device_idle_pct.bulk"} <= on
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "cand_per_s")["workloads"]
    assert bench["workloads"].index(cell) == 13 and bench["configs"][11]["name"] == "sdar_30b_a3b_rerank"
    entry = bench["configs"][11]
    assert all(len(text) <= 200 for text in (cell["why"], entry["why"], entry["source"]))


TINY = dict(
    num_fields=48, vocab_size=500, embed_dim=32, num_hidden_layers=3, block_length=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, rope_theta=1e6, layer_norm_eps=1e-6, num_experts=16, num_experts_per_tok=4,
    norm_topk_prob=True, moe_intermediate_size=16, experts_held=16, first_expert_held=0, compute_dtype="float32")
SIZES = dict(head=16, theta=1e6, eps=1e-6, block=4, first=0, top_k=4, norm_topk=True)


def test_reference_matches_the_programs_family_at_tiny_widths():
    import jax

    from distributed_tf_serving_tpu.models import ModelConfig, build_model

    model = build_model("sdar_moe", ModelConfig(**TINY))
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    # matrices wide enough that the router, the gates and the logit spread
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w * 8.0 if w.ndim >= 2 and path[-1].key != "embedding" or path[-1].key == "score" else w, params)
    rng = np.random.default_rng(1)
    batch = {"feat_ids": rng.integers(0, 500, size=(3, 48)).astype(np.int32),
             "feat_wts": rng.random((3, 48), dtype=np.float32)}
    reference = load_module(os.path.join(HERE, "reference.py"), "ref_sdar_moe")
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.forward(p, b, **SIZES))(params, batch))
        causal = np.asarray(jax.jit(lambda p, b: reference.forward(p, b, **{**SIZES, "block": 1}))(params, batch))
        out, stats = jax.jit(model.apply_stats)(params, batch)
    assert want.std() > 0.02 and np.max(np.abs(want - np.asarray(out["prediction_node"]))) < 2e-6
    assert np.max(np.abs(want - causal)) > 1e-3  # the mask is in the reference too
    named = dict(zip(model.step_stats, stats.tolist()))
    assert named["moe.tokens"] == 3 * (2 * 48 + 1) and named["moe.assignments_here"] == 4 * named["moe.tokens"]
    assert named["attn.scores_ahead"] == 3 * 2 * 48 * 3 // 2
    # the reference's defaults are the published sizes the configuration serves
    assert (reference.HEAD, reference.THETA, reference.EPS, reference.BLOCK, reference.TOP_K, reference.FIRST,
            reference.NORM_TOPK) == (
        MODEL["head_dim"], MODEL["rope_theta"], MODEL["layer_norm_eps"], MODEL["block_length"],
        MODEL["num_experts_per_tok"], MODEL["first_expert_held"], MODEL["norm_topk_prob"])
    assert "distributed_tf_serving_tpu" not in open(os.path.join(HERE, "reference.py")).read()


def test_step_cost_counts_the_served_step_by_hand():
    kv, q_o = 2 * H * 512, 2 * H * 4096
    pair = 2 * 32 * (128 + 128)  # q k' and p v over 128, 32 query heads
    pairs = L * (L + 1) // 2 + L * (B - 1) // 2  # the causal pairs and the rest of a query's block
    routed = 2 * (ROUTER + 8 * EXPERT)  # every one of a token's 8 experts is here
    whole = L * 2 * (kv + q_o) + pairs * pair + L * routed
    cut = L * 2 * kv + 2 * q_o + L * pair + routed  # layer 4: keys and values at all positions, the rest at one
    row = 4 * whole + cut + 2 * H
    flops, moved = COST.step_cost(MODEL, 8, 1)
    assert flops == 8 * row and flops == pytest.approx(8.63e12, rel=0.01)
    weights = 5 * LAYER + 2 * H
    assert moved == 8 * (L * (2 * H + 7) + 4) + 2 * weights
    assert peaks.least_seconds(flops, moved, "TPU v5 lite")[1] == "compute"
    assert COST.step_cost(MODEL, 16, 2)[0] == 2 * flops
    # by operations the routed layers are over half of the step: the issue reckoned 58% of a layer
    assert 8 * 4 * L * routed / flops == pytest.approx(0.575, abs=0.02)
    # a share of the layer counts its own passes; one more layer at the last position alone is one more block a row
    half = COST.step_cost({**MODEL, "experts_held": 64}, 8, 1)[0]
    assert flops - half == 8 * (4 * L + 1) * 2 * 4 * EXPERT
    more = COST.step_cost({**MODEL, "num_hidden_layers": 6}, 8, 1)[0]
    assert more - flops == 8 * (whole - cut + cut)


@pytest.mark.parametrize("phases,want", [
    ({"attn.scores_ahead": 3072 * 4 * 8 * 50, "attn.scores_seen": (4 * 2_101_248 + 2048) * 8 * 50}, 100 * 12288 / 8_407_040),
    ({"attn.scores_ahead": 0, "attn.scores_seen": 8_394_752}, 0.0),
    ({"attn.scores_seen": 8_394_752, "attn.scores_computed": 9_000_000}, None),
    ({"attn.scores_ahead": 0}, None), ({}, None)],
    ids=["a block of 4 over 2,048 positions", "a causal mask served in its place", "a causal family: no such counter",
         "no batch", "nothing"])
def test_the_new_reader(phases, want):
    import sys

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "layers"))
    try:
        read = load_module(os.path.join(ROOT, "benchmark", "layers", "attn_ahead_score_pct.py"), "reader_ahead").read
    finally:
        sys.path.pop(0)
    got = read({"phases": {k: {"count": v, "total_ms": 0.0} for k, v in phases.items()}, "runtime": {}})
    assert got == want if want is None or want == 0.0 else got == pytest.approx(want)
    if want:
        assert want == pytest.approx(0.146, abs=0.001)  # the last layer's lone query sees 2,048 more and none ahead
