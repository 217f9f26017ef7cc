"""The qwen3_next_80b_rerank configuration's own files: the file's numbers
against the catalog row and its served TOML, its parameter arithmetic, the
cell's place in BENCHMARK.json, the reference at a tiny size against the
program's family, `cost.py`'s counts against a hand count, and the new reader
on nothing and on counters."""

import json
import os

import numpy as np
import pytest

from benchmark import peaks
from benchmark.common import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark", "configs", "qwen3_next_80b_rerank")
with open(os.path.join(HERE, "config.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["toml"]["model"]
COST = load_module(os.path.join(HERE, "cost.py"), "cost_qwen3_next")
CELL = "qwen3_next_80b_rerank-bulk"
# The catalog row's `config` (model-configs guide, architectures.jsonl).
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5120, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next", "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
REDUCED = {"num_hidden_layers", "num_experts"}
H, L = 2048, 2048
LINEAR_MIX = H * (2048 + 2048 + 4096 + 4096) + H * 64 + 8192 * 4 + 4096 * H  # q, k, v, z; b, a; convolutions; out
FULL_MIX = H * 8192 + 2 * H * 512 + 4096 * H  # queries with gates; k, v; out
OUTSIDE = H * 512 + 3 * H * 512 + H  # router, shared expert, its gate
EXPERT = 3 * H * 512


def test_the_file_holds_the_catalog_rows_numbers_and_serves_them():
    assert all(key in CONFIG for key in CATALOG)  # a null is a key too
    differs = {k for k, v in CATALOG.items() if CONFIG[k] != v}
    assert differs == set(CONFIG["reduced"]) == REDUCED
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == CONFIG["name"])
    assert set(entry["reduced"]) == REDUCED and entry["source"] in CONFIG["source"]
    assert (CONFIG["published"]["num_hidden_layers"], CONFIG["published"]["num_experts"]) == (48, 512)
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"]) == (MODEL["num_hidden_layers"], MODEL["experts_held"])
    # no width is cut: every published size is the one served, under this package's names where they differ
    served = {
        "hidden_size": MODEL["embed_dim"], "rms_norm_eps": MODEL["layer_norm_eps"],
        **{k: MODEL[k] for k in (
            "num_hidden_layers", "full_attention_interval", "num_attention_heads", "num_key_value_heads", "head_dim",
            "partial_rotary_factor", "rope_theta", "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim", "num_experts_per_tok",
            "norm_topk_prob", "moe_intermediate_size", "shared_expert_intermediate_size", "vocab_size")},
    }
    assert served == {k: CONFIG[k] for k in served}
    assert MODEL["num_experts"] == CATALOG["num_experts"] == 512  # the ROUTER's width, as published
    assert (MODEL["experts_held"], MODEL["first_expert_held"]) == (128, 0) and 512 // 128 == 4  # one of four chips
    assert (MODEL["embed_dim"], MODEL["num_attention_heads"], MODEL["num_key_value_heads"], MODEL["head_dim"]) == (
        2048, 16, 2, 256)
    assert (MODEL["linear_num_key_heads"], MODEL["linear_num_value_heads"], MODEL["linear_key_head_dim"],
            MODEL["linear_value_head_dim"]) == (16, 32, 128, 128)
    assert MODEL["linear_allow_neg_eigval"] is False and MODEL["vocab_size"] == 151936
    # the depth: a whole period and the layer after it (four only with the host's readings that forced it)
    assert MODEL["num_hidden_layers"] in (4, 5)
    assert MODEL["num_fields"] == CONFIG["toml"]["server"]["num_fields"] == 2048
    assert CONFIG["toml"]["server"] == {"model_kind": "qwen3_next", "num_fields": 2048, "buckets": [2, 4, 8]}
    assert MODEL["compute_dtype"] == MODEL["param_dtype"] == "bfloat16"
    assert "FOUR chips" in CONFIG["deployment"] and "WHOLE" in CONFIG["deployment"]
    assert {"wire", "head", "toml_keys", "fused_projections", "norms", "gate_in_w_q", "rotary", "delta_rule", "router",
            "weights", "last_position", "precision", "attention_path"} <= set(CONFIG["assumed"])
    # the limit from the readings: three times over the served step's largest, three times under the bfloat16 reference's least
    assert 6.68e-4 < CONFIG["tolerance"] == 2e-3 < 6.22e-3 and "chip" in CONFIG["tolerance_why"]


def test_the_files_parameter_arithmetic():
    assert (LINEAR_MIX, FULL_MIX, OUTSIDE, EXPERT) == (33_718_272, 27_262_976, 4_196_352, 3_145_728)
    assert "33.72 M" in CONFIG["deployment"] and "27.26 M" in CONFIG["deployment"] and "4.20 M" in CONFIG["deployment"]
    held = 128 * EXPERT
    linear, full = LINEAR_MIX + OUTSIDE + held, FULL_MIX + OUTSIDE + held
    assert round(linear / 1e5) == 4406 and round(full / 1e5) == 4341 and "440.6 M" in CONFIG["deployment"]
    embedding = 151936 * H
    assert round(embedding / 1e5) == 3112
    dense = 4 * linear + full
    assert round(dense / 1e5) == 21964 and "2,196.4 M" in CONFIG["deployment"]
    total = dense + embedding
    assert round(total / 1e5) == 25075 and "2,507.5 M" in CONFIG["deployment"]
    assert 2 * total / 16e9 == pytest.approx(0.313, abs=0.001)  # of the chip, in bfloat16
    whole_layer = LINEAR_MIX + OUTSIDE + 512 * EXPERT
    assert 2 * whole_layer / 1e9 == pytest.approx(3.29, abs=0.01)  # one WHOLE layer, GB
    model = 36 * whole_layer + 12 * (FULL_MIX + OUTSIDE + 512 * EXPERT) + 2 * embedding
    assert round(model / 1e8) == 797  # the model whole: 79.7 B
    # the load of an expert: 16,384 tokens x 10 / 512 = 320 a step, half of the deployment's 640 at 8,192 tokens a chip
    assert 8 * L * 10 // 512 == 320 and 4 * 4 * L * 10 // 512 == 640


def test_the_cell_is_where_the_issue_put_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("qwen3_next_80b_rerank", "rerank_pairs_closed", 1)
    assert len(bench["workloads"]) >= 12 and len(bench["configs"]) >= 10
    assert sum(c["config"] == "qwen3_next_80b_rerank" for c in bench["workloads"]) == 1  # one cell, no second
    on = {m["name"] for m in bench["per_layer"] if cell["name"] in m.get("workloads", ())}
    exaone = {m["name"] for m in bench["per_layer"] if "k_exaone_moe_rerank-bulk" in m.get("workloads", ())}
    olmo = {m["name"] for m in bench["per_layer"] if "olmo_hybrid_rerank-bulk" in m.get("workloads", ())}
    # the first cell in which the routed layer and the delta rule meet: every metric either cell reports, and its own
    assert on == exaone | olmo | {"held_experts_hit_pct.bulk"}
    assert {"held_assignments_per_token.bulk", "expert_load_skew.bulk", "expert_pad_rows_pct.bulk",
            "pallas_grouped_pct.bulk", "delta_handovers_per_row.bulk", "pallas_delta_pct.bulk",
            "attn_masked_score_pct.bulk", "pallas_attention_pct.bulk", "fused_products_pct.bulk", "step_roofline",
            "device_idle_pct.bulk"} <= on
    new = next(m for m in bench["per_layer"] if m["name"] == "held_experts_hit_pct.bulk")
    assert bench["per_layer"][-1] is new  # appended, not put among the others
    assert (new["workloads"], new["source"], new["layer"], new["moves"], new["unit"], new["better"]) == (
        [cell["name"]], "program_counter", "kernels", "cand_per_s", "%", "higher")
    assert cell["name"] in next(m for m in bench["end_to_end"] if m["name"] == "cand_per_s")["workloads"]
    assert bench["workloads"][-1] is cell and bench["configs"][-1]["name"] == "qwen3_next_80b_rerank"
    entry = bench["configs"][-1]
    assert all(len(text) <= 200 for text in (cell["why"], entry["why"], entry["source"]))


TINY = dict(
    num_fields=70, vocab_size=500, embed_dim=32, num_hidden_layers=5, full_attention_interval=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, partial_rotary_factor=0.25, rope_theta=1e7,
    layer_norm_eps=1e-6, linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=12, linear_conv_kernel_dim=4, linear_allow_neg_eigval=False, num_experts=16,
    num_experts_per_tok=4, norm_topk_prob=True, moe_intermediate_size=16, shared_expert_intermediate_size=16,
    experts_held=4, first_expert_held=8, compute_dtype="float32")
SIZES = dict(first=8, top_k=4, head=16, rotary=4, theta=1e7, key_dim=8, eps=1e-6, neg_eigval=False)


def test_reference_matches_the_programs_family_at_tiny_widths():
    import jax

    from distributed_tf_serving_tpu.models import ModelConfig, build_model

    model = build_model("qwen3_next", ModelConfig(**TINY))
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    # matrices wide enough that the router, the gates and the logit spread
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w * 8.0 if w.ndim >= 2 and path[-1].key not in ("embedding", "conv_q", "conv_k", "conv_v")
        or path[-1].key in ("shared_gate", "score") else w, params)
    rng = np.random.default_rng(1)
    batch = {"feat_ids": rng.integers(0, 500, size=(3, 70)).astype(np.int32),
             "feat_wts": rng.random((3, 70), dtype=np.float32)}
    reference = load_module(os.path.join(HERE, "reference.py"), "ref_qwen3_next")
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.forward(p, b, **SIZES))(params, batch))
        out, stats = jax.jit(model.apply_stats)(params, batch)
    assert want.std() > 0.02 and np.max(np.abs(want - np.asarray(out["prediction_node"]))) < 2e-6
    named = dict(zip(model.step_stats, stats.tolist()))
    assert (named["delta.rows"], named["delta.handovers"], named["delta.positions"]) == (3, 3 * 4 * 2, 3 * 4 * 70)
    assert named["moe.tokens"] == 3 * (4 * 70 + 1) and 16 <= named["moe.experts_hit"] <= 20
    # the reference's defaults are the published sizes the configuration serves
    assert (reference.HEAD, reference.ROTARY, reference.THETA, reference.EPS, reference.KEY_DIM, reference.TOP_K,
            reference.FIRST, reference.NEG_EIGVAL) == (
        MODEL["head_dim"], int(MODEL["head_dim"] * MODEL["partial_rotary_factor"]), MODEL["rope_theta"],
        MODEL["layer_norm_eps"], MODEL["linear_key_head_dim"], MODEL["num_experts_per_tok"],
        MODEL["first_expert_held"], MODEL["linear_allow_neg_eigval"])
    assert "distributed_tf_serving_tpu" not in open(os.path.join(HERE, "reference.py")).read()


def test_step_cost_counts_the_served_step_by_hand():
    layers = MODEL["num_hidden_layers"]
    assert COST.layer_kinds(MODEL) == (["linear", "linear", "linear", "full", "linear"][:layers])
    lin_in = H * (2048 + 2048 + 4096) + H * 64  # q, k, v; b, a: at every position
    lin_out = 2 * H * 4096  # z and the output projection: where the rule's output is read
    conv = 2 * 4 * 8192  # four taps' multiply-adds, 8,192 channels
    rule = 32 * 6 * 128 * 128  # three products of a [128, 128] state a value head
    kv, q_o = 2 * H * 512, H * 8192 + 4096 * H
    pair, pairs = 2 * 16 * (256 + 256), L * (L + 1) // 2  # q k' and p v over 256, 16 query heads
    routed = 2 * (OUTSIDE + 10 * 128 / 512 * EXPERT)  # 2.5 held expert-passes a token
    linear_whole = L * (2 * (lin_in + lin_out) + conv + rule + routed)
    full_whole = L * (2 * (kv + q_o) + routed) + pairs * pair
    last = L * (2 * lin_in + conv + rule) + 2 * lin_out + routed  # layer 4: the rule at all positions, the rest at one
    assert layers == 5
    row = 3 * linear_whole + full_whole + last + 2 * H
    flops, moved = COST.step_cost(MODEL, 8, 1)
    assert flops == 8 * row and flops == pytest.approx(6.83e12, rel=0.01)  # the issue reckoned "about 6.3 TFLOP a step"
    weights = 4 * LINEAR_MIX + FULL_MIX + 5 * (OUTSIDE + 128 * EXPERT)
    state = 32 * 128 * 128 * 4  # a row's state, float32: 2.1 MB
    assert moved == 8 * (L * (2 * H + 7) + 4 + 4 * 32 * 2 * state) + 2 * weights
    assert weights == 2_196_383_744 and COST.handovers(L) == 32  # the dense weights of the file's arithmetic
    assert peaks.least_seconds(flops, moved, "TPU v5 lite")[1] == "compute"
    assert COST.step_cost(MODEL, 16, 2)[0] == 2 * flops
    # by operations the held experts are about a seventh of the step, the rule and the full layer's scores a few percent
    experts = 8 * (4 * L + 1) * 2.5 * 2 * EXPERT
    assert experts / flops == pytest.approx(0.151, abs=0.005) and 8 * 4 * L * rule / flops < 0.04
    assert COST.expert_cost(MODEL, 1000) == (1000 * 2 * EXPERT, 2 * 128 * EXPERT + 1000 * 8 * H)
    rule_flops, rule_bytes = COST.delta_rule_cost(MODEL, 8)
    assert rule_flops == 8 * L * rule and rule_bytes == 8 * (L * 4 * (2 * 2048 + 2 * 4096 + 64) + 32 * 2 * state)
    attn_flops, attn_bytes = COST.full_attention_cost(MODEL, 8)
    assert attn_flops == 8 * (L * 2 * (kv + q_o) + pairs * pair) and attn_bytes == 2 * (kv + q_o) + 8 * L * 8 * H
    assert COST.conv_cost(MODEL, 8) == (8 * L * conv, 8 * L * 8 * 8192)
    # one more layer (a linear one: layer 5) is one more whole linear layer
    assert COST.step_cost({**MODEL, "num_hidden_layers": 6}, 8, 1)[0] - flops == 8 * linear_whole


@pytest.mark.parametrize("phases,want", [
    ({"moe.experts_hit": 5 * 128 * 40 * 0.8, "batch.dispatch": 40}, 80.0),
    ({"moe.experts_hit": 5 * 128 * 7, "batch.dispatch": 7}, 100.0),
    ({"batch.dispatch": 7}, None), ({"moe.experts_hit": 99}, None), ({}, None)],
    ids=["a last layer that hits few", "every expert every step", "a program without the counter", "no batch", "nothing"])
def test_the_new_reader(phases, want):
    import sys

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "layers"))
    try:
        read = load_module(os.path.join(ROOT, "benchmark", "layers", "held_experts_hit_pct.py"), "reader_hit").read
    finally:
        sys.path.pop(0)
    startup = {"expert_plan": {"M:1": {"held": 128}}, "layer_plan": {"M:1": {"linear": 4, "full": 1}}}
    ctx = {"phases": {k: {"count": v, "total_ms": 0.0} for k, v in phases.items()}, "runtime": {"startup": startup}}
    got = read(ctx)
    assert got == want if want is None else got == pytest.approx(want)
    # a plan that names mixer and FFN: the layers named `/moe` alone are routed
    mixed = {"expert_plan": {"M:1": {"held": 8}}, "layer_plan": {"M:1": {"window/dense": 1, "window/moe": 3, "full/moe": 1}}}
    ctx = {"phases": {"moe.experts_hit": {"count": 4 * 8 * 10}, "batch.dispatch": {"count": 10}}, "runtime": {"startup": mixed}}
    assert read(ctx) == pytest.approx(100.0)
    assert read({"phases": ctx["phases"], "runtime": {}}) is None  # the parent: counters of another kind, no plan
