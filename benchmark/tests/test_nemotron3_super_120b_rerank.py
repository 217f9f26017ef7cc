"""The nemotron3_super_120b_rerank configuration's own files: the file's numbers
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
HERE = os.path.join(ROOT, "benchmark", "configs", "nemotron3_super_120b_rerank")
with open(os.path.join(HERE, "config.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["toml"]["model"]
COST = load_module(os.path.join(HERE, "cost.py"), "cost_nemotron_h")
CELL = "nemotron3_super_120b_rerank-bulk"
PATTERN = "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"
# The catalog row's `config` (model-configs guide, architectures.jsonl).
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 2688, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 128, "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h", "moe_intermediate_size": 2688,
    "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376, "moe_shared_expert_overlap": False,
    "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8, "n_routed_experts": 512, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2, "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None, "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072,
}
REDUCED = {"num_hidden_layers", "n_routed_experts"}
H, L, LATENT = 4096, 2048, 1024
MAMBA = H * (8192 + 8192 + 1024 + 1024 + 128) + 8192 * H + 10240 * 5 + 3 * 128 + 8192  # in; out; conv and bias; A, dt, D; norm
ATTENTION = 2 * H * 4096 + 2 * H * 256  # q and o; k and v
OUTSIDE = H * 512 + 2 * H * LATENT + 2 * H * 5376  # router; the latent's two projections; the shared expert
EXPERT = 2 * LATENT * 2688


def test_the_file_holds_the_catalog_rows_numbers_and_serves_them():
    assert all(key in CONFIG for key in CATALOG)  # a null is a key too
    differs = {k for k, v in CATALOG.items() if CONFIG[k] != v}
    assert differs == set(CONFIG["reduced"]) == REDUCED
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == CONFIG["name"])
    assert set(entry["reduced"]) == REDUCED and entry["source"] in CONFIG["source"]
    assert (CONFIG["published"]["num_hidden_layers"], CONFIG["published"]["n_routed_experts"]) == (88, 512)
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"]) == (MODEL["num_hidden_layers"], MODEL["experts_held"])
    assert CONFIG["published"]["hybrid_override_pattern"] == MODEL["hybrid_override_pattern"] == PATTERN
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*"), len(PATTERN)) == (40, 40, 8, 88)
    assert PATTERN[:MODEL["num_hidden_layers"]] == "MEMEMEM*EMEM"  # layers 0-11: 6 Mamba-2, 5 routed, 1 attention
    # no width is cut: every published size is the one served, under this package's names where they differ
    served = {
        "hidden_size": MODEL["embed_dim"], "layer_norm_epsilon": MODEL["layer_norm_eps"],
        "mamba_num_heads": MODEL["mamba_n_heads"], "mamba_head_dim": MODEL["mamba_d_head"],
        "ssm_state_size": MODEL["mamba_d_state"], "n_groups": MODEL["mamba_n_groups"], "conv_kernel": MODEL["mamba_d_conv"],
        "chunk_size": MODEL["mamba_chunk_size"],
        **{k: MODEL[k] for k in (
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob", "moe_latent_size", "moe_intermediate_size",
            "moe_shared_expert_intermediate_size", "vocab_size")},
    }
    assert served == {k: CONFIG[k] for k in served}
    assert MODEL["mamba_d_ssm"] == CONFIG["expand"] * CONFIG["hidden_size"] == 128 * 64
    assert MODEL["n_routed_experts"] == CATALOG["n_routed_experts"] == 512  # the ROUTER's width, as published
    assert (MODEL["experts_held"], MODEL["first_expert_held"]) == (64, 0) and 512 // 64 == 8  # one of eight chips
    assert MODEL["num_fields"] == CONFIG["toml"]["server"]["num_fields"] == 2048
    assert CONFIG["toml"]["server"]["model_kind"] == "nemotron_h" and CONFIG["toml"]["server"]["buckets"] in ([2, 4, 8], [2, 4])
    assert MODEL["compute_dtype"] == MODEL["param_dtype"] == "bfloat16"
    assert "EIGHT chips" in CONFIG["deployment"] and "WHOLE" in CONFIG["deployment"]
    assert {"wire", "head", "positions", "toml_keys", "fused_projection", "norms", "router", "weights", "last_position",
            "precision", "attention_path", "ssd_path", "buckets", "ids"} <= set(CONFIG["assumed"])
    assert "as remembered; no network here" in CONFIG["assumed"]["positions"]
    assert 0 < CONFIG["tolerance"] < 1e-2 and "chip" in CONFIG["tolerance_why"]


def test_the_files_parameter_arithmetic():
    assert (MAMBA, ATTENTION, OUTSIDE, EXPERT) == (109_635_968, 35_651_584, 54_525_952, 5_505_024)
    assert all(text in CONFIG["deployment"] for text in ("109.6 M", "35.65 M", "54.5 M", "5.505 M"))
    routed = OUTSIDE + 64 * EXPERT
    assert round(routed / 1e5) == 4068 and "406.8 M" in CONFIG["deployment"]
    embedding = 131072 * H
    assert round(embedding / 1e5) == 5369
    total = 5 * routed + 6 * MAMBA + ATTENTION + embedding
    assert round(total / 1e5) == 32646 and "3,264.6 M" in CONFIG["deployment"]
    assert 2 * total / 16e9 == pytest.approx(0.408, abs=0.001)  # of the chip, in bfloat16
    whole_layer = OUTSIDE + 512 * EXPERT
    assert 2 * whole_layer / 1e9 == pytest.approx(5.75, abs=0.01)  # one WHOLE routed layer, GB
    model = 40 * MAMBA + 8 * ATTENTION + 40 * whole_layer + 2 * embedding
    assert round(model / 1e8) == 1207  # the model whole: 120.7 B
    # the load of an expert: 16,384 tokens x 22 / 512 = 704 a step, an eighth of the deployment's 5,632
    assert 8 * L * 22 // 512 == 704 and 8 * 8 * L * 22 // 512 == 5632


def test_the_cell_is_where_the_issue_put_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("nemotron3_super_120b_rerank", "rerank_pairs_closed", 1)
    assert len(bench["workloads"]) >= 13 and len(bench["configs"]) >= 11
    assert sum(c["config"] == "nemotron3_super_120b_rerank" for c in bench["workloads"]) == 1  # one cell, no second
    new = next(m for m in bench["per_layer"] if m["name"] == "layout_fill_pct.bulk")
    names = [m["name"] for m in bench["per_layer"]]
    on = {m["name"] for m in bench["per_layer"][:names.index(new["name"]) + 1] if cell["name"] in m.get("workloads", ())}
    falcon = {m["name"] for m in bench["per_layer"][:names.index(new["name"])] if "falcon_h1_34b_rerank-bulk" in m.get("workloads", ())}
    routed = {"held_assignments_per_token.bulk", "expert_load_skew.bulk", "pallas_grouped_pct.bulk",
              "expert_pad_rows_pct.bulk", "held_experts_hit_pct.bulk"}
    # the first cell in which the routed layer and the Mamba-2 mixer meet: every metric either kind of cell reports
    assert on == falcon | routed | {"layout_fill_pct.bulk"}
    assert {"ssd_handovers_per_row.bulk", "pallas_ssd_pct.bulk", "attn_masked_score_pct.bulk", "pallas_attention_pct.bulk",
            "fused_products_pct.bulk", "step_roofline", "device_idle_pct.bulk"} <= on
    assert names.index(new["name"]) > names.index("held_experts_hit_pct.bulk")  # appended after PR 58's, not put among the others
    assert (new["source"], new["layer"], new["moves"], new["unit"], new["better"]) == (
        "program_counter", "kernels", "cand_per_s", "%", "higher")
    assert new["workloads"] == ["pangu_ultra_moe_rerank-bulk", "k_exaone_moe_rerank-bulk", "mimo_v2_5_rerank-bulk",
                                "qwen3_next_80b_rerank-bulk", cell["name"]]
    assert cell["name"] in next(m for m in bench["end_to_end"] if m["name"] == "cand_per_s")["workloads"]
    # appended after PR 58's (a later PR appends after these: no "last entry" is pinned, R0(28))
    assert bench["workloads"].index(cell) == 12 and bench["configs"][10]["name"] == "nemotron3_super_120b_rerank"
    entry = bench["configs"][10]
    assert all(len(text) <= 200 for text in (cell["why"], entry["why"], entry["source"]))


TINY = dict(
    num_fields=70, vocab_size=500, embed_dim=32, hybrid_override_pattern="MEME*EM", num_hidden_layers=7,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, layer_norm_eps=1e-5, mamba_d_ssm=64, mamba_n_heads=8,
    mamba_d_head=8, mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=32, n_routed_experts=16,
    num_experts_per_tok=4, routed_scaling_factor=5.0, norm_topk_prob=True, moe_latent_size=24, moe_intermediate_size=16,
    moe_shared_expert_intermediate_size=20, experts_held=4, first_expert_held=8, compute_dtype="float32")
SIZES = dict(head=16, ssm_head=8, groups=2, first=8, top_k=4, scaling=5.0, norm_topk=True, eps=1e-5)


def test_reference_matches_the_programs_family_at_tiny_widths():
    import jax

    from distributed_tf_serving_tpu.models import ModelConfig, build_model

    model = build_model("nemotron_h", ModelConfig(**TINY))
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    # matrices wide enough that the router, the gates and the logit spread
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w * 8.0 if w.ndim >= 2 and path[-1].key not in ("embedding", "conv_w")
        or path[-1].key == "score" else w, params)
    rng = np.random.default_rng(1)
    batch = {"feat_ids": rng.integers(0, 500, size=(3, 70)).astype(np.int32),
             "feat_wts": rng.random((3, 70), dtype=np.float32)}
    reference = load_module(os.path.join(HERE, "reference.py"), "ref_nemotron_h")
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.forward(p, b, **SIZES))(params, batch))
        out, stats = jax.jit(model.apply_stats)(params, batch)
    assert want.std() > 0.02 and np.max(np.abs(want - np.asarray(out["prediction_node"]))) < 2e-6
    named = dict(zip(model.step_stats, stats.tolist()))
    assert (named["ssd.rows"], named["ssd.handovers"], named["ssd.positions"]) == (3, 3 * 3 * 3, 3 * 3 * 70)
    assert named["moe.tokens"] == 3 * 3 * 70 and named["moe.experts_hit"] == 12
    # the reference's defaults are the published sizes the configuration serves
    assert (reference.HEAD, reference.SSM_HEAD, reference.GROUPS, reference.EPS, reference.TOP_K, reference.FIRST,
            reference.SCALING, reference.NORM_TOPK) == (
        MODEL["head_dim"], MODEL["mamba_d_head"], MODEL["mamba_n_groups"], MODEL["layer_norm_eps"],
        MODEL["num_experts_per_tok"], MODEL["first_expert_held"], MODEL["routed_scaling_factor"], MODEL["norm_topk_prob"])
    assert "distributed_tf_serving_tpu" not in open(os.path.join(HERE, "reference.py")).read()


def test_step_cost_counts_the_served_step_by_hand():
    assert COST.layer_kinds(MODEL) == ["mamba", "moe"] * 3 + ["mamba", "attention", "moe", "mamba", "moe", "mamba"]
    assert COST.positions(COST.layer_kinds(MODEL)) == ["all"] * 11 + ["cut"]
    assert COST.positions(["mamba", "attention", "moe", "moe"]) == ["all", "cut", "last", "last"]
    ssm_in, ssm_out = H * 18560, 8192 * H
    conv = (2 * 4 + 1) * 10240  # four taps' multiply-adds and the bias, 10,240 channels
    update = read = 2 * 128 * 64 * 128  # a [64, 128] state a head, 128 heads
    kv, q_o = 2 * H * 256, 2 * H * 4096
    pair, pairs = 2 * 32 * (128 + 128), L * (L + 1) // 2  # q k' and p v over 128, 32 query heads
    routed = 2 * (OUTSIDE + 22 * 64 / 512 * EXPERT)  # 2.75 held expert-passes a token
    mamba_whole = L * (2 * (ssm_in + ssm_out) + conv + update + read)
    mamba_cut = L * (2 * ssm_in + conv + update) + read + 2 * ssm_out  # layer 11: the state's walk at all positions, the rest at one
    attention_whole = L * 2 * (kv + q_o) + pairs * pair
    row = 5 * mamba_whole + mamba_cut + attention_whole + 5 * L * routed + 2 * H
    flops, moved = COST.step_cost(MODEL, 8, 1)
    assert flops == 8 * row and flops == pytest.approx(33.6e12, rel=0.01)  # the issue reckoned "33 TFLOP a 16,384-token step"
    small = 10240 * 5 + 3 * 128 + 8192
    weights = 6 * (ssm_in + ssm_out + small) + kv + q_o + 5 * (OUTSIDE + 64 * EXPERT)
    state = 128 * 64 * 128 * 4  # a row's state, float32: 4.19 MB
    assert moved == 8 * (L * (2 * H + 7) + 4 + 6 * 16 * 2 * state) + 2 * weights
    assert weights == 6 * MAMBA + ATTENTION + 5 * (OUTSIDE + 64 * EXPERT) and COST.handovers(MODEL) == 16
    assert peaks.least_seconds(flops, moved, "TPU v5 lite")[1] == "compute"
    assert COST.step_cost(MODEL, 16, 2)[0] == 2 * flops
    # by operations the six Mamba-2 layers are over half of the step and the held experts a seventh
    experts = 8 * 5 * L * 2.75 * 2 * EXPERT
    assert experts / flops == pytest.approx(0.074, abs=0.005) and 8 * (5 * mamba_whole + mamba_cut) / flops > 0.6
    assert COST.expert_cost(MODEL, 1000) == (1000 * 2 * EXPERT, 2 * 64 * EXPERT + 1000 * 8 * LATENT)
    assert COST.latent_cost(MODEL, 1000) == (1000 * 2 * 2 * H * LATENT, 2 * 2 * H * LATENT + 1000 * 8 * (H + LATENT))
    ssd_flops, ssd_bytes = COST.ssd_cost(MODEL, 8)
    assert ssd_flops == 8 * L * (update + read) and ssd_bytes == 8 * (L * 4 * (8192 + 10240 + 128) + 16 * 2 * state)
    attn_flops, attn_bytes = COST.attention_cost(MODEL, 8)
    assert attn_flops == 8 * attention_whole and attn_bytes == 2 * (kv + q_o) + 8 * L * 8 * H
    assert COST.conv_cost(MODEL, 8) == (8 * L * conv, 8 * L * 8 * 10240)
    # one more layer (a routed one: layer 12) at the last position alone is one more block a row
    assert COST.step_cost({**MODEL, "num_hidden_layers": 13}, 8, 1)[0] - flops == 8 * routed


@pytest.mark.parametrize("phases,grouped,want", [
    ({"moe.rows_computed": 5 * 368640 * 40 * 0.14, "batch.dispatch": 40}, {"rows": 368640}, 14.0),
    ({"moe.rows_computed": 5 * 1000 * 7, "batch.dispatch": 7}, {"rows": 1000}, 100.0),
    ({"moe.rows_computed": 99, "batch.dispatch": 7}, {"kernel": "pallas", "tile": 128, "pieces": 3}, None),
    ({"batch.dispatch": 7}, {"rows": 1000}, None), ({"moe.rows_computed": 99}, {"rows": 1000}, None), ({}, {}, None)],
    ids=["an eighth and the padding", "a layout filled", "a stamp without a bound", "a program without the counter",
         "no batch", "nothing"])
def test_the_new_reader(phases, grouped, want):
    import sys

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "layers"))
    try:
        read = load_module(os.path.join(ROOT, "benchmark", "layers", "layout_fill_pct.py"), "reader_fill").read
    finally:
        sys.path.pop(0)
    startup = {"grouped": {"M:1": grouped}, "layer_plan": {"M:1": {"mamba": 6, "latent/moe": 5, "attention": 1}}}
    ctx = {"phases": {k: {"count": v, "total_ms": 0.0} for k, v in phases.items()}, "runtime": {"startup": startup}}
    got = read(ctx)
    assert got == want if want is None else got == pytest.approx(want)
    # a plan that names mixers alone: every layer holds the routed block
    mixers = {"grouped": {"M:1": {"rows": 1000}}, "layer_plan": {"M:1": {"linear": 4, "full": 1}}}
    ctx = {"phases": {"moe.rows_computed": {"count": 5 * 1000 * 10 // 4}, "batch.dispatch": {"count": 10}},
           "runtime": {"startup": mixers}}
    assert read(ctx) == pytest.approx(25.0)
    assert read({"phases": ctx["phases"], "runtime": {}}) is None  # the parent of ISSUE 58: counters, no bound stated
