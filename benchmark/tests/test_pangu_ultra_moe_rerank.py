"""The pangu_ultra_moe_rerank configuration's own files: the reference against
the program's family at tiny widths, the file's numbers against the catalog
row and its served TOML, `cost.py`'s counts against a hand count, and the two
readers on a made-up context."""

import json
import os

import numpy as np
import pytest

from benchmark import peaks
from benchmark.common import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark", "configs", "pangu_ultra_moe_rerank")
with open(os.path.join(HERE, "config.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["toml"]["model"]
COST = load_module(os.path.join(HERE, "cost.py"), "cost_pangu")
# The catalog row's `config` (model-configs guide, architectures.jsonl).
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu", "hidden_size": 7680,
    "intermediate_size": 18432, "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "pangu_ultra_moe", "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 25600000, "routed_scaling_factor": 2.5, "sandwich_norm": True,
    "tie_word_embeddings": False, "v_head_dim": 128, "vocab_size": 153600,
}
REDUCED = {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "num_attention_heads", "vocab_size"}


def test_the_file_holds_the_catalog_rows_numbers_and_serves_them():
    differs = {k for k, v in CATALOG.items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"]) == REDUCED
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == CONFIG["name"])
    assert set(entry["reduced"]) == REDUCED
    # every key cut is stated with its published value, and no width is cut
    assert {k: CONFIG["published"][k] for k in REDUCED} == {k: CATALOG[k] for k in REDUCED}
    widths = ("hidden_size", "intermediate_size", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "moe_intermediate_size", "num_experts_per_tok", "routed_scaling_factor")
    assert not set(widths) & REDUCED
    served = {
        "hidden_size": MODEL["embed_dim"], "intermediate_size": MODEL["intermediate_size"],
        "num_key_value_heads": MODEL["num_attention_heads_published"],  # MLA reads it nowhere: as published
        "n_routed_experts": MODEL["experts_held"], "rms_norm_eps": MODEL["layer_norm_eps"],
        "rope_theta": MODEL["rope_theta"],
        **{k: MODEL[k] for k in (
            "num_hidden_layers", "first_k_dense_replace", "num_attention_heads", "intermediate_size", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "moe_intermediate_size",
            "num_experts_per_tok", "routed_scaling_factor", "vocab_size")},
    }
    assert served == {k: CONFIG[k] for k in served}
    # the router keeps its published width; the share is a whole one of the deployment's 32 chips
    assert MODEL["n_routed_experts"] == CATALOG["n_routed_experts"] == 32 * MODEL["experts_held"]
    assert MODEL["num_attention_heads_published"] == CATALOG["num_attention_heads"] == 4 * MODEL["num_attention_heads"]
    assert CATALOG["intermediate_size"] == MODEL["intermediate_size"] and CATALOG["vocab_size"] == 8 * MODEL["vocab_size"]
    assert MODEL["num_fields"] == CONFIG["toml"]["server"]["num_fields"] == 1024
    assert "EP32 x TP4 x DP8" in CONFIG["deployment"]
    assert {"wire", "head", "sandwich_norm", "mla", "rotary_pairing", "router", "experts", "precision",
            "last_position", "weights"} <= set(CONFIG["assumed"])
    assert 0 < CONFIG["tolerance"] < 1e-3 and "chip" in CONFIG["tolerance_why"]


def test_reference_matches_the_programs_family_at_tiny_widths():
    import jax

    from distributed_tf_serving_tpu.models import ModelConfig, build_model

    reference = load_module(os.path.join(HERE, "reference.py"), "ref_pangu")
    config = ModelConfig(
        num_fields=40, vocab_size=500, embed_dim=64, intermediate_size=96, num_hidden_layers=3,
        first_k_dense_replace=1, num_attention_heads=2, num_attention_heads_published=8, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=25600000.0,
        moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4, routed_scaling_factor=2.5,
        experts_held=4, first_expert_held=4, compute_dtype="float32")
    model = build_model("pangu_moe", config)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"feat_ids": rng.integers(0, 500, size=(3, 40)).astype(np.int32),
             "feat_wts": rng.random((3, 40), dtype=np.float32)}
    sizes = dict(first=4, top_k=4, scaling=2.5, nope=16, rope=8, v_head=16, theta=25600000.0)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.forward(p, b, **sizes))(params, batch))
        got = np.asarray(jax.jit(model.apply)(params, batch)["prediction_node"])
    assert np.max(np.abs(want - got)) < 1e-6
    # the reference's defaults are the published sizes the configuration serves
    assert (reference.NOPE, reference.ROPE, reference.V_HEAD, reference.TOP_K, reference.SCALING,
            reference.THETA, reference.EPS, reference.FIRST) == (
        MODEL["qk_nope_head_dim"], MODEL["qk_rope_head_dim"], MODEL["v_head_dim"], MODEL["num_experts_per_tok"],
        MODEL["routed_scaling_factor"], MODEL["rope_theta"], MODEL["layer_norm_eps"], MODEL["first_expert_held"])


def test_once_there_hands_back_every_weight_as_it_is():
    """The reference ties a layer's weights to the layer before it for the
    host's memory alone: values and dtypes are untouched."""
    import jax.numpy as jnp

    reference = load_module(os.path.join(HERE, "reference.py"), "ref_pangu_once")
    rng = np.random.default_rng(1)
    tree = {"w": jnp.asarray(rng.normal(0, 0.02, (5, 7)), jnp.bfloat16),
            "norm": jnp.asarray([1.0, 1e-30, 3e38, -2.5], jnp.float32)}
    for sign in (1.0, -1.0):
        out = reference.once_there(jnp.full((2, 3, 4), sign * 3.0), tree)
        for name, w in tree.items():
            assert out[name].dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(out[name], np.float32), np.asarray(w, np.float32))


def test_step_cost_counts_the_served_step_by_hand():
    H, L = 7680, 1024
    attention = H * 1536 + 1536 * 32 * 192 + H * 576 + 512 * 32 * 256 + 32 * 128 * H
    assert attention == 61_308_928  # 61.3 M held of the published 196.5 M
    pairs = L * (L + 1) // 2 * 32 * 2 * (192 + 128)
    expert = 3 * H * 2048
    routed = H * 256 + expert + 8 * 8 / 256 * expert  # router, shared, the even share of the held
    # the dense layer and three routed layers at all positions, the last layer's
    # keys and values at all and the rest of it at one
    keys_values = H * 576 + 512 * 32 * 256
    row = (L * 2 * (4 * attention + 3 * H * 18432 + 3 * routed + keys_values) + 4 * pairs
           + 2 * (attention - keys_values + routed) + L * 32 * 2 * 320 + 2 * H)
    flops, moved = COST.step_cost(MODEL, 8, 1)
    assert flops == 8 * row and flops == pytest.approx(14.46e12, rel=0.01)
    weights = 5 * attention + 3 * H * 18432 + 4 * (H * 256 + 9 * expert)
    assert moved == 8 * (L * (2 * H + 7) + 4) + 2 * weights and weights == pytest.approx(2.44e9, rel=0.01)
    assert peaks.least_seconds(flops, moved, "TPU v5 lite")[1] == "compute"
    assert COST.step_cost(MODEL, 16, 2)[0] == 2 * flops
    whole, _ = COST.step_cost({**MODEL, "num_hidden_layers": 9}, 8, 1)
    assert whole - flops == 8 * 4 * (L * 2 * (attention + routed) + pairs)  # four more routed layers at all positions
    attn_flops, attn_bytes = COST.attention_cost(MODEL, 8)
    assert attn_flops == 8 * (L * 2 * attention + pairs) and attn_bytes == 2 * attention + 8 * L * 8 * H
    grouped_flops, grouped_bytes = COST.expert_cost(MODEL, 2048)
    assert grouped_flops == 2048 * 2 * expert and grouped_bytes == 2 * 8 * expert + 2048 * 8 * H
    assert peaks.least_seconds(grouped_flops, grouped_bytes, "TPU v5 lite")[1] == "memory"


@pytest.mark.parametrize("metric,counts,plan,want", [
    ("held_assignments_per_token", (4000, 1000, 200), {"held": 8}, 0.25),
    ("expert_load_skew", (4000, 1000, 200), {"held": 8}, 1.6),
    ("held_assignments_per_token", (0, 0, 0), {"held": 8}, None),  # the parent: no such counter
    ("expert_load_skew", (0, 0, 0), None, None),
])
def test_the_two_readers(metric, counts, plan, want):
    import sys

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "layers"))
    try:
        read = load_module(os.path.join(ROOT, "benchmark", "layers", metric + ".py"), "reader_" + metric).read
    finally:
        sys.path.pop(0)
    names = ("moe.tokens", "moe.assignments_here", "moe.busiest_expert_tokens")
    phases = {n: {"count": c, "total_ms": 0.0} for n, c in zip(names, counts) if c}
    ctx = {"phases": phases, "runtime": {"startup": {"expert_plan": {"M:1": plan}} if plan else {}}}
    assert read(ctx) == want
