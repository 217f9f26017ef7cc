"""The falcon_h1_34b_rerank configuration's own files: the file's numbers
against the catalog row and its served TOML, its parameter arithmetic, the
cell's place in BENCHMARK.json, the reference at a tiny size against a NumPy
loop of the recurrence and against the program's family, `cost.py`'s counts
against a hand count, and the new reader on nothing and on counters."""

import json
import os

import numpy as np
import pytest

from benchmark import peaks
from benchmark.common import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark", "configs", "falcon_h1_34b_rerank")
with open(os.path.join(HERE, "config.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["toml"]["model"]
COST = load_module(os.path.join(HERE, "cost.py"), "cost_falcon")
CELL = "falcon_h1_34b_rerank-bulk"
# The catalog row's `config` (model-configs guide, architectures.jsonl).
CATALOG = {
    "attention_bias": False, "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
    "attn_layer_indices": None, "embedding_multiplier": 5.656854249492381, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 21504, "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2, "mamba_n_groups": 2,
    "mamba_n_heads": 32, "mamba_norm_before_gate": False, "mamba_proj_bias": False, "mamba_rms_norm": True,
    "mamba_use_mlp": True, "max_position_embeddings": 262144, "mlp_bias": False, "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284], "model_type": "falcon_h1",
    "num_attention_heads": 20, "num_hidden_layers": 72, "num_key_value_heads": 4, "num_logits_to_keep": 1,
    "projectors_bias": False, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False, "vocab_size": 261120,
}
REDUCED = {"num_hidden_layers"}
LAYER = 430_120_032  # parameters a layer


def test_the_file_holds_the_catalog_rows_numbers_and_serves_them():
    assert all(key in CONFIG for key in CATALOG)  # a null is a key too
    differs = {k for k, v in CATALOG.items() if CONFIG[k] != v}
    assert differs == set(CONFIG["reduced"]) == REDUCED
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == CONFIG["name"])
    assert set(entry["reduced"]) == REDUCED and entry["source"] in CONFIG["source"]
    assert CONFIG["published"]["num_hidden_layers"] == CATALOG["num_hidden_layers"] == 72
    # no width is cut: every published size is the one served, under this package's names where they differ
    served = {
        "hidden_size": MODEL["embed_dim"], "rms_norm_eps": MODEL["layer_norm_eps"],
        **{k: MODEL[k] for k in (
            "num_hidden_layers", "intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "rope_theta", "mamba_d_ssm", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
            "mamba_n_groups", "mamba_d_conv", "mamba_chunk_size", "embedding_multiplier", "attention_in_multiplier",
            "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
            "ssm_multipliers", "mlp_multipliers")},
    }
    assert served == {k: CONFIG[k] for k in served}
    assert (MODEL["embed_dim"], MODEL["num_attention_heads"], MODEL["num_key_value_heads"], MODEL["head_dim"]) == (
        5120, 20, 4, 128)
    assert (MODEL["intermediate_size"], MODEL["mamba_d_ssm"], MODEL["mamba_n_heads"], MODEL["mamba_d_head"]) == (
        21504, 4096, 32, 128)
    assert (MODEL["mamba_d_state"], MODEL["mamba_n_groups"], MODEL["mamba_d_conv"], MODEL["mamba_chunk_size"]) == (
        256, 2, 4, 128)
    assert MODEL["vocab_size"] == 261120 and MODEL["mlp_dims"] == [MODEL["intermediate_size"]]
    assert MODEL["mamba_d_ssm"] == MODEL["mamba_n_heads"] * MODEL["mamba_d_head"]
    assert MODEL["intermediate_size"] * 5 == CATALOG["mlp_expansion_factor"] * MODEL["embed_dim"] * 21 // 8  # 4.2 x hidden
    # the depth alone is cut: five layers (four only with the host's readings that forced it), never fewer
    assert MODEL["num_hidden_layers"] == CONFIG["num_hidden_layers"] and MODEL["num_hidden_layers"] in (4, 5)
    assert MODEL["num_fields"] == CONFIG["toml"]["server"]["num_fields"] == 2048
    assert CONFIG["toml"]["server"] == {"model_kind": "falcon_h1", "num_fields": 2048, "buckets": [2, 4]}
    assert MODEL["compute_dtype"] == MODEL["param_dtype"] == "bfloat16"
    assert "PIPELINE" in CONFIG["deployment"] and "WHOLE" in CONFIG["deployment"]
    assert {"wire", "head", "toml_keys", "multipliers", "gated_norm", "rotary", "dt", "weights", "last_position",
            "precision", "ssd_form"} <= set(CONFIG["assumed"])
    assert 0 < CONFIG["tolerance"] < 1e-3 and "chip" in CONFIG["tolerance_why"]


def test_the_files_parameter_arithmetic():
    H = 5120
    attention = H * (2560 + 512 + 512) + 2560 * H
    ssm_in, ssm_out = H * (4096 + 5120 + 32), 4096 * H
    small = 5120 * 4 + 5120 + 3 * 32 + 4096  # the convolution and its bias, dt_bias, A_log, D, the gated norm
    mlp = 3 * H * 21504
    assert (attention, ssm_in, ssm_out, mlp) == (31_457_280, 47_349_760, 20_971_520, 330_301_440)
    assert attention + ssm_in + ssm_out + small + mlp + 2 * H == LAYER
    assert round(mlp / LAYER, 2) == 0.77  # the MLP is 77% of a layer's weights
    embedding = 261120 * H
    assert embedding == 1_336_934_400
    layers = MODEL["num_hidden_layers"]
    total = layers * LAYER + embedding + 2 * H  # the final norm and the score vector
    assert round(total / 1e5) == {5: 34875, 4: 30574}[layers]
    assert {5: "3,487.5 M", 4: "3,057.4 M"}[layers] in CONFIG["deployment"] and "430.1 M" in CONFIG["deployment"]
    assert 2 * total / 16e9 == pytest.approx({5: 0.436, 4: 0.382}[layers], abs=0.001)  # of the chip, in bfloat16
    assert round((72 * LAYER + 2 * embedding) / 1e8) == 336  # the model whole: 33.6 B


def test_the_cell_is_where_the_issue_put_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("falcon_h1_34b_rerank", "rerank_pairs_closed", 1)
    assert len(bench["workloads"]) >= 11 and len(bench["configs"]) >= 9
    assert sum(c["config"] == "falcon_h1_34b_rerank" for c in bench["workloads"]) == 1  # one cell, no second
    on = {m["name"] for m in bench["per_layer"] if cell["name"] in m.get("workloads", ())}
    olmo = {m["name"] for m in bench["per_layer"] if "olmo_hybrid_rerank-bulk" in m.get("workloads", ())}
    assert on == (olmo - {"delta_handovers_per_row.bulk", "pallas_delta_pct.bulk"}) | {"ssd_handovers_per_row.bulk"}
    assert {"attn_masked_score_pct.bulk", "pallas_attention_pct.bulk", "step_roofline", "device_idle_pct.bulk"} <= on
    new = next(m for m in bench["per_layer"] if m["name"] == "ssd_handovers_per_row.bulk")
    assert bench["per_layer"][-1] is new  # appended, not put among the others
    assert (new["workloads"], new["source"], new["layer"], new["moves"], new["unit"], new["better"]) == (
        [cell["name"]], "program_counter", "kernels", "cand_per_s", "handovers/row", "lower")
    assert cell["name"] in next(m for m in bench["end_to_end"] if m["name"] == "cand_per_s")["workloads"]
    entry = next(c for c in bench["configs"] if c["name"] == "falcon_h1_34b_rerank")
    assert all(len(text) <= 200 for text in (cell["why"], entry["why"], entry["source"]))


def numpy_mixer(p, a, m):
    """The Mamba-2 mixer of the normed `a [n, L, H]` in float64 NumPy, a
    position at a time, a head at a time: written from the equations, from
    nothing of the reference's or the program's."""
    f = lambda x: np.asarray(x, np.float64)  # noqa: E731
    silu = lambda x: x / (1.0 + np.exp(-x))  # noqa: E731
    n, length, _ = a.shape
    heads, width, groups = p["A_log"].shape[0], m["ssm_head"], m["groups"]
    inner = heads * width
    state = (p["in"].shape[1] - 2 * inner - heads) // (2 * groups)
    projected = (f(a) * m["ssm_in_multiplier"]) @ f(p["in"])
    edges = np.cumsum([inner, inner, groups * state, groups * state])
    z, x, b, c, dt = (part * scale for part, scale in zip(np.split(projected, edges, axis=-1), m["ssm_multipliers"]))
    mixed = np.concatenate([x, b, c], axis=-1)
    taps = p["conv_w"].shape[1]
    padded = np.pad(mixed, ((0, 0), (taps - 1, 0), (0, 0)))
    mixed = silu(sum(padded[:, j:j + length] * f(p["conv_w"])[:, j] for j in range(taps)) + f(p["conv_b"]))
    x, b, c = np.split(mixed, [inner, inner + groups * state], axis=-1)
    dt = np.log1p(np.exp(dt + f(p["dt_bias"])))
    decay_rate, skip = -np.exp(f(p["A_log"])), f(p["D"])
    y = np.zeros((n, length, inner))
    for h in range(heads):
        g = h // (heads // groups)
        s = np.zeros((n, width, state))
        for t in range(length):
            x_t = x[:, t, h * width:(h + 1) * width]
            s = np.exp(dt[:, t, h] * decay_rate[h])[:, None, None] * s
            s = s + (dt[:, t, h][:, None] * x_t)[:, :, None] * b[:, t, g * state:(g + 1) * state][:, None, :]
            y[:, t, h * width:(h + 1) * width] = (
                np.einsum("nps,ns->np", s, c[:, t, g * state:(g + 1) * state]) + skip[h] * x_t)
    y = y * silu(z)
    for g in range(groups):
        part = y[..., g * inner // groups:(g + 1) * inner // groups]
        part /= np.sqrt((part * part).mean(-1, keepdims=True) + m["eps"])
    return (y * f(p["norm"])) @ f(p["out"]) * m["ssm_out_multiplier"]


TINY = dict(
    num_fields=21, vocab_size=500, embed_dim=32, intermediate_size=48, num_hidden_layers=2, num_attention_heads=6,
    num_key_value_heads=2, head_dim=8, rope_theta=1e4, layer_norm_eps=1e-5, mamba_d_ssm=32, mamba_n_heads=4,
    mamba_d_head=8, mamba_d_state=12, mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8, embedding_multiplier=5.6,
    attention_in_multiplier=0.8, attention_out_multiplier=0.5, key_multiplier=0.4, ssm_in_multiplier=0.6,
    ssm_out_multiplier=0.7, ssm_multipliers=(0.9, 0.8, 0.7, 1.2, 0.6), mlp_multipliers=(0.5, 0.3),
    compute_dtype="float32")
SIZES = dict(head=8, ssm_head=8, groups=2, theta=1e4, eps=1e-5,
             **{k: v for k, v in TINY.items() if k.endswith(("multiplier", "multipliers"))})


@pytest.fixture(scope="module")
def tiny():
    import jax

    from distributed_tf_serving_tpu.models import ModelConfig, build_model

    model = build_model("falcon_h1", ModelConfig(**TINY))
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    # matrices wide enough that gates, dt and the logit spread; D and the norms off 1
    rng = np.random.default_rng(1)
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w * 8.0 if w.ndim == 2 and path[-1].key not in ("conv_w", "embedding")
        else (w * (1.0 + 0.2 * rng.standard_normal(w.shape)).astype(np.float32) if path[-1].key in ("D", "norm") else w),
        params)
    batch = {"feat_ids": rng.integers(0, 500, size=(3, 21)).astype(np.int32),
             "feat_wts": rng.random((3, 21), dtype=np.float32)}
    return model, params, batch, load_module(os.path.join(HERE, "reference.py"), "ref_falcon")


def test_the_references_mixer_is_a_numpy_loop_of_the_recurrence(tiny):
    import jax

    _model, params, _batch, reference = tiny
    a = np.random.default_rng(2).standard_normal((2, 21, 32)).astype(np.float32)
    p, m = params["layers"][1]["ssm"], dict(reference.PUBLISHED, **SIZES)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda p, a: reference.ssm(p, a, m))(p, a))
    want = numpy_mixer(p, a, m)
    assert want.std() > 0.1
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_reference_matches_the_programs_family_at_tiny_widths(tiny):
    import jax

    model, params, batch, reference = tiny
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.forward(p, b, **SIZES))(params, batch))
        out, stats = jax.jit(model.apply_stats)(params, batch)
    assert want.std() > 0.02 and np.max(np.abs(want - np.asarray(out["prediction_node"]))) < 2e-6
    named = dict(zip(model.step_stats, stats.tolist()))
    assert (named["ssd.rows"], named["ssd.handovers"], named["ssd.positions"]) == (3, 3 * 2 * 3, 3 * 2 * 21)
    # the reference's defaults are the published sizes and multipliers the configuration serves
    assert reference.PUBLISHED == {
        "head": MODEL["head_dim"], "ssm_head": MODEL["mamba_d_head"], "groups": MODEL["mamba_n_groups"],
        "theta": MODEL["rope_theta"], "eps": MODEL["layer_norm_eps"],
        **{k: tuple(v) if isinstance(v, list) else v for k, v in MODEL.items()
           if k.endswith(("multiplier", "multipliers"))}}


def test_step_cost_counts_the_served_step_by_hand():
    H, L, layers = 5120, 2048, MODEL["num_hidden_layers"]
    kv, attention = 2 * H * 512, 2 * H * 2560 + 2 * H * 512
    ssm_in, ssm_out, mlp = H * 9248, 4096 * H, 3 * H * 21504
    pair = 2 * 20 * (128 + 128)  # q k' and p v over 128, 20 query heads
    pairs = L * (L + 1) // 2
    conv = (2 * 4 + 1) * 5120  # four taps' multiply-adds and the bias, 5,120 channels
    state = 32 * 128 * 256  # entries of a row's state: 4.19 MB in float32
    assert 4 * state == 4_194_304 and 2 * 2 * state == 4_194_304  # and 4.19 M operations a position
    whole = L * (2 * (attention + ssm_in + ssm_out + mlp) + conv + 4 * state) + pairs * pair
    last = (L * (2 * (kv + ssm_in) + conv + 2 * state)  # keys, values, the input projection, the convolution, the walk
            + 2 * (attention - kv + ssm_out + mlp) + 2 * state + L * pair)  # the rest at one position
    row = (layers - 1) * whole + last + 2 * H
    flops, moved = COST.step_cost(MODEL, 4, 1)
    assert flops == 4 * row and flops == pytest.approx({5: 29.55e12, 4: 22.2e12}[layers], rel=0.01)
    small = 5120 * 5 + 3 * 32 + 4096
    weights = layers * (attention + ssm_in + ssm_out + small + mlp)
    handovers = layers * 16 * 2 * 4 * state  # the state in and out once a chunk, float32
    assert moved == 4 * (L * (2 * H + 7) + 4 + handovers) + 2 * weights
    assert weights == layers * (LAYER - 2 * H) and COST.handovers(MODEL) == 16
    assert peaks.least_seconds(flops, moved, "TPU v5 lite")[1] == "compute"
    assert COST.step_cost(MODEL, 8, 2)[0] == 2 * flops
    ssd_flops, ssd_bytes = COST.ssd_cost(MODEL, 4)
    assert ssd_flops == 4 * L * 4 * state and ssd_bytes == 4 * (L * 4 * (4096 + 5120 + 32) + 16 * 2 * 4 * state)
    # position by position the state would cross memory 2,048 times a row, not 16: the recurrence is bound by its bytes
    assert peaks.least_seconds(ssd_flops, 4 * L * 2 * 4 * state, "TPU v5 lite")[1] == "memory"
    attn_flops, attn_bytes = COST.full_attention_cost(MODEL, 4)
    assert attn_flops == 4 * (L * 2 * attention + pairs * pair) and attn_bytes == 2 * attention + 4 * L * 8 * H
    assert COST.conv_cost(MODEL, 4) == (4 * L * conv, 4 * L * 8 * 5120)
    # one more layer is one more whole layer
    assert COST.step_cost({**MODEL, "num_hidden_layers": layers + 1}, 4, 1)[0] - flops == 4 * whole


@pytest.mark.parametrize("counts,want", [
    ((80 * 37, 37), 80.0), ((64 * 5, 5), 64.0), ((0, 0), None), ((123, 0), None)],
    ids=["five layers", "four layers", "a program without the counters", "no rows"])
def test_the_new_reader(counts, want):
    import sys

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "layers"))
    try:
        read = load_module(os.path.join(ROOT, "benchmark", "layers", "ssd_handovers_per_row.py"), "reader_ssd").read
    finally:
        sys.path.pop(0)
    names = ("ssd.handovers", "ssd.rows")
    ctx = {"phases": {n: {"count": c, "total_ms": 0.0} for n, c in zip(names, counts) if c}}
    assert read(ctx) == want
    assert read({"phases": {}, "batcher": {}, "runtime": {}, "gen": {}, "trace": {}, "model": {}, "notes": {}}) is None
