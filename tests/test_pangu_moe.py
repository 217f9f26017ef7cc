"""The pangu_moe family (openPangu-Ultra-MoE-718B as a pointwise sequence
ranker: latent attention, sandwich norms, a routed layer told which experts it
holds) at tiny widths on the CPU: against the benchmark's plain reference
through `model.apply` and down the served path, the shares of a layer against
the uncut layer, the last-position skip, the grouped product under any
routing, what the benchmark's tolerance catches, the step's counters and how
they reach `/monitoring`, and the shapes at the published cut."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tf_serving_tpu import native
from distributed_tf_serving_tpu.models import ModelConfig, build_model, pangu_moe, sequence
from distributed_tf_serving_tpu.ops.transfer import (
    combined_layout, describe_layout, pack_host_combined, transfer_spec,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "pangu_ultra_moe_rerank")
LENGTH, THETA = 24, 25600000.0
# The reference's keyword arguments at the tiny widths below.
SIZES = {"top_k": 4, "scaling": 2.5, "nope": 16, "rope": 8, "v_head": 16, "theta": THETA}


def tiny_config(**overrides) -> ModelConfig:
    return ModelConfig(**{
        "name": "M", "num_fields": LENGTH, "vocab_size": 1000, "embed_dim": 64, "intermediate_size": 96,
        "num_hidden_layers": 3, "first_k_dense_replace": 1, "num_attention_heads": 2,
        "num_attention_heads_published": 8, "q_lora_rank": 48, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": THETA,
        "moe_intermediate_size": 32, "n_routed_experts": 16, "num_experts_per_tok": 4,
        "routed_scaling_factor": 2.5, "experts_held": 4, "first_expert_held": 4,
        "compute_dtype": "float32", **overrides,
    })


def rows(n: int, config: ModelConfig, seed: int = 3, folded: bool = True) -> dict:
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 1 << 40, size=(n, config.num_fields), dtype=np.int64)
    return {
        "feat_ids": (ids % config.vocab_size).astype(np.int32) if folded else ids,
        "feat_wts": rng.random((n, config.num_fields), dtype=np.float32),
    }


def unit_gain(params, config: ModelConfig, seed: int = 0):
    """The tree with its matrices scaled so that a product keeps a unit input
    at the size it has at the published width of 7680 (router logits and a
    score logit of standard deviation 1.75, not 0.16), and every norm weight
    drawn around 1, so that a norm left out or misplaced shows."""
    gain = (7680 / config.embed_dim) ** 0.5
    rng = np.random.default_rng(seed)

    def scale(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if name == "embedding":
            return leaf
        if leaf.ndim == 1 and name != "score":
            return (leaf * (1.0 + 0.2 * rng.standard_normal(leaf.shape))).astype(leaf.dtype)
        return leaf * gain

    return jax.tree_util.tree_map_with_path(scale, params)


def load(name: str):
    spec = importlib.util.spec_from_file_location(f"pangu_{name}", os.path.join(CONFIG_DIR, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return load("reference")


@pytest.fixture(scope="module")
def tolerance():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        return float(json.load(f)["tolerance"])


def reference_scores(reference, params, batch, first=4):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(
            lambda p, b: reference.forward(p, b, first=first, **SIZES))(params, batch))


# ------------------------------------------------- the family and the reference


@pytest.mark.parametrize("layers,dense,length", [(3, 1, 24), (5, 1, 21), (4, 2, 24), (2, 0, 9)])
def test_float32_logits_match_the_plain_reference(reference, layers, dense, length):
    """Through `model.apply`; the reference computes every layer at every
    position, the family the last layer's queries and FFN at the last alone:
    the last-position skip is exact, also at a length that is no multiple of
    anything."""
    config = tiny_config(num_hidden_layers=layers, first_k_dense_replace=dense, num_fields=length)
    model = build_model("pangu_moe", config)
    params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(7)), config)
    batch = rows(5, config)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.logits(p, b, first=4, **SIZES))(params, batch))
        got = np.asarray(jax.jit(model.apply)(params, batch)["logits"])
    assert want.shape == got.shape == (5,) and want.std() > 0.3
    assert np.max(np.abs(want - got)) < 2e-5


def test_the_last_layers_queries_alone_are_the_whole_layers_last_position(reference):
    config = tiny_config()
    s = pangu_moe._sizes(config)
    model = build_model("pangu_moe", config)
    p = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(2)), config)["layers"][1]["attn"]
    a = jnp.asarray(np.random.default_rng(0).standard_normal((3, LENGTH, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = pangu_moe.latent_attention(p, a, s, jnp.float32, 1e-5, THETA)
        last = pangu_moe.latent_attention(p, a, s, jnp.float32, 1e-5, THETA, last_only=True)
        want = reference.mla(p, a, 16, 8, 16, THETA)
    assert last.shape == (3, 1, 64)
    np.testing.assert_allclose(np.asarray(last), np.asarray(whole[:, -1:]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_rotary_turn_is_a_complex_multiplication():
    cos, sin = pangu_moe.rope_table(10, 8, THETA)
    x = np.random.default_rng(1).standard_normal((2, 10, 3, 8)).astype(np.float32)
    got = np.asarray(pangu_moe.rotate(jnp.asarray(x), cos[:, None, :], sin[:, None, :]))
    t = np.arange(10)[:, None] * THETA ** (-np.arange(4) * 2.0 / 8)
    turned = (x[..., :4] + 1j * x[..., 4:]) * np.exp(1j * t)[None, :, None, :]
    np.testing.assert_allclose(got, np.concatenate([turned.real, turned.imag], -1), rtol=1e-5, atol=1e-6)
    assert np.allclose(got[:, 0], x[:, 0])  # position 0 is not turned


# ----------------------------------------------------- the share and the model


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(reference):
    """64 experts over 32 chips (2 a chip), 8 heads over 4: the parts that all
    the shares give of one routed layer, the shared expert, the norms and the
    residual counted once, are the uncut reference's layer."""
    uncut = tiny_config(n_routed_experts=64, experts_held=64, first_expert_held=0,
                        num_attention_heads=8, num_experts_per_tok=8)
    layer = unit_gain(jax.jit(build_model("pangu_moe", uncut).init)(jax.random.PRNGKey(4)), uncut)["layers"][1]
    x = jnp.asarray(np.random.default_rng(5).standard_normal((2, LENGTH, 64)), jnp.float32)
    sizes = dict(SIZES, top_k=8)
    f32, eps = jnp.float32, 1e-5
    with jax.default_matmul_precision("highest"):
        want = reference.layer_forward(layer, x, first=0, **sizes)
        a = pangu_moe._rms_norm(layer["in_norm"], x, eps)
        attended = 0.0
        for share in range(4):  # heads 2 * share, 2 * share + 1
            config = dataclasses.replace(uncut, num_attention_heads=2)
            cut = lambda w, width: w.reshape(w.shape[0], 8, width)[:, 2 * share:2 * share + 2].reshape(w.shape[0], -1)  # noqa: E731
            p = dict(layer["attn"], q_b=cut(layer["attn"]["q_b"], 24), kv_b=cut(layer["attn"]["kv_b"], 32),
                     o=layer["attn"]["o"].reshape(8, 16, 64)[2 * share:2 * share + 2].reshape(32, 64))
            attended = attended + pangu_moe.latent_attention(p, a, pangu_moe._sizes(config), f32, eps, THETA)
        h = x + pangu_moe._rms_norm(layer["post_attn_norm"], attended, eps)
        tokens = pangu_moe._rms_norm(layer["pre_mlp_norm"], h, eps).reshape(-1, 64)
        chosen, gates, _ = pangu_moe.route(layer["router"], tokens, 8, 2.5)
        ffn, given = pangu_moe._gated_mlp(layer["shared"], tokens, f32), 0
        for share in range(32):  # experts 2 * share, 2 * share + 1
            held = {k: w[2 * share:2 * share + 2] for k, w in layer["experts"].items()}
            part, loads, _ = pangu_moe.held_experts(held, tokens, chosen, gates, 2 * share, f32, block=16)
            ffn, given = ffn + part, given + int(loads.sum())
        got = h + pangu_moe._rms_norm(layer["post_mlp_norm"], ffn.reshape(x.shape), eps)
    assert given == tokens.shape[0] * 8  # every choice of every token fell on exactly one share
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=2e-5)
    # and one share alone is not the layer
    assert float(jnp.max(jnp.abs(h + pangu_moe._rms_norm(layer["post_mlp_norm"], part.reshape(x.shape), eps) - want))) > 0.1


@pytest.mark.parametrize("tokens,block,routing", [
    (40, 16, "uniform"), (40, 16, "all_to_one"), (37, 8, "uniform"), (5, 256, "uniform"), (64, 16, "none_here")])
def test_grouped_product_is_the_masked_sum_under_any_routing(tokens, block, routing):
    """No token is dropped: also when every token picks the same held expert
    (three blocks and a padded fourth), when none picks a held one, and when a
    block is larger than the batch."""
    rng = np.random.default_rng(tokens)
    held, first, k, hidden, width = 3, 5, 2, 16, 8
    p = {"gate": rng.standard_normal((held, hidden, width)), "up": rng.standard_normal((held, hidden, width)),
         "down": rng.standard_normal((held, width, hidden))}
    p = {name: jnp.asarray(w, jnp.float32) for name, w in p.items()}
    x = rng.standard_normal((tokens, hidden)).astype(np.float32)
    if routing == "uniform":
        chosen = np.stack([rng.permutation(12)[:k] for _ in range(tokens)])
    elif routing == "all_to_one":
        chosen = np.tile([6, 11], (tokens, 1))
    else:
        chosen = np.tile([0, 9], (tokens, 1))
    gates = rng.random((tokens, k)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got, loads, ran = jax.jit(lambda *a: pangu_moe.held_experts(p, *a, first, jnp.float32, block=block))(
            jnp.asarray(x), jnp.asarray(chosen.astype(np.int32)), jnp.asarray(gates))
    want = np.zeros((tokens, hidden))
    for e in range(held):
        w = {name: np.asarray(v[e], np.float64) for name, v in p.items()}
        g = (x @ w["gate"])
        y = (g / (1 + np.exp(-g)) * (x @ w["up"])) @ w["down"]
        want += ((chosen == first + e) * gates).sum(1)[:, None] * y
    assert loads.tolist() == [(chosen == first + e).sum() for e in range(held)]
    assert int(ran) == sum(-(-int(load) // block) * block for load in loads)  # the blocks the loops ran, padding and all
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_a_row_of_zero_weights_is_left_out_of_the_experts_and_the_counters_exactly():
    """A padded row (every weight zero) is zero throughout: its tokens, which
    the router would hand to experts 0 .. k - 1 (every score a half), take no
    block and no count, and the other rows' scores are theirs to the bit."""
    config = tiny_config(first_expert_held=0)
    model = build_model("pangu_moe", config)
    params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(4)), config)
    batch = rows(3, config)
    padded = {k: np.concatenate([v, np.zeros_like(v[:1])]) for k, v in batch.items()}
    step = jax.jit(model.apply_stats)
    (out, stats), (out_padded, stats_padded) = step(params, batch), step(params, padded)
    np.testing.assert_array_equal(np.asarray(out_padded["logits"][:3]), np.asarray(out["logits"]))
    assert stats_padded.tolist() == stats.tolist() and float(out_padded["logits"][3]) == 0.0


def test_the_counters_follow_the_blocks_the_loops_ran(monkeypatch):
    """`assignments_here` and the busiest load are counted where a tile
    gathers its rows: the loop over the tiles cut to its first tile reads
    what that tile took (16 tokens of the first held expert), not what the
    router sent."""
    rng = np.random.default_rng(1)
    p = {name: jnp.asarray(rng.standard_normal(shape), jnp.float32)
         for name, shape in (("gate", (2, 16, 8)), ("up", (2, 16, 8)), ("down", (2, 8, 16)))}
    x = jnp.asarray(rng.standard_normal((40, 16)), jnp.float32)
    chosen = jnp.asarray(np.tile([1, 0], (40, 1)).astype(np.int32))  # every token to both held experts
    gates = jnp.ones((40, 2), jnp.float32)
    run = lambda: [np.asarray(c).tolist() for c in pangu_moe.held_experts(p, x, chosen, gates, 0, jnp.float32, block=16)[1:]]  # noqa: E731
    assert run() == [[40, 40], 96]
    loop = jax.lax.fori_loop
    monkeypatch.setattr(jax.lax, "fori_loop", lambda lo, hi, body, init: loop(lo, jnp.minimum(hi, 1), body, init))
    assert run() == [[16, 0], 16]


# ------------------------------------------------------------------ counters


def test_the_steps_counters_are_a_numpy_count_on_the_same_router_scores():
    config = tiny_config()
    s = pangu_moe._sizes(config)
    model = build_model("pangu_moe", config)
    params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(9)), config)
    layer = params["layers"][2]
    a = jnp.asarray(np.random.default_rng(2).standard_normal((4, LENGTH, 64)), jnp.float32)
    _, counts = jax.jit(lambda l, x: pangu_moe.routed_ffn(l, x, s, 2.5, jnp.float32))(layer, a)
    _, _, scores = pangu_moe.route(layer["router"], a.reshape(-1, 64), 4, 2.5)
    top = np.argsort(-np.asarray(scores), axis=1)[:, :4]
    loads = [(top == e).sum() for e in range(4, 8)]
    assert counts.tolist() == [4 * LENGTH, sum(loads), max(loads), sum(-(-n // 256) * 256 for n in loads),
                               sum(n > 0 for n in loads)] and sum(loads) > 0
    # the whole step: a routed layer before the last at all positions, the last at one
    _, stats = jax.jit(model.apply_stats)(params, rows(4, config))
    assert model.step_stats == pangu_moe.STEP_STATS and int(stats[0]) == 4 * LENGTH + 4
    assert 0 < int(stats[2]) <= int(stats[1]) <= 4 * int(stats[0])


# ---------------------------------------------------------------- precision


@pytest.fixture(scope="module")
def served_precision(reference):
    """bfloat16 weights and compute as served, rows long enough for the
    attention to mix, and the float32 reference's scores."""
    config = tiny_config(num_fields=96, num_hidden_layers=4, compute_dtype="bfloat16", param_dtype="bfloat16")
    model = build_model("pangu_moe", config)
    params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(5)), config)
    batch = rows(8, config, seed=11)
    return model, params, batch, reference_scores(reference, params, batch)


def _worst(model, params, batch, want) -> float:
    got = np.asarray(jax.jit(lambda p, b: model.apply(p, b))(params, batch)["prediction_node"])
    return float(np.max(np.abs(got.astype(np.float64) - want)))


def test_three_piece_scores_within_the_benchmark_tolerance(served_precision, tolerance):
    model, params, batch, want = served_precision
    assert want.std() > 0.1  # scores that spread, or the comparison compares nothing
    assert pangu_moe.OPERAND_PIECES == 3 and _worst(model, params, batch, want) < tolerance / 3


def _one_piece(monkeypatch):
    """The nearest precision below the stated one: every activation rounded
    to bfloat16 where it enters a product."""
    monkeypatch.setattr(pangu_moe, "OPERAND_PIECES", 1)


def _an_expert_dropped(monkeypatch):
    """The last held expert's part left out of the routed sum."""
    whole = pangu_moe.held_experts

    def without_the_last(p, *args, **kwargs):
        return whole({name: w[:-1] for name, w in p.items()}, *args, **kwargs)

    monkeypatch.setattr(pangu_moe, "held_experts", without_the_last)


def _top_7(monkeypatch):
    """One choice fewer than the configuration states."""
    route = pangu_moe.route
    monkeypatch.setattr(pangu_moe, "route", lambda router, x, k, scaling: route(router, x, k - 1, scaling))


def _no_post_norms(monkeypatch):
    """The norm AFTER each sub-layer left out: of a layer's six norms, in the
    order the step calls them (in, kv latent, q latent, post attention, pre
    MLP, post MLP), the fourth and the sixth."""
    norm, calls = pangu_moe._rms_norm, []

    def but_after_a_sub_layer(w, x, eps):
        calls.append(None)
        return x if (len(calls) - 1) % 6 in (3, 5) else norm(w, x, eps)

    monkeypatch.setattr(pangu_moe, "_rms_norm", but_after_a_sub_layer)


def _rotary_left_out(monkeypatch):
    monkeypatch.setattr(pangu_moe, "rotate", lambda x, cos, sin: x)


@pytest.mark.parametrize("plant,factor", [
    (_one_piece, 3), (_an_expert_dropped, 10), (_top_7, 10), (_no_post_norms, 10), (_rotary_left_out, 10)],
    ids=["one-piece operands", "an expert dropped", "top-7", "no post norms", "rotary left out"])
def test_what_the_tolerance_refuses(served_precision, tolerance, monkeypatch, plant, factor):
    """Each fault is planted here, not in the program, and misses the
    float32 reference by more than `factor` times the benchmark's limit."""
    model, params, batch, want = served_precision
    plant(monkeypatch)
    assert _worst(model, params, batch, want) > factor * tolerance


# ------------------------------------------------------------ the served path


@pytest.fixture(scope="module")
def served():
    from distributed_tf_serving_tpu.serving.server import build_stack
    from distributed_tf_serving_tpu.utils.config import load_config

    cfgs = load_config(os.path.join(ROOT, "configs", "pangu_moe_small.toml"))
    config = dataclasses.replace(cfgs["model"], name="M")
    cfg = dataclasses.replace(cfgs["server"], model_name="M", warmup=False)
    _registry, batcher, impl, servable, _mesh, _watcher = build_stack(cfg, model_config=config)
    yield batcher, impl, servable
    batcher.stop()


def _moe_phases() -> dict:
    from distributed_tf_serving_tpu.utils.tracing import request_trace

    return {k: v["count"] for k, v in request_trace.snapshot().items() if k.startswith("moe.")}


def test_a_request_through_the_batchers_entry_scores_like_the_reference(served, reference, tolerance):
    """configs/pangu_moe_small.toml down the served path: 3 rows pad to the
    bucket of 4; ids travel as u24 and weights as float32; the counters of
    the step come back with the scores and are recorded by count, the padded
    row's tokens in none of them."""
    batcher, _impl, servable = served
    config = servable.model.config
    arrays = rows(3, config, folded=False)
    before = _moe_phases()
    got = batcher.submit(servable, arrays).result(timeout=300)
    assert set(got) == {"prediction_node", "logits"} and type(got["prediction_node"]) is np.ndarray
    batch = dict(arrays, feat_ids=(arrays["feat_ids"] % config.vocab_size).astype(np.int32))
    sizes = {"first": config.first_expert_held, "top_k": config.num_experts_per_tok,
             "scaling": config.routed_scaling_factor, "nope": config.qk_nope_head_dim,
             "rope": config.qk_rope_head_dim, "v_head": config.v_head_dim, "theta": config.rope_theta}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.forward(p, b, **sizes))(servable.params, batch))
    assert got["prediction_node"].shape == (3,) and batcher.compress_transfer
    assert np.max(np.abs(got["prediction_node"] - want)) < tolerance
    after = _moe_phases()
    moe_layers = servable.model.layer_plan.count("moe")
    assert after["moe.tokens"] - before.get("moe.tokens", 0) == 3 * ((moe_layers - 1) * config.num_fields + 1)
    _, alone = jax.jit(servable.model.apply_stats)(servable.params, batch)  # the 3 rows with no padding
    assert [after[name] - before.get(name, 0) for name in pangu_moe.STEP_STATS] == alone.tolist()
    assert alone[2] > 0


def test_predict_answers_a_row_of_tokens_and_nothing_else(served):
    from distributed_tf_serving_tpu import codec
    from distributed_tf_serving_tpu.client import build_predict_request

    batcher, impl, servable = served
    arrays = rows(2, servable.model.config, seed=9, folded=False)
    response = impl.predict(build_predict_request(arrays, "M"))
    scores = codec.to_ndarray(response.outputs["prediction_node"])
    direct = batcher.submit(servable, arrays).result(timeout=300)["prediction_node"]
    assert sorted(response.outputs) == ["logits", "prediction_node"]  # the counters are no output
    assert scores.shape == (2,) and np.all((scores > 0) & (scores < 1))
    np.testing.assert_array_equal(scores, direct)


def test_a_filtered_request_still_brings_its_counters_back(served):
    batcher, _impl, servable = served
    before = _moe_phases().get("moe.tokens", 0)
    got = batcher.submit(
        servable, rows(2, servable.model.config, seed=4, folded=False), output_keys=("logits",)
    ).result(timeout=300)
    assert set(got) == {"logits"} and _moe_phases()["moe.tokens"] > before


def test_runtime_block_reports_the_expert_plan(served):
    batcher, impl, servable = served
    batcher.submit(servable, rows(2, servable.model.config, folded=False)).result(timeout=300)
    startup = impl.runtime_stats()["startup"]
    assert startup["layer_plan"] == {"M:1": {"dense": 1, "moe": 2}}
    assert startup["expert_plan"] == {"M:1": {
        "published": 32, "held": 4, "first": 8, "top_k": 4, "heads_published": 8, "heads_held": 2,
        "chips_sharing_layer": 8}}
    assert startup["assembler"] == {"M:1": "native"} or not native.available()
    assert "feat_ids int32/24b" in startup["upload_format"]["M:1"]


def test_shadow_verification_counts_a_batch_once(served):
    """With the integrity plane's shadow execution on, the step runs twice
    over a batch and its counters are recorded once; the two executions'
    outputs compare without them."""
    from distributed_tf_serving_tpu.utils.config import IntegrityConfig

    batcher, _impl, servable = served
    arrays = rows(2, servable.model.config, seed=6, folded=False)
    batcher.submit(servable, arrays).result(timeout=300)
    once = _moe_phases()
    plain = batcher.submit(servable, arrays).result(timeout=300)
    twice = _moe_phases()
    plane = IntegrityConfig(enabled=True, shadow_fraction=1.0).build()
    batcher.integrity = plane
    try:
        shadowed = batcher.submit(servable, arrays).result(timeout=300)
    finally:
        batcher.integrity = None
    thrice = _moe_phases()
    shadow = plane.snapshot()["shadow"]
    assert shadow["batches"] == 1 and shadow["mismatches"] == 0
    np.testing.assert_array_equal(shadowed["prediction_node"], plain["prediction_node"])
    assert all(twice[k] - once[k] == thrice[k] - twice[k] > 0 for k in pangu_moe.STEP_STATS)


def test_an_entry_without_statistics_is_built_and_answers_as_before():
    """A CTR family: no expert plan, no `apply_stats`, no counters among its
    entry's outputs or in the phases."""
    from distributed_tf_serving_tpu.models import Servable, ctr_signatures
    from distributed_tf_serving_tpu.serving import batcher as batcher_module

    config = ModelConfig(num_fields=5, vocab_size=64, embed_dim=4, mlp_dims=(8,))
    model = build_model("dcn_v2", config)
    servable = Servable("D", 1, model, model.init(jax.random.PRNGKey(0)), ctr_signatures(5))
    assert servable.expert_plan is None and model.apply_stats is None and model.step_stats == ()
    batcher = batcher_module.DynamicBatcher(buckets=(4,), max_wait_us=0).start()
    try:
        fn, _spec, combined = batcher.jit_entry(servable)
        arrays, spec = rows(4, config), transfer_spec(model)
        out = fn(servable.params, pack_host_combined(arrays, spec), combined_layout(arrays, spec))
        assert combined and set(out) == {"prediction_node", "logits"}
        before = _moe_phases()
        got = batcher.submit(servable, rows(3, config, folded=False)).result(timeout=120)
        assert got["prediction_node"].shape == (3,) and _moe_phases() == before
    finally:
        batcher.stop()


# ------------------------------------------------------- the published shapes


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        shape = json.load(f)["toml"]["model"]
    return ModelConfig(**{**shape, "mlp_dims": tuple(shape["mlp_dims"])})


def test_plan_and_parameter_count_at_the_published_cut(published):
    """By `jax.eval_shape`: nothing of the 2.59 B parameters is made."""
    model = build_model("pangu_moe", published)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    size = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))  # noqa: E731
    assert model.layer_plan == ("dense", "moe", "moe", "moe", "moe")
    assert dict(model.expert_plan) == {
        "published": 256, "held": 8, "first": 0, "top_k": 8, "heads_published": 128, "heads_held": 32,
        "chips_sharing_layer": 32}
    assert round(size(shapes["layers"][0]["attn"]) / 1e5) == 613
    assert round(size(shapes["layers"][0]) / 1e5) == 4860 and round(size(shapes["layers"][1]) / 1e6) == 488
    assert shapes["embedding"].shape == (19200, 7680) and shapes["layers"][1]["router"].shape == (7680, 256)
    assert shapes["layers"][1]["experts"]["gate"].shape == (8, 7680, 2048)
    assert round(size(shapes) / 1e7) == 259 and {x.dtype for x in jax.tree.leaves(shapes)} == {jnp.dtype("bfloat16")}


@pytest.mark.parametrize("overrides,match", [
    ({"qk_rope_head_dim": 7}, "qk_rope_head_dim"),
    ({"num_experts_per_tok": 17}, "num_experts_per_tok"),
    ({"experts_held": 5}, "experts_held"),
    ({"first_expert_held": 14}, "experts_held"),
    ({"num_attention_heads_published": 5}, "num_attention_heads"),
    ({"first_k_dense_replace": 4}, "first_k_dense_replace"),
    ({"num_experts_per_tok": 0}, "num_experts_per_tok"),
])
def test_a_share_the_layer_cannot_be_cut_into_is_refused_at_build(overrides, match):
    with pytest.raises(ValueError, match=match):
        build_model("pangu_moe", tiny_config(**overrides))


def test_toml_reads_the_published_keys(tmp_path):
    from distributed_tf_serving_tpu.utils.config import load_config

    cfgs = load_config(os.path.join(ROOT, "configs", "pangu_moe_small.toml"))
    model = build_model(cfgs["server"].model_kind, cfgs["model"])
    assert model.kind == "pangu_moe" and not model.takes_dense and not model.wts_in_compute_dtype
    assert cfgs["server"].num_fields == cfgs["model"].num_fields
    assert len(model.layer_plan) == cfgs["model"].num_hidden_layers
    (tmp_path / "s.toml").write_text('[model]\nexpert_held = 8\n')
    with pytest.raises(ValueError, match="unknown ModelConfig keys"):
        load_config(str(tmp_path / "s.toml"))


@pytest.mark.skipif(not native.ensure(), reason="native hostops unavailable")
@pytest.mark.parametrize("sizes,bucket", [((2, 2, 2, 2), 8), ((2, 1), 4)])
def test_u24_ids_of_the_sliced_vocabulary_assemble_bit_for_bit(published, sizes, bucket):
    """The cell's layout through native assemble_batch: [n, 1024] int64 ids
    folded by the chip's slice of the vocabulary (19,200) and float32 weights
    as they are, against fold -> pad -> pack."""
    spec = transfer_spec(build_model("pangu_moe", published))
    assert spec == {"feat_ids": "u24"}
    parts = [rows(n, published, seed=20 + i, folded=False) for i, n in enumerate(sizes)]
    padded = {
        "feat_ids": np.zeros((bucket, 1024), np.int32), "feat_wts": np.zeros((bucket, 1024), np.float32)}
    at = 0
    for part in parts:
        n = part["feat_ids"].shape[0]
        padded["feat_ids"][at:at + n] = part["feat_ids"] % published.vocab_size
        padded["feat_wts"][at:at + n] = part["feat_wts"]
        at += n
    layout = combined_layout(padded, spec)
    assert "feat_ids int32/24b" in describe_layout(layout) and "feat_wts float32/32b" in describe_layout(layout)
    got, _ns = native.assemble_batch(
        layout, {k: [p[k] for p in parts] for k in padded}, {"feat_ids": published.vocab_size})
    np.testing.assert_array_equal(got, pack_host_combined(padded, spec))


# ------------------------------------------------ what both families share


@pytest.mark.parametrize("queries,keys,window,block", [
    (24, 24, None, 8), (1, 24, None, 8), (21, 21, 5, 8), (1, 21, 5, 8), (7, 7, None, 512)])
def test_blocked_causal_softmax_is_the_dense_one(queries, keys, window, block):
    """`sequence.query_blocks` and `causal_softmax` together against one
    dense masked softmax: every query's reach is inside its block's keys."""
    scores = np.random.default_rng(queries + keys).standard_normal((2, queries, keys)).astype(np.float32)
    q_pos = keys - queries + np.arange(queries)[:, None]
    k_pos = np.arange(keys)[None, :]
    seen = (k_pos <= q_pos) & ((q_pos - k_pos < window) if window else True)
    e = np.where(seen, np.exp(scores - scores.max(-1, keepdims=True)), 0.0)
    want = e / e.sum(-1, keepdims=True)
    got = np.zeros_like(want)
    covered = []
    for start, stop, first, last in sequence.query_blocks(queries, keys, window, block):
        covered += list(range(start, stop))
        got[:, start:stop, first:last] = np.asarray(sequence.causal_softmax(
            jnp.asarray(scores[:, start:stop, first:last]), keys - queries + start - first, window))
    assert covered == list(range(queries))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


def test_pieces_sum_to_the_operand_and_last_position_cuts_every_array():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 33)), jnp.float32)
    one, two = sequence.pieces(x, jnp.bfloat16, 1), sequence.pieces(x, jnp.bfloat16, 2)
    assert len(one) == 1 and len(two) == 2 and all(p.dtype == jnp.bfloat16 for p in two)
    assert float(jnp.max(jnp.abs(sum(p.astype(jnp.float32) for p in two) - x))) < 2e-5
    assert float(jnp.max(jnp.abs(one[0].astype(jnp.float32) - x))) > 1e-3
    assert [p.dtype for p in sequence.pieces(x, jnp.float32)] == [jnp.float32]
    a, b = sequence.last_position(jnp.zeros((2, 5, 3)), jnp.ones((2, 5)))
    assert a.shape == (2, 1, 3) and b.shape == (2, 1) and sequence.last_position(x).shape == (4, 1)
