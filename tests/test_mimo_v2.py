"""The mimo_v2 family (MiMo-V2.5 as a pointwise sequence ranker: window layers
with a learned attention sink beside full layers, key-value heads and rotary
base by layer kind, keys wider than values, a partial rotary turn, norms
before the sub-layers, a routed layer with no shared expert) at tiny widths on
the CPU: against the benchmark's plain reference through `model.apply` and
down the served path, the sink in the shared softmax, blocks and kernel
(interpreted) against a direct softmax over `[scores, b_h]`, the 32 shares of
a routed layer against the uncut layer, the last-position cut, what the
benchmark's tolerance catches, the step's counters (the sink's mass against
the reference's own) and how they reach `/monitoring`, the shapes at the
published cut, and that the four older families' steps lower to the text
they lowered to before the sink."""

import dataclasses
import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tf_serving_tpu import native
from distributed_tf_serving_tpu.models import ModelConfig, build_model, mimo_v2, routed, sequence
from distributed_tf_serving_tpu.ops import attention_kernel
from distributed_tf_serving_tpu.utils.config import load_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "mimo_v2_5_rerank")
LENGTH, WINDOW, HEAD, V_HEAD, ROTARY = 44, 8, 24, 16, 8  # ROTARY = int(24 * 0.334)
PLAN = (0, 1, 1, 1, 1, 0, 1)


def tiny_config(**overrides) -> ModelConfig:
    return ModelConfig(**{
        "name": "M", "num_fields": LENGTH, "vocab_size": 1000, "embed_dim": 64, "intermediate_size": 96,
        "num_hidden_layers": 7, "hybrid_layer_pattern": PLAN, "moe_layer_freq": (0, 1, 1, 1, 1, 1, 1),
        "sliding_window": WINDOW, "num_attention_heads": 8, "num_key_value_heads": 2, "swa_num_key_value_heads": 4,
        "head_dim": HEAD, "v_head_dim": V_HEAD, "partial_rotary_factor": 0.334, "rope_theta": 1e7,
        "swa_rope_theta": 1e4, "attention_value_scale": 0.707, "moe_intermediate_size": 32, "n_routed_experts": 16,
        "num_experts_per_tok": 4, "routed_scaling_factor": 1.0, "experts_held": 4, "first_expert_held": 4,
        "compute_dtype": "float32", **overrides,
    })


def sizes_of(config: ModelConfig) -> dict:
    """reference.py's keyword arguments for `config`."""
    head = config.head_dim
    return {
        "hybrid_layer_pattern": tuple(int(kind == "window") for kind, _ in mimo_v2.layer_plan(config)),
        "window": config.sliding_window, "head": head, "v_head": config.v_head_dim,
        "rotary": int(head * config.partial_rotary_factor), "theta_full": config.rope_theta,
        "theta_window": config.swa_rope_theta, "value_scale": config.attention_value_scale,
        "top_k": config.num_experts_per_tok, "scaling": config.routed_scaling_factor,
        "first": config.first_expert_held, "eps": config.layer_norm_eps,
    }


def rows(n: int, config: ModelConfig, seed: int = 3, folded: bool = True) -> dict:
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 1 << 40, size=(n, config.num_fields), dtype=np.int64)
    return {
        "feat_ids": (ids % config.vocab_size).astype(np.int32) if folded else ids,
        "feat_wts": rng.random((n, config.num_fields), dtype=np.float32),
    }


def unit_gain(params, config: ModelConfig, seed: int = 0):
    """The tree with its matrices scaled so that a product keeps a unit input
    at the size it has at the published width of 4096 (router logits and a
    score logit of standard deviation 1.3, not 0.16), and every norm weight
    drawn around 1, so that a norm left out or misplaced shows; the sinks stay
    as drawn."""
    gain = (4096 / config.embed_dim) ** 0.5
    rng = np.random.default_rng(seed)

    def scale(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if name in ("embedding", "sink"):
            return leaf
        if leaf.ndim == 1 and name != "score":
            return (leaf * (1.0 + 0.2 * rng.standard_normal(leaf.shape))).astype(leaf.dtype)
        return leaf * gain

    return jax.tree_util.tree_map_with_path(scale, params)


def load(name: str):
    spec = importlib.util.spec_from_file_location(f"mimo_{name}", os.path.join(CONFIG_DIR, name + ".py"))
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


def reference_scores(reference, params, batch, config):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p, b: reference.forward(p, b, **sizes_of(config)))(params, batch))


# ------------------------------------------------- the family and the reference


@pytest.mark.parametrize("plan,dense,length", [
    (PLAN, (0, 1, 1, 1, 1, 1, 1), 44), ((0, 1, 1, 0), (0, 1, 1, 1), 21), ((1, 0), (1, 1), 9),
    ((0, 1, 0, 1, 1, 0), (0, 0, 1, 1, 1, 1), 30), ((1,), (1,), 5), ((1, 1, 0), (0, 1, 1), 8)])
def test_float32_logits_match_the_plain_reference(reference, plan, dense, length):
    """Through `model.apply`; the reference computes every layer at every
    position, the family the last layer's queries and FFN at the last alone and
    a window last layer's keys over its window alone: the last-position cut is
    exact, also at a length that is no multiple of the window, under either
    kind of last layer, where the row is shorter than the window (5 of 8) and
    where it is exactly the window."""
    config = tiny_config(num_hidden_layers=len(plan), hybrid_layer_pattern=plan, moe_layer_freq=dense,
                         num_fields=length)
    model = build_model("mimo_v2", config)
    params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(7)), config)
    batch = rows(5, config)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.logits(p, b, **sizes_of(config)))(params, batch))
        got = np.asarray(jax.jit(model.apply)(params, batch)["logits"])
    assert want.shape == got.shape == (5,) and want.std() > 0.2
    assert np.max(np.abs(want - got)) < 2e-5


@pytest.mark.parametrize("kind", ["window", "full"])
def test_the_last_layers_query_alone_is_the_whole_layers_last_position(reference, kind):
    config = tiny_config()
    s = mimo_v2._sizes(config)
    layers = unit_gain(jax.jit(build_model("mimo_v2", config).init)(jax.random.PRNGKey(2)), config)["layers"]
    p = layers[1 if kind == "window" else 5]["attn"]
    x = jnp.asarray(np.random.default_rng(0).standard_normal((3, LENGTH, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, mass = mimo_v2.attention(p, x, s, kind, jnp.float32)
        last, mass_last = mimo_v2.attention(p, x, s, kind, jnp.float32, last_only=True)
        want, sunk = reference.attention(p, x, int(kind == "window"), WINDOW, HEAD, V_HEAD, ROTARY)
    assert last.shape == (3, 1, 64) and ("sink" in p) == (kind == "window")
    np.testing.assert_allclose(np.asarray(last), np.asarray(whole[:, -1:]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), rtol=1e-4, atol=1e-5)
    if kind == "full":
        assert mass is mass_last is sunk is None
    else:  # the sink's share over all queries, and over the last one alone
        np.testing.assert_allclose(np.asarray(mass), np.asarray(sunk.mean(axis=1)), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(mass_last), np.asarray(sunk[:, -1]), rtol=1e-5)


# ------------------------------------------------- the sink in the shared code


def direct_sink_attention(q, k, v, window, sink):
    """softmax over `[scores, b_h]` with one [L, L] mask and the sink's column
    dropped, in float64: (`[n, Lq, G, J, d_v]`, the sink's column `[n, Lq, G, J]`).
    The queries stand at the last positions of the keys' range."""
    q, k, v, sink = (np.asarray(x, np.float64) for x in (q, k, v, sink))
    queries, keys = q.shape[1], k.shape[1]
    t, u = np.arange(keys - queries, keys)[:, None], np.arange(keys)[None, :]
    seen = u <= t
    if window:
        seen &= t - u < window
    scores = np.where(seen, np.einsum("nqgjd,nkgd->ngjqk", q, k) / np.sqrt(q.shape[-1]), -np.inf)
    column = np.broadcast_to(sink.reshape(q.shape[2], q.shape[3], 1, 1), scores.shape[:-1] + (1,))
    both = np.concatenate([scores, column], axis=-1)
    e = np.exp(both - both.max(-1, keepdims=True))
    probs = e / e.sum(-1, keepdims=True)
    return np.einsum("ngjqk,nkgd->nqgjd", probs[..., :-1], v), np.transpose(probs[..., -1], (0, 3, 1, 2))


def drawn(seed, n, queries, keys, groups, per_group, head, v_head):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    return (draw(n, queries, groups, per_group, head), draw(n, keys, groups, head), draw(n, keys, groups, v_head),
            jnp.asarray(3.0 * rng.standard_normal(groups * per_group), jnp.float32))


@pytest.mark.parametrize("queries,keys,window", [
    (44, 44, 8), (44, 44, None), (5, 5, 8), (8, 8, 8), (1, 44, 8), (1, 44, None), (1, 6, 8), (600, 600, 128)])
def test_the_blocks_with_a_sink_are_a_direct_softmax_over_the_scores_and_the_logit(queries, keys, window):
    """`sequence.blocked_attention`'s XLA blocks (two blocks of queries at 600
    positions): a window shorter than the row, a row shorter than the window,
    the last query alone; the probabilities sum to one less the sink's share."""
    q, k, v, sink = drawn(queries, 2, queries, keys, 2, 3, 24, 16)
    with jax.default_matmul_precision("highest"):
        got, share = sequence.blocked_attention(q, k, v, window, jnp.float32, 3, sink)
        plain = sequence.blocked_attention(q, k, v, window, jnp.float32, 3)
    want, mass = direct_sink_attention(q, k, v, window, sink)
    assert got.shape == q.shape[:-1] + (16,) and share.shape == q.shape[:-1]
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(share), mass, rtol=2e-5, atol=1e-9)
    # the sink takes mass and gives no value: the output is the sink-less one times what the keys kept
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain) * (1 - mass)[..., None], rtol=1e-4, atol=1e-6)
    assert 0.01 < mass.mean() < 0.9 and (queries == 1 or mass.max() > 0.5 > mass.min())


def test_causal_softmax_without_a_sink_is_the_parents():
    scores = jnp.asarray(np.random.default_rng(0).standard_normal((2, 3, 5, 9)), jnp.float32)
    got = sequence.causal_softmax(scores, 4, 3)
    assert isinstance(got, jax.Array) and np.allclose(np.asarray(got).sum(-1), 1.0, atol=1e-6)
    probs, aside = sequence.causal_softmax(scores, 4, 3, jnp.full((3, 1, 1), -1e30, jnp.float32))
    np.testing.assert_array_equal(np.asarray(probs), np.asarray(got))  # a sink that takes nothing
    assert float(jnp.max(aside)) == 0.0


@pytest.mark.parametrize("keys,window,groups,per_group,count", [
    (300, 128, 2, 4, 3),   # a window shorter than the row, 4 heads stacked a grid step, each its own logit
    (300, None, 1, 2, 3),  # no window: one tile of 384, the row in whole lanes
    (100, 128, 2, 2, 3),   # a row shorter than the window
    (600, None, 2, 1, 2),  # two tiles of 512, two pieces
    (256, 128, 1, 8, 1),   # 8 heads read one key-value head: two grid steps of 4
])
def test_the_kernel_with_a_sink_is_the_xla_path_and_the_direct_softmax(keys, window, groups, per_group, count):
    """Interpreted on the CPU: the running state starts at the sink (maximum
    b_h, sum 1, accumulator 0), the heads a grid step stacks each at their own
    logit, and the share comes from the kernel's own maximum and sum."""
    cd = jnp.bfloat16
    q, k, v, sink = drawn(keys, 2, keys, keys, groups, per_group, 48, 32)
    xla, xla_share = sequence.blocked_attention(q, k, v, window, cd, count, sink)
    notes: list = []
    with sequence.serving_attention(notes, interpret=True):
        got, share = sequence.blocked_attention(q, k, v, window, cd, count, sink)
        plain = sequence.blocked_attention(q, k, v, window, cd, count)
    assert notes == [{"kernel": "pallas", "block": attention_kernel.tile(keys, window), "pieces": count}]
    assert got.shape == xla.shape == q.shape[:-1] + (32,) and share.shape == xla_share.shape == q.shape[:-1]
    want, mass = direct_sink_attention(q, k, v, window, sink)
    error = lambda x, y: float(np.max(np.abs(np.asarray(x, np.float64) - y)))  # noqa: E731
    limit = {1: 3e-2, 2: 2e-4, 3: 1e-5}[count]  # bfloat16 operands; 2 ** -17 of them; float32 rounding
    assert error(got, want) < limit and error(got, want) <= error(xla, want) + limit / 10
    assert error(share, mass) < limit and error(share, np.asarray(xla_share)) < limit
    # and the sink did something: the sink-less kernel on the same operands is elsewhere
    assert error(plain, want) > 30 * limit or count == 1


def test_the_kernel_without_a_sink_takes_no_sink_operand():
    """No sink given: the parent's call, operand for operand (the lowered
    text of the four older families' steps is held below)."""
    q, k, v, _ = drawn(1, 1, 256, 256, 1, 2, 48, 32)
    with sequence.serving_attention([], interpret=True):
        without = jax.make_jaxpr(lambda: sequence.blocked_attention(q, k, v, 128, jnp.bfloat16, 3))()
        with_one = jax.make_jaxpr(
            lambda: sequence.blocked_attention(q, k, v, 128, jnp.bfloat16, 3, jnp.zeros((2,), jnp.float32)))()
    assert "sink" not in str(without) and len(with_one.out_avals) == 2 and len(without.out_avals) == 1


def test_rotary_turns_the_first_dims_alone_at_the_kinds_own_base():
    config = tiny_config()
    s = mimo_v2._sizes(config)
    assert (s["rotary"], s["theta"], s["kv"]) == (8, {"full": 1e7, "window": 1e4}, {"full": 2, "window": 4})
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, LENGTH, 3, HEAD)), jnp.float32)
    cos, sin = routed.rope_table(LENGTH, 8, 1e4)
    turned = routed.rotate(x, cos[:, None, :], sin[:, None, :], 8)
    np.testing.assert_array_equal(np.asarray(turned[..., 8:]), np.asarray(x[..., 8:]))  # dims 8.. unturned
    whole = routed.rotate(x[..., :8], cos[:, None, :], sin[:, None, :])
    np.testing.assert_allclose(np.asarray(turned[..., :8]), np.asarray(whole))
    np.testing.assert_array_equal(np.asarray(turned[:, 0]), np.asarray(x[:, 0]))  # position 0: no turn
    assert float(jnp.max(jnp.abs(turned[:, 1:, :, :8] - x[:, 1:, :, :8]))) > 0.1
    # the base reaches the layer by its kind
    p = jax.jit(build_model("mimo_v2", config).init)(jax.random.PRNGKey(1))["layers"]
    a = jnp.asarray(np.random.default_rng(4).standard_normal((2, LENGTH, 64)), jnp.float32)
    for kind, layer in (("full", p[0]), ("window", p[1])):
        at = lambda theta: np.asarray(mimo_v2.attention(  # noqa: E731
            layer["attn"], a, dict(s, theta={**s["theta"], kind: theta}), kind, jnp.float32)[0])
        other = "window" if kind == "full" else "full"
        np.testing.assert_array_equal(at(s["theta"][kind]), np.asarray(mimo_v2.attention(
            layer["attn"], a, dict(s, theta={**s["theta"], other: 3.0}), kind, jnp.float32)[0]))
        assert np.max(np.abs(at(s["theta"][kind]) - at(3.0))) > 1e-4


# ----------------------------------------------------- the share and the model


def test_the_32_shares_of_a_layer_add_up_to_the_uncut_layer(reference):
    """Experts over 32 chips (here 64 experts, 2 a chip, top-8; the cell's are
    256, 8 a chip), the attention whole on each and NO shared expert: the parts
    that all the shares give of one routed layer, the attention, the norms and
    the residual counted once, are the uncut reference's layer; every choice of
    every token falls on exactly one share."""
    uncut = tiny_config(n_routed_experts=64, experts_held=64, first_expert_held=0, num_experts_per_tok=8)
    layer = unit_gain(jax.jit(build_model("mimo_v2", uncut).init)(jax.random.PRNGKey(4)), uncut)["layers"][1]
    assert "shared" not in layer
    x = jnp.asarray(np.random.default_rng(5).standard_normal((2, LENGTH, 64)), jnp.float32)
    s, f32, eps, sizes = mimo_v2._sizes(uncut), jnp.float32, 1e-5, sizes_of(uncut)
    sizes.pop("hybrid_layer_pattern")
    a_share = jax.jit(lambda held, tokens, chosen, gates, first: routed.held_experts(
        held, tokens, chosen, gates, first, f32, block=16, count=3))
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda p, x: reference.layer_forward(p, x, 1, **sizes))(layer, x)
        h = x + mimo_v2.attention(layer["attn"], routed.rms_norm(layer["input_norm"], x, eps), s, "window", f32)[0]
        tokens = routed.rms_norm(layer["post_attn_norm"], h, eps).reshape(-1, 64)
        chosen, gates, _ = routed.route(layer["router"], tokens, 8, 1.0)
        ffn, given = 0.0, 0
        for share in range(32):  # experts 2 * share, 2 * share + 1
            held = {k: w[2 * share:2 * share + 2] for k, w in layer["experts"].items()}
            part, loads, _ = a_share(held, tokens, chosen, gates, 2 * share)
            ffn, given = ffn + part, given + int(loads.sum())
        got = h + ffn.reshape(x.shape)
        whole, counts = jax.jit(lambda l, a: routed.routed_ffn(l, a, 8, 0, 1.0, f32, 3))(layer, tokens.reshape(x.shape))
    assert given == tokens.shape[0] * 8 and counts.tolist()[:2] == [tokens.shape[0], given]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(h + whole), np.asarray(want), rtol=1e-4, atol=2e-5)  # no shared expert added
    assert float(jnp.max(jnp.abs(h + part.reshape(x.shape) - want))) > 0.1  # one share alone is not the layer


def test_a_row_of_zero_weights_is_left_out_of_the_experts_and_every_counter_exactly():
    config = tiny_config(first_expert_held=0)
    model = build_model("mimo_v2", config)
    params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(4)), config)
    batch = rows(3, config)
    padded = {k: np.concatenate([v, np.zeros_like(v[:1])]) for k, v in batch.items()}
    step = jax.jit(model.apply_stats)
    (out, stats), (out_padded, stats_padded) = step(params, batch), step(params, padded)
    np.testing.assert_array_equal(np.asarray(out_padded["logits"][:3]), np.asarray(out["logits"]))
    assert stats_padded.tolist() == stats.tolist() and float(out_padded["logits"][3]) == 0.0


# ------------------------------------------------------------------ counters


def test_the_steps_counters_and_the_sinks_mass_against_the_references_own(reference):
    config = tiny_config()
    model = build_model("mimo_v2", config)
    params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(9)), config)
    batch = rows(4, config)
    _, stats = jax.jit(model.apply_stats)(params, batch)
    named = dict(zip(model.step_stats, stats.tolist()))
    assert model.step_stats == routed.STEP_STATS + (
        "attn.scores_computed", "attn.scores_seen", "attn.sink_mass_ppm", "attn.sink_rows")
    # five routed layers at all positions, the last at one
    assert named["moe.tokens"] == 4 * (5 * LENGTH + 1)
    assert 0 < named["moe.busiest_expert_tokens"] <= named["moe.assignments_here"] <= 4 * named["moe.tokens"]
    assert 0.5 < named["moe.assignments_here"] / named["moe.tokens"] < 1.5  # 4 x 4 / 16 under even routing
    # the XLA path's blocks: one block of queries at 44 positions, so a window layer computes what a full one does
    causal, banded = LENGTH * (LENGTH + 1) // 2, WINDOW * (WINDOW + 1) // 2 + (LENGTH - WINDOW) * WINDOW
    assert named["attn.scores_computed"] == 4 * (6 * LENGTH * LENGTH + WINDOW)
    assert named["attn.scores_seen"] == 4 * (2 * causal + 4 * banded + WINDOW)
    # the sink's mass: 5 window layers a row, in parts per million, against the reference's own within 1%
    assert named["attn.sink_rows"] == 4 * 5
    with jax.default_matmul_precision("highest"):
        want = float(jax.jit(lambda p, b: reference.sink_mass_pct(p, b, **sizes_of(config)))(params, batch))
    got = named["attn.sink_mass_ppm"] / named["attn.sink_rows"] / 1e4
    assert 1.0 < want < 60.0 and abs(got - want) < 0.01 * want
    # through the kernel, interpreted: the share comes from its own running maximum and sum
    def served(p, b):
        with sequence.serving_attention([], interpret=True):
            return model.apply_stats(p, b)
    _, through = jax.jit(served)(params, batch)
    here = dict(zip(model.step_stats, through.tolist()))
    assert abs(here["attn.sink_mass_ppm"] / here["attn.sink_rows"] / 1e4 - want) < 0.01 * want and here["attn.sink_rows"] == 20
    assert through.tolist()[:3] == stats.tolist()[:3] and here["attn.scores_seen"] == named["attn.scores_seen"]
    assert here["attn.scores_computed"] > named["attn.scores_computed"]


def test_a_step_that_leaves_the_sink_out_counts_the_pairs_and_no_mass(monkeypatch):
    config = tiny_config()
    model = build_model("mimo_v2", config)
    params = jax.jit(model.init)(jax.random.PRNGKey(9))
    attention = mimo_v2.attention
    monkeypatch.setattr(mimo_v2, "attention", lambda p, x, s, kind, *rest: attention(
        p, x, dict(s, sink={"full": False, "window": False}), kind, *rest))
    _, stats = jax.jit(model.apply_stats)(params, rows(2, config))
    assert stats.tolist()[-2:] == [0, 10]


def test_the_published_rows_pairs_are_what_the_reader_will_divide(monkeypatch):
    """2,048 positions, window 128, F W W W W F W through the kernel's tiles:
    a full layer 2,621,440 pairs computed a row, a window layer 507,904."""
    monkeypatch.setattr(sequence, "kernel_serves", lambda queries: queries > 1)
    kinds = ("full", "window", "window", "window", "window", "full", "window")
    # a window layer: a key block of its own for the first query block, then two a block
    assert mimo_v2.step_pairs(("window", "full"), 2048, 128) == (
        31 * 128 * 128 + 2048, 128 * 129 // 2 + 1920 * 128 + 2048)
    computed, seen = mimo_v2.step_pairs(kinds, 2048, 128)
    assert computed == 2 * 2_621_440 + 4 * 507_904 + 128
    assert seen == 2 * (2048 * 2049 // 2) + 4 * (128 * 129 // 2 + 1920 * 128) + 128
    assert 100 * (1 - seen / computed) == pytest.approx(28.35, abs=0.05)


# ---------------------------------------------------------------- precision


@pytest.fixture(scope="module")
def served_precision(reference):
    """bfloat16 weights and compute as served, rows twelve windows long, and
    the float32 reference's scores."""
    config = tiny_config(num_fields=96, compute_dtype="bfloat16", param_dtype="bfloat16")
    model = build_model("mimo_v2", config)
    params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(5)), config)
    batch = rows(8, config, seed=11)
    return model, params, batch, reference_scores(reference, params, batch, config)


def _worst(model, params, batch, want) -> float:
    got = np.asarray(jax.jit(lambda p, b: model.apply(p, b))(params, batch)["prediction_node"])
    return float(np.max(np.abs(got.astype(np.float64) - want)))


def test_three_piece_scores_within_the_benchmark_tolerance(served_precision, tolerance):
    model, params, batch, want = served_precision
    assert want.std() > 0.1  # scores that spread, or the comparison compares nothing
    assert mimo_v2.OPERAND_PIECES == 3 and _worst(model, params, batch, want) < tolerance / 3


def _resized(monkeypatch, change):
    """`mimo_v2.attention` with its layer's tree and sizes changed by
    `change(p, s, kind, x) -> (p, s)`."""
    attention = mimo_v2.attention

    def planted(p, x, s, kind, *rest):
        p, s = change(p, s, kind, x)
        return attention(p, x, s, kind, *rest)

    monkeypatch.setattr(mimo_v2, "attention", planted)


def _one_piece(monkeypatch):
    """The nearest precision below the stated one: every activation rounded
    to bfloat16 where it enters a product."""
    monkeypatch.setattr(mimo_v2, "OPERAND_PIECES", 1)


def _sink_left_out(monkeypatch):
    _resized(monkeypatch, lambda p, s, kind, x: (p, dict(s, sink={"full": False, "window": False})))


def _sink_on_the_full_layers_too(monkeypatch):
    logits = jnp.asarray(3.0 * np.random.default_rng(50).standard_normal(8), jnp.float32)
    _resized(monkeypatch, lambda p, s, kind, x: (
        dict(p, sink=p.get("sink", logits)), dict(s, sink={"full": True, "window": True})))


def _one_rotary_base(monkeypatch):
    _resized(monkeypatch, lambda p, s, kind, x: (p, dict(s, theta=dict(s["theta"], full=s["theta"]["window"]))))


def _rotary_on_all_dims(monkeypatch):
    _resized(monkeypatch, lambda p, s, kind, x: (p, dict(s, rotary=s["head"])))


def _values_unscaled(monkeypatch):
    _resized(monkeypatch, lambda p, s, kind, x: (p, dict(s, value_scale=1.0)))


def _window_kv_heads_as_full(monkeypatch):
    """A window layer reads the first 2 of its 4 key-value heads, 4 query heads each."""
    def fewer(p, s, kind, x):
        if kind != "window":
            return p, s
        n = s["kv"]["full"]
        return (dict(p, k=p["k"][:, :n * s["head"]], v=p["v"][:, :n * s["v_head"]]),
                dict(s, kv=dict(s["kv"], window=n)))
    _resized(monkeypatch, fewer)


def _every_layer_full(monkeypatch):
    _resized(monkeypatch, lambda p, s, kind, x: (p, dict(s, window=x.shape[1])))


def _top_7(monkeypatch):
    """One choice fewer than the configuration states."""
    route = routed.route
    monkeypatch.setattr(routed, "route", lambda router, x, k, scaling: route(router, x, k - 1, scaling))


def _an_expert_dropped(monkeypatch):
    """The last held expert's part left out of the routed sum."""
    whole = routed.held_experts
    monkeypatch.setattr(routed, "held_experts", lambda p, *a, **kw: whole({n: w[:-1] for n, w in p.items()}, *a, **kw))


@pytest.mark.parametrize("plant,factor", [
    (_one_piece, 3), (_sink_left_out, 10), (_sink_on_the_full_layers_too, 10), (_one_rotary_base, 10),
    (_rotary_on_all_dims, 10), (_values_unscaled, 10), (_window_kv_heads_as_full, 10), (_top_7, 10),
    (_an_expert_dropped, 10), (_every_layer_full, 10)],
    ids=["one-piece operands", "sink left out", "sink on the full layers too", "one rotary base",
         "rotary on all dims", "values unscaled", "window kv heads as full", "top-7", "an expert dropped",
         "every layer full"])
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

    cfgs = load_config(os.path.join(ROOT, "configs", "mimo_v2_small.toml"))
    config = dataclasses.replace(cfgs["model"], name="M")
    cfg = dataclasses.replace(cfgs["server"], model_name="M", warmup=False)
    _registry, batcher, impl, servable, _mesh, _watcher = build_stack(cfg, model_config=config)
    yield batcher, impl, servable
    batcher.stop()


def _step_phases() -> dict:
    from distributed_tf_serving_tpu.utils.tracing import request_trace

    return {k: v["count"] for k, v in request_trace.snapshot().items() if k.startswith(("moe.", "attn."))}


def test_a_request_through_the_batchers_entry_scores_like_the_reference(served, reference, tolerance):
    """configs/mimo_v2_small.toml down the served path: 3 rows pad to the
    bucket of 4; ids travel as u24 and weights as float32; the step's seven
    counters come back with the scores and are recorded by count, the padded
    row in none of them."""
    batcher, _impl, servable = served
    config = servable.model.config
    arrays = rows(3, config, folded=False)
    before = _step_phases()
    got = batcher.submit(servable, arrays).result(timeout=300)
    assert set(got) == {"prediction_node", "logits"} and type(got["prediction_node"]) is np.ndarray
    batch = dict(arrays, feat_ids=(arrays["feat_ids"] % config.vocab_size).astype(np.int32))
    want = reference_scores(reference, servable.params, batch, config)
    assert got["prediction_node"].shape == (3,) and batcher.compress_transfer
    assert np.max(np.abs(got["prediction_node"] - want)) < tolerance
    after = _step_phases()
    _, alone = jax.jit(servable.model.apply_stats)(servable.params, batch)  # the 3 rows with no padding
    assert [after[name] - before.get(name, 0) for name in servable.model.step_stats] == alone.tolist()
    assert after["moe.tokens"] - before.get("moe.tokens", 0) == 3 * (5 * config.num_fields + 1)
    assert after["attn.sink_rows"] - before.get("attn.sink_rows", 0) == 3 * 5
    named = dict(zip(servable.model.step_stats, alone.tolist()))
    assert named["moe.busiest_expert_tokens"] > 0 and 0 < named["attn.scores_seen"] < named["attn.scores_computed"]
    assert 0 < named["attn.sink_mass_ppm"] < 1e6 * named["attn.sink_rows"]


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


def test_runtime_block_reports_the_three_plans(served):
    batcher, impl, servable = served
    batcher.submit(servable, rows(2, servable.model.config, folded=False)).result(timeout=300)
    startup = impl.runtime_stats()["startup"]
    assert startup["layer_plan"] == {"M:1": {"full/dense": 1, "window/moe": 5, "full/moe": 1}}
    window = {"kind": "window", "window": 16, "block": 80, "keys_a_block": 80, "kv_heads": 4, "rotary_dims": 16,
              "theta": 10000.0, "sink": True}
    full = {"kind": "full", "window": 0, "block": 80, "keys_a_block": 80, "kv_heads": 2, "rotary_dims": 16,
            "theta": 10000000.0, "sink": False}
    assert startup["attention_plan"] == {"M:1": [full, window, window, window, window, full, window]}
    assert startup["expert_plan"] == {"M:1": {
        "published": 32, "held": 4, "first": 8, "top_k": 4, "heads_published": 8, "heads_held": 8,
        "chips_sharing_layer": 8}}
    assert startup["attention"] == {"M:1": {"kernel": "xla", "block": 0, "pieces": 3}}  # no TPU here
    assert startup["assembler"] == {"M:1": "native"} or not native.available()
    assert "feat_ids int32/24b" in startup["upload_format"]["M:1"]


# ------------------------------------------------------- the published shapes


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        shape = json.load(f)["toml"]["model"]
    return ModelConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in shape.items()})


def test_plan_and_parameter_count_at_the_published_cut(published):
    """By `jax.eval_shape`: nothing of the 2.144 B parameters is made."""
    model = build_model("mimo_v2", published)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    size = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))  # noqa: E731
    assert model.layer_plan == ("full/dense",) + ("window/moe",) * 4 + ("full/moe", "window/moe")
    assert dict(model.expert_plan) == {
        "published": 256, "held": 8, "first": 0, "top_k": 8, "heads_published": 64, "heads_held": 64,
        "chips_sharing_layer": 32}
    assert [dict(layer) for layer in model.attention_plan][4:6] == [
        {"kind": "window", "window": 128, "block": 512, "keys_a_block": 639, "kv_heads": 8, "rotary_dims": 64,
         "theta": 10000.0, "sink": True},
        {"kind": "full", "window": 0, "block": 512, "keys_a_block": 2048, "kv_heads": 4, "rotary_dims": 64,
         "theta": 10000000.0, "sink": False}]
    full, window = shapes["layers"][5]["attn"], shapes["layers"][4]["attn"]
    assert (full["q"].shape, full["k"].shape, full["v"].shape, full["o"].shape) == (
        (4096, 12288), (4096, 768), (4096, 512), (8192, 4096))
    assert (window["q"].shape, window["k"].shape, window["v"].shape, window["o"].shape) == (
        (4096, 12288), (4096, 1536), (4096, 1024), (8192, 4096))
    assert window["sink"].shape == (64,) and "sink" not in full
    assert round(size(full) / 1e4) == 8913 and round(size(window) / 1e4) == 9437
    assert round(size(shapes["layers"][0]["mlp"]) / 1e5) == 2013 and "shared" not in shapes["layers"][1]
    assert round(size(shapes["layers"][1]) / 1e5) == 2968 and round(size(shapes["layers"][5]) / 1e5) == 2915
    assert shapes["embedding"].shape == (19072, 4096) and shapes["layers"][1]["router"].shape == (4096, 256)
    assert shapes["layers"][1]["experts"]["gate"].shape == (8, 4096, 2048)
    assert shapes["layers"][0]["mlp"]["gate"].shape == (4096, 16384)
    assert round(size(shapes) / 1e6) == 2144 and {x.dtype for x in jax.tree.leaves(shapes)} == {jnp.dtype("bfloat16")}


@pytest.mark.parametrize("overrides,match", [
    ({"hybrid_layer_pattern": (0, 1, 1, 1)}, "hybrid_layer_pattern"),
    ({"hybrid_layer_pattern": (0, 1, 1, 1, 1, 0, 2)}, "hybrid_layer_pattern"),
    ({"moe_layer_freq": (0, 1)}, "moe_layer_freq"),
    ({"partial_rotary_factor": 0.3}, "partial_rotary_factor"),  # int(24 * 0.3) = 7: no pairs
    ({"partial_rotary_factor": 0.0}, "partial_rotary_factor"),
    ({"num_key_value_heads": 3}, "key-value heads on a full layer"),
    ({"swa_num_key_value_heads": 3}, "key-value heads on a window layer"),
    ({"sliding_window": 0}, "sliding_window"),
    ({"n_routed_experts": 0}, "n_routed_experts"),
    ({"num_experts_per_tok": 17}, "num_experts_per_tok"),
    ({"experts_held": 5}, "experts_held"),
    ({"first_expert_held": 14}, "experts_held"),
])
def test_a_share_or_a_plan_the_stack_cannot_be_built_from_is_refused(overrides, match):
    with pytest.raises(ValueError, match=match):
        build_model("mimo_v2", tiny_config(**overrides))


def test_keys_left_out_take_the_published_pattern():
    model = build_model("mimo_v2", tiny_config(
        hybrid_layer_pattern=(), moe_layer_freq=(), num_hidden_layers=13, swa_num_key_value_heads=0))
    assert [kind for kind, _ in mimo_v2.layer_plan(model.config)] == (
        ["full"] + ["window"] * 4 + ["full"] + ["window"] * 5 + ["full", "window"])
    assert [ffn for _, ffn in mimo_v2.layer_plan(model.config)] == ["dense"] + ["moe"] * 12
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))["layers"]
    assert shapes[1]["attn"]["k"].shape == shapes[0]["attn"]["k"].shape == (64, 2 * HEAD)  # the full layers' heads


def test_toml_reads_the_published_keys(tmp_path):
    cfgs = load_config(os.path.join(ROOT, "configs", "mimo_v2_small.toml"))
    model = build_model(cfgs["server"].model_kind, cfgs["model"])
    assert model.kind == "mimo_v2" and not model.takes_dense and not model.wts_in_compute_dtype
    assert cfgs["server"].num_fields == cfgs["model"].num_fields
    assert len(model.layer_plan) == cfgs["model"].num_hidden_layers == len(cfgs["model"].hybrid_layer_pattern)
    assert cfgs["model"].add_swa_attention_sink_bias and not cfgs["model"].add_full_attention_sink_bias
    (tmp_path / "s.toml").write_text("[model]\nswa_kv_heads = 4\n")
    with pytest.raises(ValueError, match="unknown ModelConfig keys"):
        load_config(str(tmp_path / "s.toml"))


# ------------------------------------------ what the sink left as it was


# sha256 (its first 16 digits) of the lowered text of the four older families'
# served steps at the commit before the sink (PR 49's tree, on this
# container's jax), at every rung of their small TOMLs' ladders: `xla` as
# `model.apply_stats` (or `apply`) lowers outside `serving_attention`, `kernel`
# inside it with the kernel interpreted. With no sink given the shared
# softmax, blocks, kernel, rotary turn and routed layer trace operand for
# operand what they traced there. (`pangu_moe_small` and `exaone_moe_small`
# since PR 51 at PR 51's tree: their routed layers count one thing more,
# `moe.rows_computed`, on the XLA path, and inside the entry their held
# experts are the grouped kernels, interpreted. `olmo_hybrid_small` since
# PR 52 at PR 52's tree: its counters leave with the logits through one
# `optimization_barrier` on either path, before which the XLA path lowered
# to PR 49's text still, and inside the entry the rule's chunk pass is the
# delta kernel, interpreted. `phi4flash_small` and `olmo_hybrid_small` since
# PR 57 at PR 57's tree: their two pieces meet a weight in ONE product, along
# a second contracted axis (`sequence.product`), on either path and at every
# rung; the three-piece families' ten digests passed that change untouched.
# `pangu_moe_small` and `exaone_moe_small` since PR 58 at PR 58's tree: a
# routed layer's pairs are laid out by ONE sort and walked by one loop (the
# kernels' tile table as long as `T x k` rows and a tile an expert), and the
# layers count a fifth thing, `moe.experts_hit`.)
PARENTS_TEXT = {
    "phi4flash_small/2/xla": "4301e004f3aa5b02", "phi4flash_small/2/kernel": "f1d7617a55d2f678",
    "phi4flash_small/4/xla": "9c36b633ec949e4c", "phi4flash_small/4/kernel": "567ce8c611a949ed",
    "phi4flash_small/8/xla": "f1b0268aae7189c9", "phi4flash_small/8/kernel": "6b8f1bda33313ff8",
    "pangu_moe_small/2/xla": "89d28ae9c63851d4", "pangu_moe_small/2/kernel": "0a044d0f1395fab2",
    "pangu_moe_small/4/xla": "4411e78ec9ba45f8", "pangu_moe_small/4/kernel": "d98e08eb410da32d",
    "pangu_moe_small/8/xla": "5c9b63c0a52c3ea8", "pangu_moe_small/8/kernel": "ffa8e034d2c70bfe",
    "exaone_moe_small/2/xla": "fd0a9c4f6522c23a", "exaone_moe_small/2/kernel": "6e608894b7a2580f",
    "exaone_moe_small/4/xla": "ab4f227eb45d603c", "exaone_moe_small/4/kernel": "9256b6fa33fdb4d8",
    "olmo_hybrid_small/2/xla": "ecdd9541dea18350", "olmo_hybrid_small/2/kernel": "bdf706717c727039",
    "olmo_hybrid_small/4/xla": "503592a291cb1507", "olmo_hybrid_small/4/kernel": "e05f07731113e13b",
}


def lowered_text_digest(name: str, rung: int, path: str) -> str:
    cfgs = load_config(os.path.join(ROOT, "configs", name + ".toml"))
    config = cfgs["model"]
    model = build_model(cfgs["server"].model_kind, config)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    run = model.apply_stats if model.step_stats else model.apply
    batch = {"feat_ids": jax.ShapeDtypeStruct((rung, config.num_fields), np.int32),
             "feat_wts": jax.ShapeDtypeStruct((rung, config.num_fields), np.float32)}

    def served(p, b):
        with sequence.serving_attention([], interpret=True):
            return run(p, b)

    text = jax.jit(served if path == "kernel" else run).lower(params, batch).as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("key", sorted(PARENTS_TEXT))
def test_the_older_families_steps_lower_to_the_parents_text(key):
    name, rung, path = key.split("/")
    assert lowered_text_digest(name, int(rung), path) == PARENTS_TEXT[key]
