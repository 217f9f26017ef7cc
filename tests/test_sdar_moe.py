"""The sdar_moe family (SDAR-30B-A3B-Chat as a pointwise sequence ranker: ONE
denoising pass of block diffusion, the Qwen3-MoE layer under a mask that sees
to the end of the query's block, every layer routed behind a softmax router
with no shared expert) at tiny widths on the CPU: against the benchmark's
plain reference through `model.apply` at three block lengths and down the
served path with the kernels interpreted, the `span` mask of `models/
sequence.py` and `ops/attention_kernel.py` against a dense `[L, L]` mask (XLA's
blocks and the kernel, all positions and a lone last query), `span=None` and a
span of 1 against the causal path bit for bit, the spans the tiles would cut,
the last-position cut, two shares of a routed layer against the layer held
whole, what the benchmark's tolerance catches, and the step's counters and
stamps."""

import dataclasses
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import ModelConfig, build_model, routed, sdar_moe, sequence
from distributed_tf_serving_tpu.ops import attention_kernel
from distributed_tf_serving_tpu.utils.config import load_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "sdar_30b_a3b_rerank")
LENGTH = 72  # whole blocks of 2, 4 and 8
interpreted = functools.partial(sequence.serving_attention, interpret=True)


def tiny_config(**overrides) -> ModelConfig:
    return ModelConfig(**{
        "name": "S", "num_fields": LENGTH, "vocab_size": 1000, "embed_dim": 64, "num_hidden_layers": 3,
        "block_length": 4, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32, "rope_theta": 1e6,
        "layer_norm_eps": 1e-6, "num_experts": 16, "num_experts_per_tok": 4, "norm_topk_prob": True,
        "moe_intermediate_size": 32, "experts_held": 16, "first_expert_held": 0, "compute_dtype": "float32",
        **overrides,
    })


def sizes_of(config: ModelConfig) -> dict:
    """reference.py's keyword arguments for `config`."""
    return {"head": config.head_dim, "theta": config.rope_theta, "eps": config.layer_norm_eps,
            "block": config.block_length, "first": config.first_expert_held, "top_k": config.num_experts_per_tok,
            "norm_topk": config.norm_topk_prob}


def rows(n: int, config: ModelConfig, seed: int = 3) -> dict:
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 1 << 40, size=(n, config.num_fields), dtype=np.int64)
    return {"feat_ids": (ids % config.vocab_size).astype(np.int32),
            "feat_wts": rng.random((n, config.num_fields), dtype=np.float32)}


def unit_gain(params, config: ModelConfig):
    """The tree with its matrices scaled so that a product keeps a unit input
    at the size it has at the published width of 2048 (router logits and a
    score logit of deviation near 1, not 0.16): as drawn, a tiny model's
    router hardly tells its experts apart."""
    gain = (2048 / config.embed_dim) ** 0.5

    def scale(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        return leaf if name == "embedding" or (leaf.ndim < 2 and name != "score") else leaf * gain

    return jax.tree_util.tree_map_with_path(scale, params)


def load(name: str):
    spec = importlib.util.spec_from_file_location(f"sdar_moe_{name}", os.path.join(CONFIG_DIR, name + ".py"))
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


def model_and_tree(config: ModelConfig, seed: int = 0):
    model = build_model("sdar_moe", config)
    return model, unit_gain(model.init(jax.random.PRNGKey(seed)), config)


def reference_scores(reference, params, batch, config):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p, b: reference.forward(p, b, **sizes_of(config)))(params, batch))


# ------------------------------------------------- the family and the reference


def test_the_reference_turns_by_the_programs_angles_at_the_cells_length(reference):
    """At the cell's 2,048 positions, head of 128 and base of 1e6 the
    reference's rotary turn is the program's table to float32's rounding. An
    angle made in float32 is off by 1e-4 rad by the end of such a row, which
    flipped the reference's own routers' near ties where a score is read (the
    driver's refusal of PR 64, PERF.md section 6)."""
    length, head, theta = 2048, 128, 1e6
    cos, sin = routed.rope_table(length, head, theta)
    ones = np.ones((1, length, 1, head), np.float32)
    turned = np.asarray(reference.rot(jnp.asarray(ones), theta))[0, :, 0]
    np.testing.assert_allclose(turned, np.concatenate([cos - sin, cos + sin], -1), atol=2.5e-7, rtol=0)


@pytest.mark.parametrize("block,layers,length,held,first", [
    (4, 3, LENGTH, 16, 0), (8, 3, LENGTH, 16, 0), (2, 2, 38, 16, 0), (4, 1, 12, 4, 8), (1, 2, 33, 8, 8)],
    ids=["a block of 4", "a block of 8", "a block of 2", "one layer, a share", "a block of 1: the causal mask"])
def test_float32_logits_match_the_plain_reference(reference, block, layers, length, held, first):
    """Through `model.apply`; the reference computes every layer at every
    position under a dense `[L, L]` mask, the program the last layer's queries,
    attention output and routed layer at the last position alone."""
    config = tiny_config(block_length=block, num_hidden_layers=layers, num_fields=length, experts_held=held,
                         first_expert_held=first)
    model, params = model_and_tree(config)
    batch = rows(3, config)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(params, batch)["logits"])
        want = np.asarray(jax.jit(lambda p, b: reference.logits(p, b, **sizes_of(config)))(params, batch))
    assert np.abs(want).max() > 0.05  # a score that says something
    np.testing.assert_allclose(got, want, atol=5e-6)


def test_the_served_step_in_bfloat16_is_within_the_tolerance(reference, tolerance):
    """Three bfloat16 pieces an activation, the kernels interpreted (the
    attention's under the block mask, the grouped ones over the layer held
    whole): inside the configuration's tolerance of the float32 reference."""
    config = tiny_config(compute_dtype="bfloat16", param_dtype="bfloat16")
    model, params = model_and_tree(config)
    batch = rows(2, config)
    notes, grouped = [], []

    def served(p, b):
        with interpreted(notes, grouped=grouped):
            return model.apply(p, b)["prediction_node"]

    got = np.asarray(jax.jit(served)(params, batch))
    assert np.abs(got - reference_scores(reference, params, batch, config)).max() < tolerance
    assert {"kernel": "pallas", "block": 128, "pieces": 3, "span": 4} in notes
    assert {"kernel": "xla", "block": 0, "pieces": 3, "span": 4} in notes  # the last layer's one query
    assert grouped[0]["kernel"] == "pallas" and grouped[0]["held"] == 16


def test_the_last_position_form_is_the_all_positions_form_cut():
    config = tiny_config()
    s = sdar_moe._sizes(config)
    p = sdar_moe._layer_init(jax.random.PRNGKey(2), s, jnp.float32)["attn"]
    a = jnp.asarray(np.random.default_rng(1).standard_normal((2, LENGTH, 64)), jnp.float32)
    attention = jax.jit(functools.partial(sdar_moe.attention, s=s, cd=jnp.float32, eps=1e-6), static_argnames="last_only")
    with jax.default_matmul_precision("highest"):
        whole, cut = attention(p, a), attention(p, a, last_only=True)
    assert cut.shape == (2, 1, 64)
    np.testing.assert_allclose(np.asarray(cut), np.asarray(whole[:, -1:]), atol=1e-6)


# ------------------------------------------------------------- the span mask


def _normal(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape), jnp.float32)


def _dense(q, k, v, span, queries=None):
    """softmax(q k' / sqrt(d) | u // span <= t // span) v under ONE `[L, L]`
    mask, float32 at `highest`; `q [n, L, G, J, d]`, the last `queries` rows."""
    length = k.shape[1]
    t = np.arange(length)
    seen = jnp.asarray(t[None, :] // span <= t[:, None] // span)
    einsum = functools.partial(jnp.einsum, precision="highest")
    scores = einsum("nqgjd,nkgd->ngjqk", q, k) * q.shape[-1] ** -0.5
    out = einsum("ngjqk,nkgd->nqgjd", jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), v)
    return out if queries is None else out[:, -queries:]


@pytest.mark.parametrize("span", [4, 8, 2])
def test_causal_softmax_with_a_span_is_the_dense_masks_softmax(span):
    scores = _normal(0, 2, 24, 24)
    t = np.arange(24)
    want = jax.nn.softmax(jnp.where(t[None, :] // span <= t[:, None] // span, scores, -jnp.inf), axis=-1)
    np.testing.assert_array_equal(np.asarray(sequence.causal_softmax(scores, 0, span=span)), np.asarray(want))
    last = sequence.causal_softmax(scores[:, -1:], 23, span=span)  # the last position: the last of its span
    np.testing.assert_allclose(np.asarray(last), np.asarray(jax.nn.softmax(scores[:, -1:], axis=-1)), atol=1e-7)
    assert float(jnp.abs(sequence.causal_softmax(scores, 0, span=span) - sequence.causal_softmax(scores, 0)).max()) > 0.01


@pytest.mark.parametrize("span,queries", [(4, None), (8, None), (4, 1), (8, 1)],
                         ids=["4, all positions", "8, all positions", "4, the last query", "8, the last query"])
def test_blocked_attention_with_a_span_is_the_dense_mask(span, queries):
    """1,040 positions: two whole blocks of 512 queries and 16 of a third,
    each against the keys up to its own last position and no further."""
    q, k, v = _normal(1, 1, 1040, 1, 2, 16), _normal(2, 1, 1040, 1, 16), _normal(3, 1, 1040, 1, 16)
    mine = q if queries is None else q[:, -queries:]
    got = sequence.blocked_attention(mine, k, v, None, jnp.float32, 3, span=span)
    want = _dense(q, k, v, span, queries)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    causal = sequence.blocked_attention(mine, k, v, None, jnp.float32, 3)
    if queries is None:
        assert float(jnp.abs(got - causal).max()) > 0.01  # the mask looks ahead, and it shows
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(causal))  # the last of its block sees every key


@pytest.mark.parametrize("span,length", [(4, 640), (8, 640), (4, 200)],
                         ids=["4: two tiles of 512", "8: two tiles of 512", "4: 200 positions in a tile of 256"])
def test_the_kernel_with_a_span_is_the_dense_mask(span, length):
    """Interpreted: the key blocks walked are the causal mask's, and hold all
    that the block mask keeps; bfloat16 in three pieces, as the cell runs."""
    q, k, v = _normal(4, 1, length, 2, 2, 64), _normal(5, 1, length, 2, 64), _normal(6, 1, length, 2, 64)
    with interpreted(notes := []):
        got = jax.jit(lambda q, k, v: sequence.blocked_attention(q, k, v, None, jnp.bfloat16, 3, span=span))(q, k, v)
    assert notes == [{"kernel": "pallas", "block": attention_kernel.tile(length, None), "pieces": 3, "span": span}]
    np.testing.assert_allclose(np.asarray(got), np.asarray(_dense(q, k, v, span)), atol=3e-5)
    xla = sequence.blocked_attention(q, k, v, None, jnp.bfloat16, 3, span=span)
    np.testing.assert_allclose(np.asarray(got), np.asarray(xla), atol=3e-5)


def test_no_span_and_a_span_of_one_are_the_causal_path_bit_for_bit():
    """`span=None` is the call every other family makes; a block of ONE
    position is the causal mask by another formula, in XLA's blocks and in the
    kernel."""
    q, k, v = _normal(7, 1, 640, 2, 2, 64), _normal(8, 1, 640, 2, 64), _normal(9, 1, 640, 2, 64)
    plain = sequence.blocked_attention(q, k, v, None, jnp.bfloat16, 3)
    np.testing.assert_array_equal(np.asarray(sequence.blocked_attention(q, k, v, None, jnp.bfloat16, 3, span=None)),
                                  np.asarray(plain))
    np.testing.assert_array_equal(np.asarray(sequence.blocked_attention(q, k, v, None, jnp.bfloat16, 3, span=1)),
                                  np.asarray(plain))
    heads_first = functools.partial(jnp.transpose, axes=(0, 2, 1, 3))
    run = functools.partial(
        attention_kernel.attention, (heads_first(q.reshape(1, 640, 4, 64)),), (heads_first(k),), heads_first(v),
        scale=64 ** -0.5, window=None, cd=jnp.dtype(jnp.bfloat16), count=3, interpret=True)
    np.testing.assert_array_equal(np.asarray(run(span=1)), np.asarray(run()))
    np.testing.assert_array_equal(np.asarray(run(span=None)), np.asarray(run()))
    assert sequence.attention_choice(640, 640, None, 3) == {"kernel": "xla", "block": 0, "pieces": 3}  # no `span` key
    assert sequence.blocked_pairs(640, 640) == sequence.blocked_pairs(640, 640, span=1)


@pytest.mark.parametrize("queries,keys,span,block,window", [
    (24, 24, 5, 512, None), (24, 24, 3, 512, None), (24, 24, 0, 512, None), (22, 24, 4, 512, None),
    (2, 24, 4, 512, None), (24, 24, 4, 512, 8), (1, 22, 4, 512, None)],
    ids=["the row is no whole spans", "nor the block of queries", "a span of none", "the queries start inside a span",
         "two last queries of a span of 4", "a window beside a span", "a last query inside its span"])
def test_a_span_the_tiles_would_cut_raises(queries, keys, span, block, window):
    with pytest.raises(ValueError, match="span"):
        attention_kernel.check_span(queries, keys, span, block, window)
    if block == 512:
        with pytest.raises(ValueError, match="span"):
            sequence.attention_choice(queries, keys, window, 3, span=span)
    if window is None and span:
        with pytest.raises(ValueError, match="span"):
            attention_kernel.attention(
                (_normal(0, 1, 1, queries, 8),), (_normal(1, 1, 1, keys, 8),), _normal(2, 1, 1, keys, 8), scale=1.0,
                window=None, cd=jnp.dtype(jnp.float32), count=1, interpret=True, span=span)


def test_a_span_that_fits_is_taken_and_a_family_that_cannot_be_built_is_refused():
    for queries, keys, span, block in ((2048, 2048, 4, 512), (1, 2048, 4, 512), (8, 24, 8, 128), (640, 640, 128, 512)):
        attention_kernel.check_span(queries, keys, span, block)
    for wrong, match in (({"block_length": 5}, "span"), ({"block_length": 0}, "span"),
                         ({"num_fields": 30}, "span"), ({"block_length": 24, "num_fields": 48}, "span"),
                         ({"experts_held": 5}, "divides"), ({"num_key_value_heads": 3}, "whole groups"),
                         ({"head_dim": 31}, "pairs"), ({"num_experts_per_tok": 17}, "num_experts_per_tok")):
        with pytest.raises(ValueError, match=match):
            build_model("sdar_moe", tiny_config(**wrong))


# ------------------------------------------------------------- the routed block


def test_two_shares_add_up_to_the_layer_held_whole_which_is_the_uncut_reference(reference):
    """Experts 0-7 and 8-15 of 16, each share's held part through `routed_ffn`
    as the family calls it, against the layer held WHOLE the same way and
    against the reference with every expert held: no shared expert to count
    once, so the shares' parts are the layer."""
    config = tiny_config()
    s = sdar_moe._sizes(config)
    layer = unit_gain(sdar_moe._layer_init(jax.random.PRNGKey(4), s, jnp.float32), config)
    b = jnp.asarray(np.random.default_rng(5).standard_normal((3, 40, 64)), jnp.float32)
    ffn = jax.jit(functools.partial(routed.routed_ffn, top_k=4, scaling=1.0, cd=jnp.float32, count=3,
                                    router=sdar_moe.route), static_argnames="first")
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(jax.jit(functools.partial(reference.moe, first=0, top_k=4))(layer, b))
        whole, counts = ffn(layer, b, first=0)
        parts, hit, here = [], 0, 0
        for share in range(2):
            mine = {**layer, "experts": jax.tree.map(lambda w: w[8 * share:8 * share + 8], layer["experts"])}
            out, shares_counts = ffn(mine, b, first=8 * share)
            parts.append(np.asarray(out))
            hit, here = hit + int(shares_counts[4]), here + int(shares_counts[1])
    # held whole, every one of a token's 4 choices is here: 4 assignments a token, exactly
    assert (int(counts[0]), int(counts[1]), int(counts[4])) == (120, 480, 16) and (hit, here) == (16, 480)
    np.testing.assert_allclose(sum(parts), uncut, atol=2e-5)
    np.testing.assert_allclose(np.asarray(whole), uncut, atol=2e-5)
    assert np.abs(uncut).max() > 0.01


# ------------------------------------------------------------- planted faults
#
# The tolerance is the configuration's own (`config.json`, set from the chip's
# readings at the published widths: over the served step's largest error and
# under one piece's and a bfloat16 reference's least). Here, at a tiny size
# with weights scaled to the published width's gain, the sound step has to sit
# a twentieth of it from the reference, so that what a fault moves is the
# fault's and not rounding, and every fault has to move a score past it.


def _no_query_norm(_qk_norm):
    return lambda p, q, k, eps: (q, routed.rms_norm(p["k_norm"], k, eps))


def _half_turned(rotate):
    """partial_rotary_factor 0.5: the first half of a head's dims in pairs
    (i, i + d/4) at the angles of a table half as wide; the rest unturned."""
    return lambda x, cos, sin: rotate(x, cos[..., ::2], sin[..., ::2], x.shape[-1] // 2)


# name -> (the module, the name in it that is replaced, what takes its place given what was there)
PATCHES = {
    "no RMS on the query heads": (sdar_moe, "qk_norm", _no_query_norm),
    "the rotary turn on half the dims": (sdar_moe, "rotate", _half_turned),
    "sigmoid scores in place of the softmax": (sdar_moe, "route", lambda _route: lambda router, x, k, scaling, normalise=True:
                                               routed.route(router, x, k, scaling, "sigmoid", normalise)),
}
# name -> the configuration's keys that say something else than the published file
MISCONFIGURED = {
    "the causal mask in place of the block mask": {"block_length": 1},
    "a block of 8 in place of 4": {"block_length": 8},
    "gates not normalised": {"norm_topk_prob": False},
    "top-7": {"num_experts_per_tok": 7},
}


@pytest.fixture(scope="module")
def sound_case(reference):
    """(config, tree, rows, the reference's scores, the sound float32 step's): made once for all the faults."""
    config = tiny_config(num_experts_per_tok=8)
    model, params = model_and_tree(config)
    batch = rows(4, config)
    want = reference_scores(reference, params, batch, config)
    with jax.default_matmul_precision("highest"):
        sound = np.asarray(jax.jit(model.apply)(params, batch)["prediction_node"])
    return config, params, batch, want, sound


@pytest.mark.parametrize("fault", sorted(PATCHES) + sorted(MISCONFIGURED))
def test_a_planted_fault_is_refused_by_the_tolerance(sound_case, tolerance, fault, monkeypatch):
    """The float32 step scores inside a twentieth of the tolerance of the
    reference; with one fault planted, outside the tolerance."""
    config, params, batch, want, sound = sound_case
    if fault in PATCHES:
        module, name, planted = PATCHES[fault]
        monkeypatch.setattr(module, name, planted(getattr(module, name)))
    else:
        config = dataclasses.replace(config, **MISCONFIGURED[fault])
    model = build_model("sdar_moe", config)
    with jax.default_matmul_precision("highest"):
        faulty = np.asarray(jax.jit(lambda p, b: model.apply(p, b))(params, batch)["prediction_node"])
    assert np.abs(sound - want).max() < tolerance / 20
    assert np.abs(faulty - want).max() > tolerance, fault


def test_one_piece_in_place_of_three_is_refused_by_the_tolerance(reference, tolerance, monkeypatch):
    """The nearest precision below the stated one: every activation rounded to
    ONE bfloat16 piece where it enters a product. Three pieces sit inside the
    tolerance; one sits outside it."""
    config = tiny_config(num_fields=256, compute_dtype="bfloat16", param_dtype="bfloat16")
    model, params = model_and_tree(config)
    batch = rows(4, config)
    want = reference_scores(reference, params, batch, config)
    three = np.asarray(jax.jit(model.apply)(params, batch)["prediction_node"])
    monkeypatch.setattr(sdar_moe, "OPERAND_PIECES", 1)
    one = np.asarray(jax.jit(lambda p, b: model.apply(p, b))(params, batch)["prediction_node"])
    assert np.abs(three - want).max() < tolerance
    assert np.abs(one - want).max() > tolerance


# ------------------------------------------------- plans, counters and stamps


def test_plans_of_the_small_toml():
    config = load_config(os.path.join(ROOT, "configs", "sdar_moe_small.toml"))["model"]
    model = build_model("sdar_moe", config)
    assert model.layer_plan == ("block/moe",) * 5
    assert dict(model.expert_plan) == {"published": 16, "held": 16, "first": 0, "top_k": 4, "heads_published": 4,
                                       "heads_held": 4, "chips_sharing_layer": 1}
    assert dict(model.attention_plan[0]) == {
        "kind": "block", "span": 4, "window": 0, "block": 152, "keys_a_block": 152, "kv_heads": 2, "rotary_dims": 32,
        "theta": 1e6}
    assert model.step_stats == routed.STEP_STATS + ("attn.scores_computed", "attn.scores_seen", "attn.scores_ahead")
    share = build_model("sdar_moe", dataclasses.replace(config, experts_held=8, first_expert_held=8))
    assert dict(share.expert_plan)["chips_sharing_layer"] == 2


@pytest.mark.parametrize("block", [4, 8, 1])
def test_the_steps_counters_follow_the_work(block):
    """A padded row is in no counter; the routing's five are summed over the
    three routed layers (the last at one position a row); the score pairs over
    two layers at all positions and one lone last query: `L (B - 1) / 2` a row
    and layer AHEAD of their query, none for the last query and none at all
    under a block of one position, which is the causal mask."""
    config = tiny_config(block_length=block)
    model, params = model_and_tree(config)
    batch = rows(3, config)
    batch["feat_wts"][2] = 0.0
    out, stats = jax.jit(model.apply_stats)(params, batch)
    named = dict(zip(model.step_stats, np.asarray(stats).tolist()))
    assert float(out["logits"][2]) == 0.0
    assert named["moe.tokens"] == 2 * (2 * LENGTH + 1)
    # the layer held whole: every one of a token's 4 choices is here
    assert named["moe.assignments_here"] == 4 * named["moe.tokens"] <= named["moe.rows_computed"]
    assert 2 * 16 <= named["moe.experts_hit"] <= 3 * 16
    ahead = 2 * 2 * LENGTH * (block - 1) // 2
    assert named["attn.scores_ahead"] == ahead
    assert named["attn.scores_seen"] == 2 * (2 * LENGTH * (LENGTH + 1) // 2 + LENGTH) + ahead
    assert named["attn.scores_seen"] <= named["attn.scores_computed"]
    assert sequence.ahead_pairs(LENGTH, LENGTH, None) == sequence.ahead_pairs(1, LENGTH, block) == 0
    assert sequence.ahead_pairs(2048, 2048, 4) == 3072 and sequence.blocked_pairs(2048, 2048, span=4)[1] == 2101248


def test_a_causal_familys_step_counts_nothing_ahead():
    """`exaone_moe`'s step at the small TOML: its `attn.scores_seen` is the
    causal count, `sequence.ahead_pairs` of its masks 0, and it has no
    `attn.scores_ahead` to report."""
    config = load_config(os.path.join(ROOT, "configs", "exaone_moe_small.toml"))["model"]
    model = build_model("exaone_moe", config)
    assert "attn.scores_ahead" not in model.step_stats
    assert sequence.ahead_pairs(config.num_fields, config.num_fields, None) == 0
    assert sequence.blocked_pairs(80, 80)[1] == 80 * 81 // 2


def test_the_batcher_stamps_the_span_and_counts_the_three_score_counters(monkeypatch):
    from distributed_tf_serving_tpu.serving import batcher as batcher_mod
    from distributed_tf_serving_tpu.serving.server import build_stack
    from distributed_tf_serving_tpu.utils.tracing import request_trace

    cfgs = load_config(os.path.join(ROOT, "configs", "sdar_moe_small.toml"))
    config = dataclasses.replace(cfgs["model"], name="S")
    cfg = dataclasses.replace(cfgs["server"], model_name="S", warmup=False)
    monkeypatch.setattr(batcher_mod, "serving_attention", interpreted)
    _registry, batcher, impl, servable, _mesh, _watcher = build_stack(cfg, model_config=config)
    try:
        count = lambda name: request_trace.snapshot().get(name, {}).get("count", 0)  # noqa: E731
        before = {name: count(name) for name in servable.model.step_stats}
        rng = np.random.RandomState(3)
        payload = {"feat_ids": rng.randint(0, 1 << 40, size=(2, config.num_fields)).astype(np.int64),
                   "feat_wts": rng.rand(2, config.num_fields).astype(np.float32)}
        scores = batcher.submit(servable, payload).result(timeout=600)["prediction_node"]
        startup = impl.runtime_stats()["startup"]
        counted = {name: count(name) - before[name] for name in servable.model.step_stats}
    finally:
        batcher.stop()
    length = config.num_fields
    assert scores.shape == (2,) and np.isfinite(scores).all()
    assert startup["attention"]["S:1"] == {"kernel": "pallas", "block": 256, "pieces": 3, "span": 4}
    grouped = startup["grouped"]["S:1"]
    assert grouped == {"kernel": "pallas", "tile": 128, "pieces": 3, "held": 16, "rows": grouped["rows"],
                       "form": "gated_silu", "width": 128}
    assert grouped["rows"] == routed.layout_tiles(2 * length, 4, 16, 128) * 128
    assert startup["layer_plan"]["S:1"] == {"block/moe": 5}
    assert counted["moe.tokens"] == 2 * (4 * length + 1) and counted["moe.assignments_here"] == 4 * counted["moe.tokens"]
    assert counted["attn.scores_ahead"] == 2 * 4 * length * 3 // 2
    assert counted["attn.scores_seen"] == 2 * (4 * length * (length + 1) // 2 + length) + counted["attn.scores_ahead"]
