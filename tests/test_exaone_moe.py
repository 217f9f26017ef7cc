"""The exaone_moe family (K-EXAONE-236B-A23B as a pointwise sequence ranker:
window and full grouped-query attention in one stack, norms on a sub-layer's
output and on every query and key head, a routed layer told which experts it
holds) at tiny widths on the CPU: against the benchmark's plain reference
through `model.apply` and down the served path, the 16 shares of a routed layer
against the uncut layer, the band's blocks against the dense masked form, the
head grouping, the rotary's layers, the last-position cut, what the
benchmark's tolerance catches, the step's counters and how they reach
`/monitoring`, the shapes at the published cut, and what the move of the routed
layer into `models/routed.py` left as it was."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tf_serving_tpu import native
from distributed_tf_serving_tpu.models import ModelConfig, build_model, exaone_moe, routed, sequence
from distributed_tf_serving_tpu.utils.config import load_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "k_exaone_moe_rerank")
S, F = "sliding_attention", "full_attention"
LENGTH, WINDOW, HEAD, THETA = 44, 8, 16, 1000000.0
# The reference's keyword arguments at the tiny widths below.
SIZES = {"top_k": 4, "scaling": 2.5, "window": WINDOW, "head": HEAD, "theta": THETA}


def tiny_config(**overrides) -> ModelConfig:
    return ModelConfig(**{
        "name": "M", "num_fields": LENGTH, "vocab_size": 1000, "embed_dim": 64, "intermediate_size": 96,
        "num_hidden_layers": 5, "first_k_dense_replace": 1, "layer_types": (S, S, S, F, S),
        "sliding_window": WINDOW, "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": HEAD,
        "rope_theta": THETA, "moe_intermediate_size": 32, "num_experts": 16, "num_experts_per_tok": 4,
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
    at the size it has at the published width of 6144 (router logits and a
    score logit of standard deviation 1.6, not 0.16), and every norm weight
    drawn around 1, so that a norm left out or misplaced shows."""
    gain = (6144 / config.embed_dim) ** 0.5
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
    spec = importlib.util.spec_from_file_location(f"exaone_{name}", os.path.join(CONFIG_DIR, name + ".py"))
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
    sizes = dict(SIZES, layer_types=config.layer_types, first=config.first_expert_held)
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p, b: reference.forward(p, b, **sizes))(params, batch))


def heads_of(a, config, seed=1):
    """Random q [n, L, G, J, d], k and v [n, L, G, d] at the config's heads."""
    rng = np.random.default_rng(seed)
    n, length = a
    groups, per_group = config.num_key_value_heads, config.num_attention_heads // config.num_key_value_heads
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    return (draw(n, length, groups, per_group, config.head_dim), draw(n, length, groups, config.head_dim),
            draw(n, length, groups, config.head_dim))


def dense_attention(q, k, v, window):
    """softmax(q k' / sqrt(d) | seen) v with one [L, L] mask, in float64."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    length = q.shape[1]
    t = np.arange(length)
    seen = t[None, :] <= t[:, None]
    if window:
        seen &= t[:, None] - t[None, :] < window
    scores = np.einsum("nqgjd,nkgd->ngjqk", q, k) / np.sqrt(q.shape[-1])
    e = np.where(seen, np.exp(scores - scores.max(-1, keepdims=True)), 0.0)
    return np.einsum("ngjqk,nkgd->nqgjd", e / e.sum(-1, keepdims=True), v)


# ------------------------------------------------- the family and the reference


@pytest.mark.parametrize("kinds,dense,length", [
    ((S, S, S, F, S), 1, 44), ((S, S, S, F), 1, 21), ((F, S), 0, 9), ((S, F, S, S, F, F), 2, 30), ((S,), 0, 5)])
def test_float32_logits_match_the_plain_reference(reference, kinds, dense, length):
    """Through `model.apply`; the reference computes every layer at every
    position, the family the last layer's queries and FFN at the last alone and
    a sliding last layer's keys over its window alone: the last-position cut is
    exact, also at a length that is no multiple of the window, under either
    kind of last layer, and where the row is shorter than the window."""
    config = tiny_config(num_hidden_layers=len(kinds), layer_types=kinds, first_k_dense_replace=dense,
                         num_fields=length)
    model = build_model("exaone_moe", config)
    params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(7)), config)
    batch = rows(5, config)
    sizes = dict(SIZES, layer_types=kinds, first=4)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.logits(p, b, **sizes))(params, batch))
        got = np.asarray(jax.jit(model.apply)(params, batch)["logits"])
    assert want.shape == got.shape == (5,) and want.std() > 0.3
    assert np.max(np.abs(want - got)) < 2e-5


@pytest.mark.parametrize("kind", ["window", "full"])
def test_the_last_layers_query_alone_is_the_whole_layers_last_position(reference, kind):
    config = tiny_config()
    s = exaone_moe._sizes(config)
    p = unit_gain(jax.jit(build_model("exaone_moe", config).init)(jax.random.PRNGKey(2)), config)["layers"][1]["attn"]
    x = jnp.asarray(np.random.default_rng(0).standard_normal((3, LENGTH, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = exaone_moe.attention(p, x, s, kind, jnp.float32, 1e-5, THETA)
        last = exaone_moe.attention(p, x, s, kind, jnp.float32, 1e-5, THETA, last_only=True)
        want = reference.attention(p, x, S if kind == "window" else F, WINDOW, HEAD, THETA)
    assert last.shape == (3, 1, 64)
    np.testing.assert_allclose(np.asarray(last), np.asarray(whole[:, -1:]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("length,window,block", [
    (44, 8, None), (44, 8, 4), (44, 8, 16), (37, 5, 8), (40, 16, 3), (6, 8, None), (33, 1, 4), (64, 16, 16)])
def test_the_bands_blocks_are_the_dense_masked_softmax(length, window, block):
    """Rows several windows long, in blocks smaller and larger than the window
    and of no common measure with it or with the row: every query's window is
    inside the key blocks its block reads, padded keys and the blocks before
    the first are seen by none."""
    config = tiny_config()
    q, k, v = heads_of((2, length), config, seed=length + window)
    with jax.default_matmul_precision("highest"):
        got = exaone_moe.band_attention(q, k, v, window, jnp.float32, block)
    np.testing.assert_allclose(np.asarray(got), dense_attention(q, k, v, window), rtol=1e-4, atol=1e-5)
    blocks, back = exaone_moe.band_blocks(length, window, block or window)
    assert blocks * (block or window) >= length and back * (block or window) >= window - 1


@pytest.mark.parametrize("queries,window", [(44, None), (1, None), (1, 8)])
def test_the_full_layers_blocks_are_the_dense_masked_softmax(queries, window, monkeypatch):
    monkeypatch.setattr(sequence.query_blocks, "__defaults__", (None, 16))  # three blocks, the last one short
    q, k, v = heads_of((2, LENGTH), tiny_config(), seed=4)
    with jax.default_matmul_precision("highest"):
        got = exaone_moe.blocked_attention(q[:, LENGTH - queries:], k, v, window, jnp.float32)
    want = dense_attention(q, k, v, window)[:, LENGTH - queries:]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def test_a_query_head_reads_the_key_value_head_of_its_group():
    """8 query heads over 2 key-value heads: heads 0-3 read head 0, heads 4-7
    head 1. Another VALUE for key-value head 1 moves the output rows of W_o
    that heads 4-7 feed and no other."""
    config = tiny_config()
    s = exaone_moe._sizes(config)
    p = jax.jit(build_model("exaone_moe", config).init)(jax.random.PRNGKey(1))["layers"][0]["attn"]
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, LENGTH, 64)), jnp.float32)
    eye = dict(p, o=jnp.eye(8 * HEAD, dtype=jnp.float32))  # the heads' outputs themselves
    moved = dict(eye, v=eye["v"].at[:, HEAD:].multiply(2.0))  # key-value head 1's values doubled
    for kind in ("window", "full"):
        a, b = (np.asarray(exaone_moe.attention(q, x, dict(s, hidden=8 * HEAD), kind, jnp.float32, 1e-5, THETA))
                for q in (eye, moved))
        a, b = a.reshape(2, LENGTH, 8, HEAD), b.reshape(2, LENGTH, 8, HEAD)
        np.testing.assert_array_equal(a[:, :, :4], b[:, :, :4])
        np.testing.assert_allclose(2.0 * a[:, :, 4:], b[:, :, 4:], rtol=1e-5, atol=1e-7)


def test_rotary_turns_the_sliding_layers_and_not_the_full_ones():
    config = tiny_config()
    s = exaone_moe._sizes(config)
    p = jax.jit(build_model("exaone_moe", config).init)(jax.random.PRNGKey(1))["layers"][0]["attn"]
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, LENGTH, 64)), jnp.float32)
    at = lambda kind, theta: np.asarray(exaone_moe.attention(p, x, s, kind, jnp.float32, 1e-5, theta))  # noqa: E731
    np.testing.assert_array_equal(at("full", THETA), at("full", 10.0))
    assert np.max(np.abs(at("window", THETA) - at("window", 10.0))) > 1e-3


# ----------------------------------------------------- the share and the model


def test_the_16_shares_of_a_layer_add_up_to_the_uncut_layer(reference):
    """128 experts over 16 chips (8 a chip), the attention whole on each: the
    parts that all the shares give of one routed layer, the attention, the
    shared expert, the norms and the residual counted once, are the uncut
    reference's layer; every choice of every token falls on exactly one share."""
    uncut = tiny_config(num_experts=128, experts_held=128, first_expert_held=0, num_experts_per_tok=8)
    layer = unit_gain(jax.jit(build_model("exaone_moe", uncut).init)(jax.random.PRNGKey(4)), uncut)["layers"][1]
    x = jnp.asarray(np.random.default_rng(5).standard_normal((2, LENGTH, 64)), jnp.float32)
    s, f32, eps = exaone_moe._sizes(uncut), jnp.float32, 1e-5
    with jax.default_matmul_precision("highest"):
        want = reference.layer_forward(layer, x, S, first=0, **dict(SIZES, top_k=8))
        h = x + routed.rms_norm(layer["post_attn_norm"], exaone_moe.attention(layer["attn"], x, s, "window", f32, eps, THETA), eps)
        tokens = h.reshape(-1, 64)
        chosen, gates, _ = routed.route(layer["router"], tokens, 8, 2.5)
        ffn, given = routed.gated_mlp(layer["shared"], tokens, f32, 3), 0
        for share in range(16):  # experts 8 * share .. 8 * share + 7
            held = {k: w[8 * share:8 * share + 8] for k, w in layer["experts"].items()}
            part, loads, _ = routed.held_experts(held, tokens, chosen, gates, 8 * share, f32, block=16, count=3)
            ffn, given = ffn + part, given + int(loads.sum())
        got = h + routed.rms_norm(layer["post_ffn_norm"], ffn.reshape(x.shape), eps)
    assert given == tokens.shape[0] * 8
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=2e-5)
    # and one share alone is not the layer
    assert float(jnp.max(jnp.abs(h + routed.rms_norm(layer["post_ffn_norm"], part.reshape(x.shape), eps) - want))) > 0.1


def test_a_row_of_zero_weights_is_left_out_of_the_experts_and_every_counter_exactly():
    config = tiny_config(first_expert_held=0)
    model = build_model("exaone_moe", config)
    params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(4)), config)
    batch = rows(3, config)
    padded = {k: np.concatenate([v, np.zeros_like(v[:1])]) for k, v in batch.items()}
    step = jax.jit(model.apply_stats)
    (out, stats), (out_padded, stats_padded) = step(params, batch), step(params, padded)
    np.testing.assert_array_equal(np.asarray(out_padded["logits"][:3]), np.asarray(out["logits"]))
    assert stats_padded.tolist() == stats.tolist() and float(out_padded["logits"][3]) == 0.0


# ------------------------------------------------------------------ counters


def numpy_pairs(kinds, length, window, band_block, full_block):
    """(computed, seen) (query, key) pairs a row of the served step, counted
    pair by pair: every layer but the last at all positions in its kind's
    blocks, the last layer's one query against what it reads."""
    computed = seen = 0
    t = np.arange(length)
    for i, kind in enumerate(kinds):
        mask = t[None, :] <= t[:, None]
        if kind == S:
            mask &= t[:, None] - t[None, :] < window
        if i == len(kinds) - 1:
            computed, seen = computed + (min(window, length) if kind == S else length), seen + int(mask[-1].sum())
            continue
        seen += int(mask.sum())
        if kind == S:
            blocks = -(-length // band_block)
            back = -(-(window - 1) // band_block)
            computed += blocks * band_block * (back + 1) * band_block
        else:
            computed += sum((min(start + full_block, length) - start) * min(start + full_block, length)
                            for start in range(0, length, full_block))
    return computed, seen


def test_the_steps_counters_are_a_numpy_count():
    config = tiny_config()
    model = build_model("exaone_moe", config)
    params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(9)), config)
    _, stats = jax.jit(model.apply_stats)(params, rows(4, config))
    named = dict(zip(model.step_stats, stats.tolist()))
    assert model.step_stats == routed.STEP_STATS + ("attn.scores_computed", "attn.scores_seen")
    # three routed layers at all positions, the last at one
    assert named["moe.tokens"] == 4 * (3 * LENGTH + 1)
    assert 0 < named["moe.busiest_expert_tokens"] <= named["moe.assignments_here"] <= 4 * named["moe.tokens"]
    assert 0.5 < named["moe.assignments_here"] / named["moe.tokens"] < 1.5  # 4 x 4 / 16 under even routing
    computed, seen = numpy_pairs(config.layer_types, LENGTH, WINDOW, WINDOW, sequence.ATTN_BLOCK)
    assert (named["attn.scores_computed"], named["attn.scores_seen"]) == (4 * computed, 4 * seen)
    assert seen < computed
    # the routing's count on the same router scores, one layer
    layer = params["layers"][2]
    a = jnp.asarray(np.random.default_rng(2).standard_normal((4, LENGTH, 64)), jnp.float32)
    _, counts = jax.jit(lambda l, x: routed.routed_ffn(l, x, 4, 4, 2.5, jnp.float32, 3))(layer, a)
    _, _, scores = routed.route(layer["router"], a.reshape(-1, 64), 4, 2.5)
    top = np.argsort(-np.asarray(scores), axis=1)[:, :4]
    loads = [(top == e).sum() for e in range(4, 8)]
    assert counts.tolist() == [4 * LENGTH, sum(loads), max(loads), sum(-(-n // 256) * 256 for n in loads),
                               sum(n > 0 for n in loads)] and sum(loads) > 0


def test_the_published_rows_pairs_are_what_the_reader_will_divide():
    """2,048 positions, window 128, S S S F S: 4,194,432 pairs computed a row,
    2,860,352 kept: 31.8% masked, where 512-query blocks on the window layers
    would have computed 6,352,512 and masked 55%."""
    kinds = ("window", "window", "window", "full", "window")
    assert exaone_moe.step_pairs(("window", "full"), 2048, 128) == (2048 * 256 + 2048, 128 * 129 // 2 + 1920 * 128 + 2048)
    assert exaone_moe.step_pairs(("full", "window"), 2048, 128) == (2_621_440 + 128, 2048 * 2049 // 2 + 128)
    assert exaone_moe.step_pairs(kinds, 2048, 128) == (4_194_432, 2_860_352)
    assert exaone_moe.step_pairs(kinds, 2048, 128) == numpy_pairs((S, S, S, F, S), 2048, 128, 128, 512)
    as_today = sum((stop - start) * (last - first) for start, stop, first, last in sequence.query_blocks(2048, 2048, 128))
    assert as_today == 4 * 512 * 639 - 512 * 127  # the first block has no keys before it
    assert 3 * as_today + 2_621_440 + 128 == 6_352_512


# ---------------------------------------------------------------- precision


@pytest.fixture(scope="module")
def served_precision(reference):
    """bfloat16 weights and compute as served, rows twelve windows long, and
    the float32 reference's scores."""
    config = tiny_config(num_fields=96, compute_dtype="bfloat16", param_dtype="bfloat16")
    model = build_model("exaone_moe", config)
    params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(5)), config)
    batch = rows(8, config, seed=11)
    return model, params, batch, reference_scores(reference, params, batch, config)


def _worst(model, params, batch, want) -> float:
    got = np.asarray(jax.jit(lambda p, b: model.apply(p, b))(params, batch)["prediction_node"])
    return float(np.max(np.abs(got.astype(np.float64) - want)))


def test_three_piece_scores_within_the_benchmark_tolerance(served_precision, tolerance):
    model, params, batch, want = served_precision
    assert want.std() > 0.1  # scores that spread, or the comparison compares nothing
    assert exaone_moe.OPERAND_PIECES == 3 and _worst(model, params, batch, want) < tolerance / 3


def _one_piece(monkeypatch):
    """The nearest precision below the stated one: every activation rounded
    to bfloat16 where it enters a product."""
    monkeypatch.setattr(exaone_moe, "OPERAND_PIECES", 1)


def _every_layer_full(monkeypatch):
    attention = exaone_moe.attention
    monkeypatch.setattr(exaone_moe, "attention", lambda p, x, s, kind, *rest: attention(p, x, s, "full", *rest))


def _rotary_on_the_full_layer(monkeypatch):
    """The full layer as a sliding one whose window is the whole row: the same
    mask, and the rotary turn it should not have."""
    attention = exaone_moe.attention

    def turned(p, x, s, kind, *rest):
        return attention(p, x, s, kind, *rest) if kind == "window" else attention(
            p, x, dict(s, window=x.shape[1]), "window", *rest)

    monkeypatch.setattr(exaone_moe, "attention", turned)


def _norms_left_out(monkeypatch, which, layers=5):
    """Of a layer's four norms in the order the step calls them (query heads,
    key heads, post attention, post FFN) those in `which`; the final norm, the
    call after the last layer's, stays."""
    norm, calls = exaone_moe.rms_norm, []

    def planted(w, x, eps):
        calls.append(None)
        return x if (len(calls) - 1) % 4 in which and len(calls) <= 4 * layers else norm(w, x, eps)

    monkeypatch.setattr(exaone_moe, "rms_norm", planted)


def _head_norms_left_out(monkeypatch):
    _norms_left_out(monkeypatch, (0, 1))


def _post_norms_left_out(monkeypatch):
    _norms_left_out(monkeypatch, (2, 3))


def _top_7(monkeypatch):
    """One choice fewer than the configuration states."""
    route = routed.route
    monkeypatch.setattr(routed, "route", lambda router, x, k, scaling: route(router, x, k - 1, scaling))


def _an_expert_dropped(monkeypatch):
    """The last held expert's part left out of the routed sum."""
    whole = routed.held_experts

    def without_the_last(p, *args, **kwargs):
        return whole({name: w[:-1] for name, w in p.items()}, *args, **kwargs)

    monkeypatch.setattr(routed, "held_experts", without_the_last)


@pytest.mark.parametrize("plant,factor", [
    (_one_piece, 3), (_every_layer_full, 10), (_rotary_on_the_full_layer, 10), (_head_norms_left_out, 10),
    (_top_7, 10), (_an_expert_dropped, 10), (_post_norms_left_out, 10)],
    ids=["one-piece operands", "every layer full", "rotary on the full layer", "no head norms", "top-7",
         "an expert dropped", "no post norms"])
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

    cfgs = load_config(os.path.join(ROOT, "configs", "exaone_moe_small.toml"))
    config = dataclasses.replace(cfgs["model"], name="M")
    cfg = dataclasses.replace(cfgs["server"], model_name="M", warmup=False)
    _registry, batcher, impl, servable, _mesh, _watcher = build_stack(cfg, model_config=config)
    yield batcher, impl, servable
    batcher.stop()


def _step_phases() -> dict:
    from distributed_tf_serving_tpu.utils.tracing import request_trace

    return {k: v["count"] for k, v in request_trace.snapshot().items() if k.startswith(("moe.", "attn."))}


def test_a_request_through_the_batchers_entry_scores_like_the_reference(served, reference, tolerance):
    """configs/exaone_moe_small.toml down the served path: 3 rows pad to the
    bucket of 4; ids travel as u24 and weights as float32; the step's five
    counters come back with the scores and are recorded by count, the padded
    row in none of them."""
    batcher, _impl, servable = served
    config = servable.model.config
    arrays = rows(3, config, folded=False)
    before = _step_phases()
    got = batcher.submit(servable, arrays).result(timeout=300)
    assert set(got) == {"prediction_node", "logits"} and type(got["prediction_node"]) is np.ndarray
    batch = dict(arrays, feat_ids=(arrays["feat_ids"] % config.vocab_size).astype(np.int32))
    sizes = {"layer_types": config.layer_types, "first": config.first_expert_held,
             "top_k": config.num_experts_per_tok, "scaling": config.routed_scaling_factor,
             "window": config.sliding_window, "head": config.head_dim, "theta": config.rope_theta}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.forward(p, b, **sizes))(servable.params, batch))
    assert got["prediction_node"].shape == (3,) and batcher.compress_transfer
    assert np.max(np.abs(got["prediction_node"] - want)) < tolerance
    after = _step_phases()
    _, alone = jax.jit(servable.model.apply_stats)(servable.params, batch)  # the 3 rows with no padding
    assert [after[name] - before.get(name, 0) for name in servable.model.step_stats] == alone.tolist()
    assert after["moe.tokens"] - before.get("moe.tokens", 0) == 3 * (3 * config.num_fields + 1)
    named = dict(zip(servable.model.step_stats, alone.tolist()))
    assert named["moe.busiest_expert_tokens"] > 0 and 0 < named["attn.scores_seen"] < named["attn.scores_computed"]


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


def test_runtime_block_reports_the_attention_plan(served):
    batcher, impl, servable = served
    batcher.submit(servable, rows(2, servable.model.config, folded=False)).result(timeout=300)
    startup = impl.runtime_stats()["startup"]
    assert startup["layer_plan"] == {"M:1": {"window/dense": 1, "window/moe": 3, "full/moe": 1}}
    window = {"kind": "window", "window": 16, "block": 16, "keys_a_block": 32}
    assert startup["attention_plan"] == {"M:1": [
        window, window, window, {"kind": "full", "window": 0, "block": 80, "keys_a_block": 80}, window]}
    assert startup["expert_plan"] == {"M:1": {
        "published": 32, "held": 4, "first": 8, "top_k": 4, "heads_published": 8, "heads_held": 8,
        "chips_sharing_layer": 8}}
    assert startup["assembler"] == {"M:1": "native"} or not native.available()
    assert "feat_ids int32/24b" in startup["upload_format"]["M:1"]


def test_shadow_verification_counts_a_batch_once(served):
    """With the integrity plane's shadow execution on, the step runs twice
    over a batch and its counters, the attention's too, are recorded once."""
    from distributed_tf_serving_tpu.utils.config import IntegrityConfig

    batcher, _impl, servable = served
    arrays = rows(2, servable.model.config, seed=6, folded=False)
    batcher.submit(servable, arrays).result(timeout=300)
    once = _step_phases()
    plain = batcher.submit(servable, arrays).result(timeout=300)
    twice = _step_phases()
    plane = IntegrityConfig(enabled=True, shadow_fraction=1.0).build()
    batcher.integrity = plane
    try:
        shadowed = batcher.submit(servable, arrays).result(timeout=300)
    finally:
        batcher.integrity = None
    thrice = _step_phases()
    shadow = plane.snapshot()["shadow"]
    assert shadow["batches"] == 1 and shadow["mismatches"] == 0
    np.testing.assert_array_equal(shadowed["prediction_node"], plain["prediction_node"])
    assert all(twice[k] - once[k] == thrice[k] - twice[k] > 0 for k in servable.model.step_stats)


def test_no_attention_plan_for_a_family_whose_layers_are_alike():
    from distributed_tf_serving_tpu.models import Servable, ctr_signatures

    config = ModelConfig(num_fields=5, vocab_size=64, embed_dim=4, mlp_dims=(8,))
    model = build_model("dcn_v2", config)
    servable = Servable("D", 1, model, model.init(jax.random.PRNGKey(0)), ctr_signatures(5))
    assert servable.attention_plan is None and model.attention_plan == ()
    cfgs = load_config(os.path.join(ROOT, "configs", "pangu_moe_small.toml"))
    assert build_model("pangu_moe", cfgs["model"]).attention_plan == ()


# ------------------------------------------------------- the published shapes


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        shape = json.load(f)["toml"]["model"]
    return ModelConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in shape.items()})


def test_plan_and_parameter_count_at_the_published_cut(published):
    """By `jax.eval_shape`: nothing of the 2.386 B parameters is made."""
    model = build_model("exaone_moe", published)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    size = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))  # noqa: E731
    assert model.layer_plan == ("window/dense", "window/moe", "window/moe", "full/moe", "window/moe")
    assert dict(model.expert_plan) == {
        "published": 128, "held": 8, "first": 0, "top_k": 8, "heads_published": 64, "heads_held": 64,
        "chips_sharing_layer": 16}
    assert [dict(layer) for layer in model.attention_plan][2:4] == [
        {"kind": "window", "window": 128, "block": 128, "keys_a_block": 256},
        {"kind": "full", "window": 0, "block": 512, "keys_a_block": 2048}]
    attn = shapes["layers"][3]["attn"]
    assert (attn["q"].shape, attn["k"].shape, attn["v"].shape, attn["o"].shape) == (
        (6144, 8192), (6144, 1024), (6144, 1024), (8192, 6144))
    assert attn["q_norm"].shape == attn["k_norm"].shape == (128,)
    assert round(size(attn) / 1e4) == 11325 and round(size(shapes["layers"][0]["mlp"]) / 1e5) == 3397
    assert round(size(shapes["layers"][1]) / 1e5) == 4538  # 113.25 attention + 340.5 routed
    assert shapes["embedding"].shape == (19200, 6144) and shapes["layers"][1]["router"].shape == (6144, 128)
    assert shapes["layers"][1]["experts"]["gate"].shape == (8, 6144, 2048)
    assert shapes["layers"][0]["mlp"]["gate"].shape == (6144, 18432)
    assert round(size(shapes) / 1e6) == 2386 and {x.dtype for x in jax.tree.leaves(shapes)} == {jnp.dtype("bfloat16")}


@pytest.mark.parametrize("overrides,match", [
    ({"layer_types": (S, S, S, F)}, "layer_types"),
    ({"layer_types": (S, S, S, F, "linear_attention")}, "layer_types"),
    ({"head_dim": 15}, "head_dim"),
    ({"head_dim": 0, "embed_dim": 56}, "head_dim"),  # 56 / 8 heads
    ({"num_key_value_heads": 3}, "num_key_value_heads"),
    ({"sliding_window": 0}, "sliding_window"),
    ({"num_experts": 0}, "num_experts"),
    ({"num_experts_per_tok": 17}, "num_experts_per_tok"),
    ({"experts_held": 5}, "experts_held"),
    ({"first_expert_held": 14}, "experts_held"),
    ({"first_k_dense_replace": 6}, "first_k_dense_replace"),
])
def test_a_share_or_a_plan_the_stack_cannot_be_built_from_is_refused(overrides, match):
    with pytest.raises(ValueError, match=match):
        build_model("exaone_moe", tiny_config(**overrides))


def test_keys_left_out_take_the_published_pattern_and_the_usual_head():
    model = build_model("exaone_moe", tiny_config(layer_types=(), head_dim=0, num_hidden_layers=6, first_k_dense_replace=0))
    assert [kind for kind, _ in exaone_moe.layer_plan(model.config)] == ["window"] * 3 + ["full"] + ["window"] * 2
    assert jax.eval_shape(model.init, jax.random.PRNGKey(0))["layers"][0]["attn"]["q_norm"].shape == (64 // 8,)


def test_toml_reads_the_published_keys(tmp_path):
    cfgs = load_config(os.path.join(ROOT, "configs", "exaone_moe_small.toml"))
    model = build_model(cfgs["server"].model_kind, cfgs["model"])
    assert model.kind == "exaone_moe" and not model.takes_dense and not model.wts_in_compute_dtype
    assert cfgs["server"].num_fields == cfgs["model"].num_fields
    assert len(model.layer_plan) == cfgs["model"].num_hidden_layers == len(cfgs["model"].layer_types)
    (tmp_path / "s.toml").write_text('[model]\nlayer_type = ["full_attention"]\n')
    with pytest.raises(ValueError, match="unknown ModelConfig keys"):
        load_config(str(tmp_path / "s.toml"))


# ------------------------------------- what the move into models/routed.py left


# The logits the 4-row step at the small TOML's sizes gave on this container's
# CPU for rows drawn at seed 5, at the commit BEFORE the routed layer moved out
# of pangu_moe.py (PR 42's tree). PR 43 held the lowered programs to that
# tree's by sha256; since PR 44 a product of pieces is one product
# (models/sequence.py::product), so the programs differ and the float32
# partial sums meet in another order: the logits move in their last bits
# (4.3e-6 relative at most, 9.8e-7 absolute), inside the limits below.
# `exaone_moe_small`: this family's own step at PR 44's tree, held since PR 46
# moved its full layers' blocks into `sequence.blocked_attention` (and
# phi4flash's convolution into `sequence.causal_conv`): the lines moved, the
# programs did not.
PARENTS_LOGITS = {
    "exaone_moe_small": ["-0x1.50c5640000000p-2", "-0x1.ad16e40000000p-3", "-0x1.62eef60000000p-3", "-0x1.f22f7c0000000p-3"],
    "pangu_moe_small": ["-0x1.3a46520000000p-2", "0x1.5d61040000000p-2", "-0x1.0209e20000000p-2", "0x1.377f260000000p-3"],
    "phi4flash_small": ["0x1.b9a12c0000000p-2", "0x1.5610340000000p-3", "0x1.7298ba0000000p-2", "0x1.d0b9ec0000000p-3"],
}


@pytest.mark.parametrize("name", sorted(PARENTS_LOGITS))
def test_the_other_sequence_families_steps_give_the_parents_logits(name):
    cfgs = load_config(os.path.join(ROOT, "configs", name + ".toml"))
    config = cfgs["model"]
    model = build_model(cfgs["server"].model_kind, config)
    rng = np.random.default_rng(5)
    drawn = {"feat_ids": rng.integers(0, config.vocab_size, (4, config.num_fields)).astype(np.int32),
             "feat_wts": rng.random((4, config.num_fields), dtype=np.float32)}
    logits = np.asarray(jax.jit(model.apply)(jax.jit(model.init)(jax.random.PRNGKey(0)), drawn)["logits"])
    np.testing.assert_allclose(logits, [float.fromhex(x) for x in PARENTS_LOGITS[name]], rtol=1e-5, atol=1e-6)
