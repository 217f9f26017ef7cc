"""The falcon_h1 family (Falcon-H1-34B-Instruct as a pointwise sequence ranker:
every layer a Mamba-2 mixer and grouped-query attention side by side on one
normed input, under the model's twelve multipliers) at tiny widths on the CPU:
against the benchmark's plain reference through `model.apply` and down the
served path, the chunked SSD against the position-by-position loop, a row
split with its state handed over, the extremes of decay, the last-position
cut of both mixers, a padded row, what the benchmark's tolerance catches (each
multiplier, each piece of the mixer, the precisions below), the step's
counters and how they reach `/monitoring`, the plan and the `startup.ssd`
and `startup.conv` stamps, the mixer through its two kernels interpreted (the
convolution's, which reads the projection where it lies, and the SSD's, which
reads x, B and C as windows of the convolution's one array), the shapes at the
published cut, and the configurations that are refused."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tf_serving_tpu import native
from distributed_tf_serving_tpu.models import ModelConfig, build_model, falcon_h1, sequence
from distributed_tf_serving_tpu.utils.config import load_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "falcon_h1_34b_rerank")
LENGTH, CHUNK = 40, 8  # five chunks; other lengths below are no multiple of the chunk
HEADS, WIDTH, STATE, GROUPS = 4, 16, 32, 2  # the mixer's
MULTIPLIERS = {  # none of them 1: one left out shows
    "embedding_multiplier": 5.6, "attention_in_multiplier": 0.8, "attention_out_multiplier": 0.5,
    "key_multiplier": 0.4, "ssm_in_multiplier": 0.6, "ssm_out_multiplier": 0.7,
    "ssm_multipliers": (0.9, 0.8, 0.7, 1.2, 0.6), "mlp_multipliers": (0.5, 0.3),
}


def tiny_config(**overrides) -> ModelConfig:
    return ModelConfig(**{
        "name": "M", "num_fields": LENGTH, "vocab_size": 1000, "embed_dim": 64, "intermediate_size": 96,
        "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "rope_theta": 1e4, "layer_norm_eps": 1e-5, "mamba_d_ssm": HEADS * WIDTH, "mamba_n_heads": HEADS,
        "mamba_d_head": WIDTH, "mamba_d_state": STATE, "mamba_n_groups": GROUPS, "mamba_d_conv": 4,
        "mamba_chunk_size": CHUNK, "compute_dtype": "float32", **MULTIPLIERS, **overrides,
    })


def rows(n: int, config: ModelConfig, seed: int = 3, folded: bool = True) -> dict:
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 1 << 40, size=(n, config.num_fields), dtype=np.int64)
    return {
        "feat_ids": (ids % config.vocab_size).astype(np.int32) if folded else ids,
        "feat_wts": rng.random((n, config.num_fields), dtype=np.float32),
    }


def unit_gain(params, config: ModelConfig, seed: int = 0, published: int = 5120):
    """The tree with its matrices scaled so that a product keeps a unit input
    at the size it has at the published width (gates, `dt` and a score logit
    that spread, not ones that sit at their middle), and every norm weight and
    `D` drawn around 1, so that a norm or the skip left out or misplaced
    shows."""
    gain = (published / config.embed_dim) ** 0.5
    rng = np.random.default_rng(seed)

    def scale(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if name == "embedding" or name.startswith("conv") or name in ("A_log", "dt_bias"):
            return leaf
        if name.endswith("norm") or name == "D":
            return (leaf * (1.0 + 0.2 * rng.standard_normal(leaf.shape))).astype(leaf.dtype)
        return (leaf.astype(jnp.float32) * gain).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(scale, params)


def load(name: str):
    spec = importlib.util.spec_from_file_location(f"falcon_{name}", os.path.join(CONFIG_DIR, name + ".py"))
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


def reference_sizes(config: ModelConfig) -> dict:
    """reference.py's keyword arguments from the served configuration."""
    return {
        "head": config.head_dim, "ssm_head": config.mamba_d_head, "groups": config.mamba_n_groups,
        "theta": config.rope_theta, "eps": config.layer_norm_eps,
        **{name: getattr(config, name) for name in MULTIPLIERS},
    }


def reference_scores(reference, params, batch, config, what="forward"):
    sizes = reference_sizes(config)
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p, b: getattr(reference, what)(p, b, **sizes))(params, batch))


def ssd_inputs(n, length, seed=0):
    """x, dt > 0, a < 0 (a head whose state lasts the row beside one that
    forgets inside a chunk), B and C of the SSD, float32."""
    rng = np.random.default_rng(seed)
    draw = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (n, length, HEADS))).astype(np.float32)
    a = -np.asarray([0.05, 1.0, 4.0, 16.0], np.float32)
    return draw(n, length, HEADS, WIDTH), dt, a, draw(n, length, GROUPS, STATE), draw(n, length, GROUPS, STATE)


def ssd_by_position(x, dt, a, b, c, state=None):
    """S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t, y_t = S_t C_t, in
    float64, a position at a time; head h reads group h // (H / G)."""
    x, dt, a, b, c = (np.asarray(v, np.float64) for v in (x, dt, a, b, c))
    n, length, heads, width = x.shape
    b, c = (np.repeat(v, heads // v.shape[2], axis=2) for v in (b, c))
    state = np.zeros((n, heads, width, b.shape[-1])) if state is None else np.asarray(state, np.float64)
    out = []
    for t in range(length):
        state = np.exp(dt[:, t] * a)[..., None, None] * state
        state = state + (dt[:, t][..., None] * x[:, t])[..., :, None] * b[:, t][..., None, :]
        out.append(np.einsum("nhps,nhs->nhp", state, c[:, t]))
    return np.stack(out, axis=1), state


# ------------------------------------------------- the family and the reference


@pytest.mark.parametrize("layers,length,chunk,limit", [
    (3, 40, 8, 2e-5), (1, 5, 8, 2e-5), (2, 16, 8, 2e-5), (5, 130, 64, 1e-4), (2, 33, 1, 2e-5), (4, 75, 128, 3e-5)])
def test_float32_logits_match_the_plain_reference(reference, layers, length, chunk, limit):
    """Through `model.apply`; the reference computes every layer at every
    position and the mixer a position at a time, the family the SSD in chunks
    and what follows the last layer's mixing at the last position alone:
    exact at a length that is no multiple of the chunk, at one that is, where
    the row is shorter than a chunk, at a chunk of one position and over
    several chunks."""
    config = tiny_config(num_hidden_layers=layers, num_fields=length, mamba_chunk_size=chunk)
    model = build_model("falcon_h1", config)
    params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(7)), config)
    batch = rows(5, config)
    want = reference_scores(reference, params, batch, config, "logits")
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(params, batch)["logits"])
    assert want.shape == got.shape == (5,) and want.std() > 0.3
    assert np.max(np.abs(want - got)) < limit


@pytest.mark.parametrize("mixer", ["ssm", "attention"])
def test_the_last_layers_cut_is_the_whole_layers_last_position(reference, mixer):
    config = tiny_config()
    s = falcon_h1._sizes(config)
    layer = unit_gain(jax.jit(build_model("falcon_h1", config).init)(jax.random.PRNGKey(2)), config)["layers"][1]
    x = jnp.asarray(np.random.default_rng(0).standard_normal((3, LENGTH, 64)), jnp.float32)
    m = dict(reference.PUBLISHED, **reference_sizes(config))
    if mixer == "ssm":
        mix, plain, p = (lambda p, x, **kw: falcon_h1.ssm(p, x, s, jnp.float32, 1e-5, **kw)), reference.ssm, layer["ssm"]
    else:
        mix, plain, p = (lambda p, x, **kw: falcon_h1.attention(p, x, s, jnp.float32, **kw)), reference.attention, layer["attn"]
    with jax.default_matmul_precision("highest"):
        whole, last = jax.jit(lambda p, x: (mix(p, x), mix(p, x, last_only=True)))(p, x)
        want = jax.jit(lambda p, x: plain(p, x, m))(p, x)
    assert last.shape == (3, 1, 64) and float(jnp.std(whole)) > 1e-2
    np.testing.assert_allclose(np.asarray(last), np.asarray(whole[:, -1:]), rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), rtol=1e-4, atol=1e-5)


# ----------------------------------------------------------------- the SSD


@pytest.mark.parametrize("length", [5, 8, 40, 75])
@pytest.mark.parametrize("chunk", [1, 8, 64, 128])
def test_the_chunked_ssd_is_the_position_by_position_loop(length, chunk):
    """At rows under a chunk, of one chunk exactly, of several and of several
    and a part, at chunks of 1 (the loop itself), the tests' 8, 64 and the
    published 128: outputs and the last state; and the last position alone
    (`last_only`), which makes the hand-overs and none of the chunks' own
    products."""
    x, dt, a, b, c = ssd_inputs(2, length)
    want, state = ssd_by_position(x, dt, a, b, c)
    arrays = [jnp.asarray(v) for v in (x, dt, a, b, c)]
    with jax.default_matmul_precision("highest"):
        (got, last), (only, last_too) = jax.jit(lambda *v: (
            falcon_h1.ssd(*v, chunk=chunk), falcon_h1.ssd(*v, chunk=chunk, last_only=True)))(*arrays)
    assert got.shape == (2, length, HEADS, WIDTH) and last.shape == (2, HEADS, WIDTH, STATE)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(last), state, rtol=2e-4, atol=2e-5)
    assert only.shape == (2, 1, HEADS, WIDTH)
    np.testing.assert_allclose(np.asarray(only), want[:, -1:], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(last_too), state, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("cut", [1, 8, 13, 32, 39])
def test_a_row_split_anywhere_with_its_state_handed_over_is_the_whole_row(cut):
    arrays = [jnp.asarray(v) for v in ssd_inputs(2, LENGTH, seed=cut)]
    split = lambda lo, hi: [v if v.ndim == 1 else v[:, lo:hi] for v in arrays]  # noqa: E731 - `a` is a head's, not a position's
    ssd = jax.jit(lambda *v: falcon_h1.ssd(*v, chunk=CHUNK))
    with jax.default_matmul_precision("highest"):
        whole, state = ssd(*arrays)
        head, handed = ssd(*split(0, cut))
        tail, last = ssd(*split(cut, LENGTH), handed)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([head, tail], axis=1)), np.asarray(whole), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(last), np.asarray(state), rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(handed))) > 0.01  # a state worth handing over


@pytest.mark.parametrize("a,dt", [(-16.0, 30.0), (-1e4, 1.0), (0.0, 1.0), (-1e-6, 1e-3), (-16.0, 0.0)])
def test_decays_near_zero_and_near_one_stay_finite(a, dt):
    """exp(-cum_j) alone overflows float32 past a running sum of 88: every
    exponent the SSD takes is a difference under its mask. A decay of 1 keeps
    everything; dt = 0 feeds nothing and forgets nothing."""
    x, _, _, b, c = ssd_inputs(1, 130, seed=5)
    dts, heads = np.full((1, 130, HEADS), dt, np.float32), np.full((HEADS,), a, np.float32)
    want, state = ssd_by_position(x, dts, heads, b, c)
    with jax.default_matmul_precision("highest"):
        got, last = jax.jit(lambda *v: falcon_h1.ssd(*v, chunk=64))(*map(jnp.asarray, (x, dts, heads, b, c)))
    assert np.isfinite(np.asarray(got)).all() and np.isfinite(np.asarray(last)).all()
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(last), state, rtol=1e-3, atol=1e-3)


def test_a_row_of_zero_weights_is_in_no_counter_and_moves_no_other_row():
    config = tiny_config()
    model = build_model("falcon_h1", config)
    params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(4)), config)
    batch = rows(3, config)
    padded = {k: np.concatenate([v, np.zeros_like(v[:1])]) for k, v in batch.items()}
    step = jax.jit(model.apply_stats)
    (out, stats), (out_padded, stats_padded) = step(params, batch), step(params, padded)
    np.testing.assert_array_equal(np.asarray(out_padded["logits"][:3]), np.asarray(out["logits"]))
    assert stats_padded.tolist() == stats.tolist() and np.isfinite(float(out_padded["logits"][3]))


# ------------------------------------------------------------------ counters


@pytest.mark.parametrize("layers,length,chunk,live", [(3, 40, 8, 4), (1, 5, 8, 2), (2, 130, 64, 3), (2, 128, 128, 1)])
def test_the_steps_counters_are_a_numpy_count(layers, length, chunk, live):
    """`ssd.handovers` = layers x ceil(L / chunk) x live rows; every layer
    but the last attends at all positions (a tile of the whole row here, the
    causal half kept), the last with one query."""
    config = tiny_config(num_hidden_layers=layers, num_fields=length, mamba_chunk_size=chunk)
    model = build_model("falcon_h1", config)
    params = jax.jit(model.init)(jax.random.PRNGKey(9))
    batch = rows(live + 1, config)
    batch["feat_wts"][live:] = 0.0  # a padded row
    _, stats = jax.jit(model.apply_stats)(params, batch)
    assert model.step_stats == ("attn.scores_computed", "attn.scores_seen", "ssd.rows", "ssd.handovers", "ssd.positions")
    assert stats.tolist() == [
        live * ((layers - 1) * length * length + length), live * ((layers - 1) * length * (length + 1) // 2 + length),
        live, live * layers * -(-length // chunk), live * layers * length]


def test_the_published_rows_counts_are_what_the_readers_will_divide():
    """2,048 positions, five layers, chunks of 128: 80 hand-overs a row; four
    layers in blocks of 512 compute 2,621,440 pairs each and keep 2,098,176,
    the last one's query 2,048: 20.0% masked."""
    assert falcon_h1.step_counts(5, 2048, 128) == (4 * 2_621_440 + 2048, 4 * (2048 * 2049 // 2) + 2048, 1, 80, 5 * 2048)
    assert falcon_h1.ssd_chunks(2048, 128) == (128, 16) and falcon_h1.ssd_chunks(150, 64) == (64, 3)
    assert falcon_h1.ssd_chunks(40, 128) == (40, 1)


# ---------------------------------------------------------------- precision


@pytest.fixture(scope="module")
def served_precision(reference):
    """bfloat16 weights and compute as served, the published multipliers (but
    the attention's input, 1 as published and 0.5 here, so that it too can be
    left out), rows of two chunks and a part, and the float32 reference's
    scores. The tree is `unit_gain`'s, with the heads' decays drawn SLOWER
    (`A` a tenth of the init's) and `D` smaller: at Mamba-2's own init most
    heads forget inside a chunk and `D x` outweighs what the state gives, and
    a state carried in bfloat16 from chunk to chunk then moves the score by a
    tenth of the limit; the limit is the published width's."""
    config = tiny_config(
        num_fields=72, compute_dtype="bfloat16", param_dtype="bfloat16", num_hidden_layers=2, embed_dim=256,
        intermediate_size=512, num_attention_heads=8, num_key_value_heads=2, head_dim=32, rope_theta=1e11,
        mamba_d_ssm=256, mamba_n_heads=8, mamba_d_head=32, mamba_d_state=64, mamba_chunk_size=32,
        embedding_multiplier=5.656854249492381, attention_in_multiplier=0.5, attention_out_multiplier=0.0375,
        key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25, ssm_out_multiplier=0.08838834764831845,
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738),
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284))
    model = build_model("falcon_h1", config)
    params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(5)), config)
    for layer in params["layers"]:
        layer["ssm"]["A_log"] = (layer["ssm"]["A_log"].astype(jnp.float32) + np.log(0.1)).astype(jnp.bfloat16)
        layer["ssm"]["D"] = layer["ssm"]["D"] * jnp.asarray(0.1, jnp.bfloat16)
    batch = rows(8, config, seed=11)
    return model, params, batch, reference_scores(reference, params, batch, config)


def _worst(model, params, batch, want) -> float:
    got = np.asarray(jax.jit(lambda p, b: model.apply(p, b))(params, batch)["prediction_node"])
    worst = float(np.max(np.abs(got.astype(np.float64) - want)))
    return worst if np.isfinite(worst) else np.inf  # a score that is no number misses by any limit


def test_two_piece_scores_within_the_benchmark_tolerance(served_precision, tolerance):
    model, params, batch, want = served_precision
    assert want.std() > 0.1  # scores that spread, or the comparison compares nothing
    assert falcon_h1.OPERAND_PIECES == 2 and falcon_h1.STATE_DTYPE == jnp.float32
    assert _worst(model, params, batch, want) < tolerance / 3


def _multiplier_left_out(name, index=None):
    """The multiplier taken for 1."""
    def plant(monkeypatch, model):
        value = 1.0
        if index is not None:
            value = tuple(1.0 if i == index else m for i, m in enumerate(getattr(model.config, name)))
        return build_model("falcon_h1", dataclasses.replace(model.config, **{name: value}))
    return plant


def _the_slices_in_another_order(monkeypatch, model):
    """The five multipliers read dt, C, B, x, z."""
    monkeypatch.setattr(falcon_h1, "slice_multipliers",
                        lambda s: np.repeat(np.asarray(s["ssm_mults"][::-1], np.float32), s["widths"]))


def _the_convolutions_bias_left_out(monkeypatch, model):
    conv = sequence.causal_conv
    monkeypatch.setattr(sequence, "causal_conv", lambda x, w, b=None: conv(x, w))


def _the_skip_left_out(monkeypatch, model):
    monkeypatch.setattr(falcon_h1, "skip", lambda p, y, x: y)


def _the_norm_before_the_gate(monkeypatch, model):
    """`mamba_norm_before_gate` taken for true."""
    def planted(p, y, z, s, eps):
        grouped = y.reshape(y.shape[:-1] + (s["groups"], -1))
        grouped = grouped * jax.lax.rsqrt(jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
        return grouped.reshape(y.shape) * p["norm"].astype(jnp.float32) * jax.nn.silu(z)

    monkeypatch.setattr(falcon_h1, "gated_norm", planted)


def _one_norm_group_for_two(monkeypatch, model):
    norm = falcon_h1.gated_norm
    monkeypatch.setattr(falcon_h1, "gated_norm", lambda p, y, z, s, eps: norm(p, y, z, dict(s, groups=1), eps))


def _rotary_left_out(monkeypatch, model):
    monkeypatch.setattr(falcon_h1, "rotate", lambda x, cos, sin, width=None: x)


def _dt_bias_left_out(monkeypatch, model):
    monkeypatch.setattr(falcon_h1, "time_steps", lambda p, dt: jax.nn.softplus(dt))


def _the_other_groups_b_and_c(monkeypatch, model):
    ssd = falcon_h1.ssd
    monkeypatch.setattr(falcon_h1, "ssd",
                        lambda x, dt, a, b, c, *rest, **kw: ssd(x, dt, a, b[:, :, ::-1], c[:, :, ::-1], *rest, **kw))


def _a_bfloat16_state(monkeypatch, model):
    monkeypatch.setattr(falcon_h1, "STATE_DTYPE", jnp.bfloat16)


def _one_piece(monkeypatch, model):
    """The nearest precision below the stated one: every activation rounded
    to bfloat16 where it enters a product."""
    monkeypatch.setattr(falcon_h1, "OPERAND_PIECES", 1)


FAULTS = {
    "embedding_multiplier": (_multiplier_left_out("embedding_multiplier"), 10),
    "attention_in_multiplier": (_multiplier_left_out("attention_in_multiplier"), 10),
    "attention_out_multiplier": (_multiplier_left_out("attention_out_multiplier"), 10),
    "key_multiplier": (_multiplier_left_out("key_multiplier"), 10),
    "ssm_in_multiplier": (_multiplier_left_out("ssm_in_multiplier"), 10),
    "ssm_out_multiplier": (_multiplier_left_out("ssm_out_multiplier"), 10),
    "ssm_multipliers z": (_multiplier_left_out("ssm_multipliers", 0), 10),
    "ssm_multipliers x": (_multiplier_left_out("ssm_multipliers", 1), 10),
    "ssm_multipliers B": (_multiplier_left_out("ssm_multipliers", 2), 10),
    "ssm_multipliers C": (_multiplier_left_out("ssm_multipliers", 3), 10),
    "ssm_multipliers dt": (_multiplier_left_out("ssm_multipliers", 4), 10),
    "mlp_multipliers gate": (_multiplier_left_out("mlp_multipliers", 0), 10),
    "mlp_multipliers down": (_multiplier_left_out("mlp_multipliers", 1), 10),
    "the slices in another order": (_the_slices_in_another_order, 10),
    "no convolution bias": (_the_convolutions_bias_left_out, 10),
    "no D x": (_the_skip_left_out, 10),
    "norm before gate": (_the_norm_before_the_gate, 10),
    "one norm group for two": (_one_norm_group_for_two, 10),
    "rotary off": (_rotary_left_out, 10),
    "dt_bias off": (_dt_bias_left_out, 10),
    "B and C of the wrong group": (_the_other_groups_b_and_c, 10),
    "a bfloat16 state": (_a_bfloat16_state, 1),
    "one-piece operands": (_one_piece, 3),
}


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_what_the_tolerance_refuses(served_precision, tolerance, monkeypatch, name):
    """Each fault is planted here, not in the program, and misses the
    float32 reference by more than its factor times the benchmark's limit."""
    model, params, batch, want = served_precision
    plant, factor = FAULTS[name]
    model = plant(monkeypatch, model) or model
    assert _worst(model, params, batch, want) > factor * tolerance


# ------------------------------------------------------------ the served path


@pytest.fixture(scope="module")
def served():
    from distributed_tf_serving_tpu.serving.server import build_stack

    cfgs = load_config(os.path.join(ROOT, "configs", "falcon_h1_small.toml"))
    config = dataclasses.replace(cfgs["model"], name="M")
    cfg = dataclasses.replace(cfgs["server"], model_name="M", warmup=False)
    _registry, batcher, impl, servable, _mesh, _watcher = build_stack(cfg, model_config=config)
    yield batcher, impl, servable
    batcher.stop()


def _step_phases() -> dict:
    from distributed_tf_serving_tpu.utils.tracing import request_trace

    return {k: v["count"] for k, v in request_trace.snapshot().items() if k.startswith(("ssd.", "attn."))}


def test_a_request_through_the_batchers_entry_scores_like_the_reference(served, reference, tolerance):
    """configs/falcon_h1_small.toml down the served path: 3 rows pad to the
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
    want = reference_scores(reference, servable.params, batch, config)
    assert got["prediction_node"].shape == (3,) and batcher.compress_transfer
    assert np.max(np.abs(got["prediction_node"] - want)) < tolerance
    after = _step_phases()
    delta = {name: after[name] - before.get(name, 0) for name in servable.model.step_stats}
    # 150 positions, 4 layers: 3 hand-overs a row and layer; three layers' whole tiles and the last's one query
    assert delta == {"attn.scores_computed": 3 * (3 * 150 * 150 + 150), "attn.scores_seen": 3 * (3 * 150 * 151 // 2 + 150),
                     "ssd.rows": 3, "ssd.handovers": 3 * 4 * 3, "ssd.positions": 3 * 4 * 150}


def test_a_request_through_the_interpreted_kernels_scores_like_the_reference(reference, tolerance, monkeypatch):
    """The same request through an entry traced as on a TPU, its kernels
    interpreted: the attention's at 6 query heads over 2 key-value heads, 3 a
    group, a grouping no other family has, and the SSD's chunk walk
    (ops/ssd_kernel.py) in every layer but the last, whose hand-overs stay
    XLA's scan; the `startup.ssd` stamp names the kernel's path of the two the
    entry noted, and the batch is counted once under `batch.ssd_kernel`."""
    import functools

    from distributed_tf_serving_tpu.serving import batcher as batcher_mod
    from distributed_tf_serving_tpu.serving.server import build_stack
    from distributed_tf_serving_tpu.utils.tracing import request_trace

    monkeypatch.setattr(batcher_mod, "serving_attention", functools.partial(sequence.serving_attention, interpret=True))
    cfgs = load_config(os.path.join(ROOT, "configs", "falcon_h1_small.toml"))
    config = dataclasses.replace(cfgs["model"], name="M")
    cfg = dataclasses.replace(cfgs["server"], model_name="M", warmup=False)
    _registry, batcher, impl, servable, _mesh, _watcher = build_stack(cfg, model_config=config)
    counted = lambda: request_trace.snapshot().get("batch.ssd_kernel", {}).get("count", 0)  # noqa: E731
    try:
        arrays, before = rows(3, config, folded=False), counted()
        got = batcher.submit(servable, arrays).result(timeout=600)
        startup = impl.runtime_stats()["startup"]
    finally:
        batcher.stop()
    batch = dict(arrays, feat_ids=(arrays["feat_ids"] % config.vocab_size).astype(np.int32))
    want = reference_scores(reference, servable.params, batch, config)
    assert np.max(np.abs(got["prediction_node"] - want)) < tolerance
    assert startup["attention"]["M:1"]["kernel"] == "pallas" and batcher.stats.attention_kernel_batches == 1
    assert startup["ssd"] == {"M:1": {"path": "pallas", "chunk": 64, "state_bytes_a_row": 8 * 16 * 32 * 4,
                                     "heads": [8, 16, 32]}}
    assert batcher.stats.batches == 1 and batcher.stats.ssd_kernel_batches == 1 and counted() - before == 1
    # 150 positions are no whole sublane tiles: the convolution stays XLA's, and says why
    assert startup["conv"] == {"M:1": {"path": "xla", "lanes": 0, "positions": 0, "why": "positions"}}
    assert batcher.stats.conv_kernel_batches == 0


def test_the_ssd_takes_the_kernel_inside_a_served_entry_on_a_tpu_and_nowhere_else(monkeypatch):
    """`ssd_choice` from what a trace can see: `pallas` inside the batcher's
    one-chip entry on a backend that answers `tpu` (or interpreted), `xla` on
    a CPU, outside the entry whatever the backend (the mesh executors,
    `shard_map`, the trainer) and for the last position alone; `note_ssd`
    notes each choice once."""
    s = falcon_h1._sizes(load_config(os.path.join(ROOT, "configs", "falcon_h1_small.toml"))["model"])
    xla = {"path": "xla", "chunk": 64, "state_bytes_a_row": 8 * 16 * 32 * 4, "heads": [8, 16, 32]}
    assert falcon_h1.ssd_choice(150, s) == xla
    with sequence.serving_attention([], ssd=(notes := [])):
        falcon_h1.note_ssd(150, s)
    assert notes == [xla]
    with sequence.serving_attention([], interpret=True):
        assert falcon_h1.ssd_choice(150, s) == dict(xla, path="pallas")
    monkeypatch.setattr(sequence.jax, "default_backend", lambda: "tpu")
    assert falcon_h1.ssd_choice(150, s) == xla
    with sequence.serving_attention([], ssd=(notes := [])):
        falcon_h1.note_ssd(150, s)
        falcon_h1.note_ssd(150, s)
        falcon_h1.note_ssd(40, s)
        falcon_h1.note_ssd(150, s, last_only=True)  # the last layer's hand-overs stay XLA's scan
    assert notes == [dict(xla, path="pallas"), dict(xla, path="pallas", chunk=40), xla]


def test_the_convolution_takes_the_kernel_where_its_shapes_are_whole_blocks(monkeypatch):
    """`conv_choice` from what a trace can see, as `ssd_choice`: `pallas`
    inside the batcher's one-chip entry on a TPU (or interpreted) where
    `d_ssm` and the channels are whole blocks of lanes and the length whole
    sublane tiles, XLA's form with the reason elsewhere; `note_conv` notes
    each choice once, and the last layer convolves like the others."""
    s = falcon_h1._sizes(load_config(os.path.join(ROOT, "configs", "falcon_h1_small.toml"))["model"])
    xla = {"path": "xla", "lanes": 0, "positions": 0}
    assert (s["d_ssm"], s["channels"]) == (128, 256) and falcon_h1.conv_choice(160, s) == xla
    with sequence.serving_attention([], interpret=True):
        assert falcon_h1.conv_choice(160, s) == {"path": "pallas", "lanes": 128, "positions": 160}
        assert falcon_h1.conv_choice(150, s) == dict(xla, why="positions")  # 18.75 sublane tiles
        assert falcon_h1.conv_choice(160, dict(s, d_ssm=64, channels=192)) == dict(xla, why="lanes")
        assert falcon_h1.conv_choice(160, dict(s, channels=320)) == dict(xla, why="lanes")  # x | B | C: 2.5 lane tiles
        assert falcon_h1.conv_choice(160, dict(s, taps=10)) == dict(xla, why="taps")
        # the published widths: 4 and 8 blocks of 1,024 lanes before the channels, 5 and 10 of them
        assert falcon_h1.conv_choice(2048, dict(s, d_ssm=4096, channels=5120)) == {
            "path": "pallas", "lanes": 1024, "positions": 512} == falcon_h1.conv_choice(2048, dict(s, d_ssm=8192, channels=10240))
    monkeypatch.setattr(sequence.jax, "default_backend", lambda: "tpu")
    assert falcon_h1.conv_choice(160, s) == xla  # outside an entry whatever the backend
    with sequence.serving_attention([], conv=(notes := [])):
        for length in (160, 160, 150, 4096):
            falcon_h1.note_conv(length, s)
    assert notes == [{"path": "pallas", "lanes": 128, "positions": 160}, dict(xla, why="positions"),
                     {"path": "pallas", "lanes": 128, "positions": 4096}]


# (heads, a head's width, groups, the state's width): whether x, B and C cross into the SSD's kernel as windows of one array
MIXERS = {"windows of the one array": (4, 64, 2, 128, True), "three arrays split out of it": (8, 16, 2, 32, False)}


@pytest.mark.parametrize("last_only", [False, True], ids=["all positions", "the last position"])
@pytest.mark.parametrize("name", sorted(MIXERS))
def test_the_mixer_through_its_two_kernels_is_the_mixer_through_xla(name, last_only):
    """`ssm` inside a served entry whose kernels run interpreted: the
    convolution's kernel reads the channels where they lie in the input
    projection's array and the SSD's kernel its result (at all positions; the
    last layer's hand-overs stay XLA's scan behind the same convolution),
    against the same call outside any entry, to float32 rounding."""
    from distributed_tf_serving_tpu.ops import ssd_kernel

    heads, width, groups, state, windows = MIXERS[name]
    config = tiny_config(num_fields=128, mamba_d_ssm=heads * width, mamba_n_heads=heads, mamba_d_head=width,
                         mamba_d_state=state, mamba_n_groups=groups, mamba_chunk_size=64)
    s = falcon_h1._sizes(config)
    assert ssd_kernel.windows_fit(heads, groups, width, state) is windows
    p = falcon_h1._ssm_init(jax.random.PRNGKey(1), s, jnp.float32)
    a = jnp.asarray(np.random.default_rng(2).standard_normal((2, 128, 64)), jnp.float32)
    mixer = lambda p, a: falcon_h1.ssm(p, a, s, jnp.float32, 1e-5, last_only)  # noqa: E731
    want = jax.jit(mixer)(p, a)

    def served(p, a):
        with sequence.serving_attention([], interpret=True, ssd=ssds, conv=convs):
            return mixer(p, a)

    ssds, convs = [], []
    text = str(jax.make_jaxpr(served)(p, a))
    assert text.count("pallas_call") == (1 if last_only else 2) and "name=causal_conv" in text
    assert [c["path"] for c in convs] == ["pallas"] and [c["path"] for c in ssds] == ["xla" if last_only else "pallas"]
    got = jax.jit(served)(p, a)
    assert got.shape == want.shape == (2, 1 if last_only else 128, 64)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5 * float(jnp.abs(want).max()))


def test_a_request_through_an_entry_whose_mixers_run_both_kernels(reference, tolerance, monkeypatch):
    """configs/falcon_h1_small.toml at 160 tokens a row, whole sublane tiles:
    every layer's convolution is the kernel (the last layer's too), every
    layer's SSD but the last's its own; the `startup.conv` stamp says so and
    the batch is counted once under `batch.conv_kernel`."""
    import functools

    from distributed_tf_serving_tpu.serving import batcher as batcher_mod
    from distributed_tf_serving_tpu.serving.server import build_stack
    from distributed_tf_serving_tpu.utils.tracing import request_trace

    monkeypatch.setattr(batcher_mod, "serving_attention", functools.partial(sequence.serving_attention, interpret=True))
    cfgs = load_config(os.path.join(ROOT, "configs", "falcon_h1_small.toml"))
    config = dataclasses.replace(cfgs["model"], name="M", num_fields=160)
    cfg = dataclasses.replace(cfgs["server"], model_name="M", warmup=False, num_fields=160)
    _registry, batcher, impl, servable, _mesh, _watcher = build_stack(cfg, model_config=config)
    counted = lambda: request_trace.snapshot().get("batch.conv_kernel", {}).get("count", 0)  # noqa: E731
    try:
        arrays, before = rows(3, config, folded=False), counted()
        got = batcher.submit(servable, arrays).result(timeout=600)
        startup = impl.runtime_stats()["startup"]
    finally:
        batcher.stop()
    batch = dict(arrays, feat_ids=(arrays["feat_ids"] % config.vocab_size).astype(np.int32))
    want = reference_scores(reference, servable.params, batch, config)
    assert np.max(np.abs(got["prediction_node"] - want)) < tolerance
    assert startup["conv"] == {"M:1": {"path": "pallas", "lanes": 128, "positions": 160}}
    assert startup["ssd"]["M:1"]["path"] == "pallas"
    assert batcher.stats.batches == 1 == batcher.stats.conv_kernel_batches == batcher.stats.ssd_kernel_batches
    assert counted() - before == 1


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


def test_runtime_block_reports_the_plan_and_the_ssd_stamp(served):
    batcher, impl, servable = served
    batcher.submit(servable, rows(2, servable.model.config, folded=False)).result(timeout=300)
    startup = impl.runtime_stats()["startup"]
    assert startup["layer_plan"] == {"M:1": {"parallel": 4}}
    layer = {"kind": "parallel", "window": 0, "block": 150, "keys_a_block": 150, "kv_heads": 2, "theta": 1e11,
             "ssd": {"kind": "ssd", "chunk": 64, "handovers_a_row": 3, "state_bytes_a_row": 8 * 16 * 32 * 4}}
    assert startup["attention_plan"] == {"M:1": [layer] * 4}
    assert startup["ssd"] == {"M:1": {"path": "xla", "chunk": 64, "state_bytes_a_row": 16384, "heads": [8, 16, 32]}}
    assert startup["attention"] == {"M:1": {"kernel": "xla", "block": 0, "pieces": 2}}
    assert startup["expert_plan"] == {"M:1": None} and startup["delta_rule"] == {} and startup["grouped"] == {}
    assert startup["conv"] == {"M:1": {"path": "xla", "lanes": 0, "positions": 0}}  # on a CPU: no reason to give
    assert startup["assembler"] == {"M:1": "native"} or not native.available()
    assert "feat_ids int32/24b" in startup["upload_format"]["M:1"]


# ------------------------------------------------------- the published shapes


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        shape = json.load(f)["toml"]["model"]
    return ModelConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in shape.items()})


def test_plan_and_parameter_count_at_the_published_cut(published):
    """By `jax.eval_shape`: nothing of the 3.49 B parameters is made."""
    model = build_model("falcon_h1", published)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    size = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))  # noqa: E731
    layers = published.num_hidden_layers
    assert model.layer_plan == ("parallel",) * layers and model.expert_plan == () and layers in (4, 5)
    assert dict(model.attention_plan[0]) == {
        "kind": "parallel", "window": 0, "block": 512, "keys_a_block": 2048, "kv_heads": 4, "theta": 1e11,
        "ssd": (("kind", "ssd"), ("chunk", 128), ("handovers_a_row", 16), ("state_bytes_a_row", 4_194_304))}
    attn, ssm = shapes["layers"][0]["attn"], shapes["layers"][0]["ssm"]
    assert {k: v.shape for k, v in attn.items()} == {
        "q": (5120, 2560), "k": (5120, 512), "v": (5120, 512), "o": (2560, 5120)}
    assert {k: v.shape for k, v in ssm.items()} == {
        "in": (5120, 9248), "conv_w": (5120, 4), "conv_b": (5120,), "A_log": (32,), "dt_bias": (32,), "D": (32,),
        "norm": (4096,), "out": (4096, 5120)}
    assert size(attn) == 31_457_280 and size(ssm) == 47_349_760 + 20_971_520 + 5120 * 5 + 96 + 4096
    assert size(shapes["layers"][0]["mlp"]) == 3 * 5120 * 21504 == 330_301_440
    assert size(shapes["layers"][0]) == 430_120_032  # 430.1 M a layer
    assert shapes["embedding"].shape == (261120, 5120) and shapes["score"].shape == (5120,)
    assert size(shapes) == layers * 430_120_032 + 261120 * 5120 + 2 * 5120
    assert round(size(shapes) / 1e5) == {5: 34875, 4: 30574}[layers]  # 3,487.5 M; 3,057.4 M at four layers
    assert {x.dtype for x in jax.tree.leaves(shapes)} == {jnp.dtype("bfloat16")}


@pytest.mark.parametrize("overrides,match", [
    ({"num_key_value_heads": 3}, "num_key_value_heads"),
    ({"head_dim": 15}, "head_dim"),
    ({"mamba_d_ssm": 60}, "mamba_d_ssm"),
    ({"mamba_n_heads": 0, "mamba_d_ssm": 0}, "mamba_d_ssm"),
    ({"mamba_n_groups": 3}, "mamba_n_groups"),
    ({"mamba_d_state": 0}, "mamba_d_state"),
    ({"mamba_chunk_size": 0}, "mamba_chunk_size"),
    ({"ssm_multipliers": (1.0, 1.0)}, "ssm_multipliers"),
    ({"mlp_multipliers": (1.0,)}, "mlp_multipliers"),
    ({"key_multiplier": 0.0}, "key_multiplier"),
    ({"num_hidden_layers": 0}, "num_hidden_layers"),
])
def test_a_configuration_the_stack_cannot_be_built_from_is_refused(overrides, match):
    with pytest.raises(ValueError, match=match):
        build_model("falcon_h1", tiny_config(**overrides))


def test_model_configs_own_defaults_build_a_valid_small_model():
    model = build_model("falcon_h1", ModelConfig())
    assert model.layer_plan == ("parallel",) * 8 and model.kind == "falcon_h1"
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert shapes["layers"][0]["ssm"]["in"].shape == (16, 2 * 64 + 2 * 2 * 32 + 4)


def test_the_initial_decays_spread_and_the_keys_are_drawn_wider():
    """`A` uniform in (1, 16) and `dt` log-uniform in (1e-3, 1e-1): a step's
    decay exp(-A softplus(dt_bias)) = exp(-A dt) lies in (0.2, 1), where a
    missing gate can be told; `D` and the norms 1; the keys' matrix wider by
    1 / key_multiplier, so that the scores spread."""
    config = tiny_config(mamba_n_heads=64, mamba_d_head=1, key_multiplier=0.25)
    layer = jax.jit(build_model("falcon_h1", config).init)(jax.random.PRNGKey(3))["layers"][0]
    p = layer["ssm"]
    decay = np.exp(-np.exp(np.asarray(p["A_log"], np.float64)) * np.log1p(np.exp(np.asarray(p["dt_bias"], np.float64))))
    assert 0.19 < decay.min() < 0.9 and 0.97 < decay.max() <= 1.0 and decay.std() > 0.05
    assert np.all(np.asarray(p["D"]) == 1) and np.all(np.asarray(p["norm"]) == 1)
    assert np.abs(np.asarray(p["conv_w"])).max() <= 0.5 and np.abs(np.asarray(p["conv_b"])).max() <= 0.5
    assert 0.07 < float(jnp.std(layer["attn"]["k"])) < 0.09 and 0.018 < float(jnp.std(layer["attn"]["q"])) < 0.022


def test_toml_reads_the_published_keys(tmp_path):
    cfgs = load_config(os.path.join(ROOT, "configs", "falcon_h1_small.toml"))
    model = build_model(cfgs["server"].model_kind, cfgs["model"])
    assert model.kind == "falcon_h1" and not model.takes_dense and not model.wts_in_compute_dtype
    assert cfgs["server"].num_fields == cfgs["model"].num_fields == 150
    assert len(model.layer_plan) == cfgs["model"].num_hidden_layers == 4
    assert cfgs["model"].ssm_multipliers == (0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738)
    assert cfgs["model"].num_fields % cfgs["model"].mamba_chunk_size
    (tmp_path / "s.toml").write_text('[model]\nmamba_d_states = 8\n')
    with pytest.raises(ValueError, match="unknown ModelConfig keys"):
        load_config(str(tmp_path / "s.toml"))
