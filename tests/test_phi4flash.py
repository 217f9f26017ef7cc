"""The phi4flash family (Phi-4-mini-flash-reasoning / SambaY as a pointwise
sequence ranker) at tiny widths on the CPU: against the benchmark's plain
reference, the chunked scan against the recurrence, the served step's
last-position skip against the whole forward pass, the layer plan and the
parameter count at the published depth, what the benchmark's tolerance
catches, and one request down the served path."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tf_serving_tpu import native
from distributed_tf_serving_tpu.models import ModelConfig, build_model, phi4flash
from distributed_tf_serving_tpu.models.embeddings import unpack_params
from distributed_tf_serving_tpu.ops.transfer import (
    combined_layout, describe_layout, pack_host_combined, transfer_spec,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "phi4_mini_flash_rerank")
WINDOW, LENGTH = 8, 24


def tiny_config(**overrides) -> ModelConfig:
    return ModelConfig(**{
        "name": "M", "num_fields": LENGTH, "vocab_size": 1000, "embed_dim": 64, "mlp_dims": (128,),
        "num_hidden_layers": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
        "sliding_window": WINDOW, "compute_dtype": "float32", **overrides,
    })


def rows(n: int, config: ModelConfig, seed: int = 3, folded: bool = True) -> dict:
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 1 << 40, size=(n, config.num_fields), dtype=np.int64)
    return {
        "feat_ids": (ids % config.vocab_size).astype(np.int32) if folded else ids,
        "feat_wts": rng.random((n, config.num_fields), dtype=np.float32),
    }


def unit_gain(params, config: ModelConfig):
    """The tree with its matrices scaled so that a linear layer keeps a unit
    input at unit size, as the published 0.02 does at the published width of
    2560: at width 64 the logits would otherwise stay within 0.2 of zero and
    every score within 0.05 of a half, whatever the layers compute."""
    gain = (2560 / config.embed_dim) ** 0.5
    scaled = ("in_proj", "x_proj", "dt_proj", "out_proj", "qkv", "o", "q", "gate", "up", "down", "score")

    def scale(path, leaf):
        return leaf * gain if path[-1].key in scaled else leaf

    return jax.tree_util.tree_map_with_path(scale, params)


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "ref_phi4flash", os.path.join(CONFIG_DIR, "reference.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def tolerance():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        return float(json.load(f)["tolerance"])


def reference_scores(reference, params, batch, window=WINDOW):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(
            lambda p, b: reference.forward(p, b, window))(params, batch))


@pytest.mark.parametrize("layers", [8, 16])
def test_float32_logits_match_the_plain_reference(reference, layers):
    config = tiny_config(num_hidden_layers=layers)
    model = build_model("phi4flash", config)
    params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(7)), config)
    batch = rows(5, config)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(
            lambda p, b: reference.logits(p, b, WINDOW))(params, batch))
        got = np.asarray(jax.jit(model.apply)(params, batch)["logits"])
    assert want.shape == got.shape == (5,) and want.std() > 0.1
    assert np.max(np.abs(want - got)) < 1e-5


@pytest.mark.parametrize("length,chunk", [(24, 16), (21, 8), (5, 16), (32, 16)])
def test_chunked_scan_is_the_recurrence(length, chunk):
    """Also where the length is no multiple of the chunk, or shorter than one."""
    rng = np.random.default_rng(length)
    n, inner, state = 3, 32, 4
    u = rng.standard_normal((n, length, inner)).astype(np.float32)
    delta = rng.random((n, length, inner)).astype(np.float32) * 0.5
    a = -np.exp(rng.standard_normal((inner, state))).astype(np.float32)
    b, c = (rng.standard_normal((n, length, state)).astype(np.float32) for _ in range(2))
    s = np.zeros((n, inner, state))
    want = np.zeros((n, length, inner))
    for t in range(length):
        s = np.exp(delta[:, t, :, None] * a) * s + (delta[:, t] * u[:, t])[..., None] * b[:, t, None, :]
        want[:, t] = (s * c[:, t, None, :]).sum(-1)
    got = np.asarray(phi4flash.selective_scan(*map(jnp.asarray, (u, delta, a, b, c)), chunk=chunk))
    assert got.shape == (n, length, inner)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def scan_with_a_bfloat16_state(u, delta, a, b, c):
    """The recurrence of `selective_scan` with the state rounded to bfloat16
    after every position: the planted fault of the two tests that use it."""
    def step(state, xs):
        d, du, b_t, c_t = xs
        state = jnp.exp(d[:, None, :] * a.T[None]) * state.astype(jnp.float32)
        state = (state + du[:, None, :] * b_t[:, :, None]).astype(jnp.bfloat16)
        return state, jnp.sum(state.astype(jnp.float32) * c_t[:, :, None], axis=1)

    state0 = jnp.zeros((u.shape[0], a.shape[1], u.shape[2]), jnp.bfloat16)
    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (delta, delta * u, b, c))
    return jnp.moveaxis(jax.lax.scan(step, state0, xs)[1], 0, 1)


def test_a_bfloat16_scan_state_fails_the_scans_own_limit():
    """The state is float32 as the configuration states. Carried in bfloat16
    it drops the small increments of the slow channels (delta * A near zero:
    a memory of hundreds of positions) and misses the recurrence by a hundred
    times the limit the float32 state keeps."""
    rng = np.random.default_rng(0)
    n, length, inner, state = 2, 256, 32, 4
    u = rng.standard_normal((n, length, inner)).astype(np.float32)
    delta = (rng.random((n, length, inner)) * 0.02 + 0.001).astype(np.float32)
    a = -np.arange(1, state + 1, dtype=np.float32) * np.ones((inner, 1), np.float32)
    b, c = (rng.standard_normal((n, length, state)).astype(np.float32) for _ in range(2))
    args = tuple(map(jnp.asarray, (u, delta, a, b, c)))
    exact = np.asarray(phi4flash.selective_scan(*args, chunk=1))
    limit = 2e-5 * np.abs(exact).max()
    assert np.max(np.abs(np.asarray(phi4flash.selective_scan(*args)) - exact)) < limit
    low = np.asarray(scan_with_a_bfloat16_state(*args))
    assert np.max(np.abs(low - exact)) > 100 * limit


@pytest.mark.parametrize("layers", [8, 16])
def test_the_last_position_skip_is_the_whole_forward_pass(reference, layers):
    """The reference computes every layer at every position; the served step
    everything after the full layer's keys and values at the last alone. At a
    length that is no multiple of the scan's chunk or the window."""
    config = tiny_config(num_hidden_layers=layers, num_fields=21)
    model = build_model("phi4flash", config)
    params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(1)), config)
    batch = rows(4, config)
    with jax.default_matmul_precision("highest"):
        served = jax.jit(lambda p, b: phi4flash.forward(config, p, b))(params, batch)
        whole = jax.jit(lambda p, b: reference.logits(p, b, WINDOW))(params, batch)
    assert float(jnp.std(whole)) > 0.1
    np.testing.assert_allclose(np.asarray(served), np.asarray(whole), rtol=1e-5, atol=1e-5)


def test_a_window_as_long_as_the_row_is_full_attention():
    """L <= window: a window layer sees what a full layer sees; a window
    shorter than the row must change the score."""
    logits = {}
    for window in (WINDOW, LENGTH, 10 * LENGTH):
        config = tiny_config(sliding_window=window)
        model = build_model("phi4flash", config)
        params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(2)), config)
        logits[window] = np.asarray(jax.jit(model.apply)(params, rows(4, config))["logits"])
    np.testing.assert_allclose(logits[LENGTH], logits[10 * LENGTH], rtol=1e-6, atol=1e-6)
    assert np.max(np.abs(logits[WINDOW] - logits[LENGTH])) > 1e-3


def test_layer_plan_and_parameter_count_at_the_published_depth():
    """By `jax.eval_shape`: nothing of the 3.85 B parameters is made."""
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        published = json.load(f)["toml"]["model"]
    counts = {}
    size = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))  # noqa: E731
    for layers in (16, 32):
        config = ModelConfig(**{**published, "mlp_dims": tuple(published["mlp_dims"]),
                                "num_hidden_layers": layers})
        model = build_model("phi4flash", config)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        per_kind = {}
        for kind, layer in zip(model.layer_plan, shapes["layers"]):
            per_kind.setdefault(kind, set()).add(size(layer))
        counts[layers] = (
            {k: model.layer_plan.count(k) for k in per_kind}, per_kind, size(shapes),
            {x.dtype for x in jax.tree.leaves(shapes)})
    plan, per_kind, total, dtypes = counts[32]
    assert plan == {"mamba": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}
    assert model.layer_plan[:4] == ("mamba", "window", "mamba", "window")
    assert model.layer_plan[16:20] == ("mamba", "full", "gmu", "cross")
    assert {k: round(next(iter(v)) / 1e6) for k, v in per_kind.items()} == {
        "mamba": 120, "window": 98, "full": 98, "gmu": 105, "cross": 92}
    assert all(len(v) == 1 for v in per_kind.values())
    assert round(total / 1e7) == 385 and dtypes == {jnp.dtype("bfloat16")}
    assert counts[16][0] == {"mamba": 5, "window": 4, "full": 1, "gmu": 3, "cross": 3}
    assert round(counts[16][2] / 1e7) == 219


@pytest.mark.parametrize("layers", [4, 10, 6])
def test_a_depth_the_rule_cannot_lay_out_is_refused_at_build(layers):
    with pytest.raises(ValueError, match="num_hidden_layers"):
        build_model("phi4flash", tiny_config(num_hidden_layers=layers))


def test_toml_reads_the_published_keys(tmp_path):
    from distributed_tf_serving_tpu.utils.config import load_config

    cfgs = load_config(os.path.join(ROOT, "configs", "phi4flash_small.toml"))
    model = build_model(cfgs["server"].model_kind, cfgs["model"])
    assert model.kind == "phi4flash" and not model.takes_dense and not model.wts_in_compute_dtype
    assert cfgs["server"].num_fields == cfgs["model"].num_fields
    assert len(model.layer_plan) == cfgs["model"].num_hidden_layers
    (tmp_path / "s.toml").write_text('[model]\nnum_hidden_layer = 8\n')
    with pytest.raises(ValueError, match="unknown ModelConfig keys"):
        load_config(str(tmp_path / "s.toml"))


# ---------------------------------------------------------------- precision


@pytest.fixture(scope="module")
def long_rows(reference):
    """Rows long enough for a recurrent state to matter (several times the
    slowest channel's memory would be better still; the CPU sets the limit),
    bfloat16 weights and compute as served, and the float32 reference's
    scores."""
    config = tiny_config(num_fields=192, compute_dtype="bfloat16", param_dtype="bfloat16")
    model = build_model("phi4flash", config)
    params = unit_gain(jax.jit(model.init)(jax.random.PRNGKey(5)), config)
    batch = rows(6, config, seed=11)
    return config, model, params, batch, reference_scores(reference, params, batch)


def _worst(model, params, batch, want) -> float:
    got = np.asarray(jax.jit(lambda p, b: model.apply(p, b))(params, batch)["prediction_node"])
    return float(np.max(np.abs(got.astype(np.float64) - want)))


def test_two_piece_scores_within_the_benchmark_tolerance(long_rows, tolerance):
    _config, model, params, batch, want = long_rows
    assert want.std() > 0.05  # scores that spread, or the comparison compares nothing
    assert _worst(model, params, batch, want) < tolerance / 3


def test_one_piece_operands_fail_the_tolerance(long_rows, tolerance, monkeypatch):
    """The nearest precision below the stated one: every activation rounded
    to bfloat16 where it enters a product."""
    _config, model, params, batch, want = long_rows
    monkeypatch.setattr(phi4flash, "OPERAND_PIECES", 1)
    assert _worst(model, params, batch, want) > 3 * tolerance


def test_a_bfloat16_scan_state_fails_the_tolerance(long_rows, tolerance, monkeypatch):
    _config, model, params, batch, want = long_rows
    monkeypatch.setattr(phi4flash, "selective_scan", scan_with_a_bfloat16_state)
    assert _worst(model, params, batch, want) > tolerance


def test_dropping_the_lambda_term_fails_the_tolerance(long_rows, tolerance, monkeypatch):
    """The fault is planted here: the second softmax map's output zeroed, so
    that `o = softmax(q1 k1') v` alone reaches the norm."""
    _config, model, params, batch, want = long_rows
    product = phi4flash._product

    def without_the_second_map(spec, x, y, cd):
        out = product(spec, x, y, cd)
        return out.at[..., 1, :].set(0.0) if spec.endswith("->nqgjce") else out

    monkeypatch.setattr(phi4flash, "_product", without_the_second_map)
    assert _worst(model, params, batch, want) > 10 * tolerance


# ------------------------------------------------------------ the served path


@pytest.fixture(scope="module")
def served():
    from distributed_tf_serving_tpu.serving.server import build_stack
    from distributed_tf_serving_tpu.utils.config import ServerConfig

    config = tiny_config(compute_dtype="bfloat16", param_dtype="bfloat16")
    cfg = ServerConfig(
        model_kind="phi4flash", model_name="M", num_fields=LENGTH, buckets=(2, 4, 8), warmup=False)
    _registry, batcher, impl, servable, _mesh, _watcher = build_stack(cfg, model_config=config)
    yield batcher, impl, servable
    batcher.stop()


def test_a_request_through_the_batchers_entry_scores_like_the_reference(served, reference, tolerance):
    """3 rows pad to the bucket of 4; ids travel as u24 and weights as float32."""
    batcher, _impl, servable = served
    config = servable.model.config
    arrays = rows(3, config, folded=False)
    got = batcher.submit(servable, arrays).result(timeout=120)["prediction_node"]
    batch = dict(arrays, feat_ids=(arrays["feat_ids"] % config.vocab_size).astype(np.int32))
    direct = np.asarray(jax.jit(servable.model.apply)(servable.params, batch)["prediction_node"])
    logical = unpack_params(servable.params, config.embed_dim)  # embed_dim 64: held two rows a lane row
    want = reference_scores(reference, logical, batch)
    assert got.shape == (3,) and batcher.compress_transfer
    np.testing.assert_allclose(got, direct, rtol=1e-6, atol=1e-6)
    assert np.max(np.abs(got - want)) < tolerance


def test_predict_answers_a_row_of_tokens(served):
    from distributed_tf_serving_tpu import codec
    from distributed_tf_serving_tpu.client import build_predict_request

    batcher, impl, servable = served
    arrays = rows(2, servable.model.config, seed=9, folded=False)
    response = impl.predict(build_predict_request(arrays, "M"))
    scores = codec.to_ndarray(response.outputs["prediction_node"])
    direct = batcher.submit(servable, arrays).result(timeout=120)["prediction_node"]
    assert scores.shape == (2,) and np.all((scores > 0) & (scores < 1))
    np.testing.assert_array_equal(scores, direct)


def test_runtime_block_reports_plan_and_bytes(served):
    batcher, impl, servable = served
    batcher.submit(servable, rows(2, servable.model.config, folded=False)).result(timeout=120)
    startup = impl.runtime_stats()["startup"]
    assert startup["lookups_per_row"] == {"M:1": LENGTH}
    assert startup["layer_plan"] == {"M:1": {"mamba": 3, "window": 2, "full": 1, "gmu": 1, "cross": 1}}
    leaves = jax.tree.leaves(servable.params)
    assert startup["params_bytes"] == {"M:1": 2 * sum(x.size for x in leaves)}
    assert "feat_ids int32/24b" in startup["upload_format"]["M:1"]  # filled once the entry is traced


def test_a_ctr_family_reports_no_layer_plan():
    from distributed_tf_serving_tpu.models import Servable, ctr_signatures

    config = ModelConfig(num_fields=5, vocab_size=64, embed_dim=4, mlp_dims=(8,))
    model = build_model("dcn_v2", config)
    servable = Servable("D", 1, model, model.init(jax.random.PRNGKey(0)), ctr_signatures(5))
    assert servable.layer_plan is None
    assert servable.params_bytes == 4 * sum(x.size for x in jax.tree.leaves(servable.params))


@pytest.mark.skipif(not native.ensure(), reason="native hostops unavailable")
@pytest.mark.parametrize("sizes,bucket", [((2,), 2), ((2, 2), 4), ((2, 2, 2, 2), 8), ((2, 1), 4)])
def test_u24_ids_and_float32_weights_of_1024_token_rows_assemble_bit_for_bit(sizes, bucket):
    """The new cell's layout through native assemble_batch: [n, 1024] int64
    ids folded by the published vocabulary (200,064 < 1 << 24: u24) and
    float32 weights as they are, against fold -> pad -> pack."""
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        published = json.load(f)["toml"]["model"]
    config = ModelConfig(**{**published, "mlp_dims": (10240,)})
    spec = transfer_spec(build_model("phi4flash", config))
    assert spec == {"feat_ids": "u24"}
    parts = [rows(n, config, seed=20 + i, folded=False) for i, n in enumerate(sizes)]
    padded = {
        "feat_ids": np.zeros((bucket, 1024), np.int32), "feat_wts": np.zeros((bucket, 1024), np.float32)}
    at = 0
    for part in parts:
        n = part["feat_ids"].shape[0]
        padded["feat_ids"][at:at + n] = part["feat_ids"] % config.vocab_size
        padded["feat_wts"][at:at + n] = part["feat_wts"]
        at += n
    layout = combined_layout(padded, spec)
    assert "feat_ids int32/24b" in describe_layout(layout) and "feat_wts float32/32b" in describe_layout(layout)
    got, _ns = native.assemble_batch(
        layout, {k: [p[k] for p in parts] for k in padded}, {"feat_ids": config.vocab_size})
    np.testing.assert_array_equal(got, pack_host_combined(padded, spec))
