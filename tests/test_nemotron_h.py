"""The nemotron_h family (NVIDIA-Nemotron-3-Super-120B-A12B-BF16 as a pointwise
sequence ranker: layers that are ONE mixer each, a Mamba-2 mixer, grouped-query
attention with no position signal or the LATENT routed block: ungated relu^2
experts in a latent between two projections, a sigmoid router with a selection
bias, an ungated shared expert at the full width) at tiny widths on the CPU:
against the benchmark's plain reference through `model.apply` and down the
served path with the kernels interpreted, the last-position cut over patterns
that end in every kind of layer, the eight shares of a routed layer against the
uncut layer, the router's bias, the held experts' ungated form on both paths,
what the benchmark's tolerance catches, and the step's counters and stamps."""

import dataclasses
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import ModelConfig, build_model, falcon_h1, nemotron_h, routed, sequence
from distributed_tf_serving_tpu.utils.config import load_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "nemotron3_super_120b_rerank")
LENGTH = 150  # no multiple of the SSD's chunk of 64: 3 hand-overs a row
interpreted = functools.partial(sequence.serving_attention, interpret=True)


def tiny_config(**overrides) -> ModelConfig:
    return ModelConfig(**{
        "name": "M", "num_fields": LENGTH, "vocab_size": 1000, "embed_dim": 64, "hybrid_override_pattern": "MEME*EM",
        "num_hidden_layers": 7, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "layer_norm_eps": 1e-5, "mamba_d_ssm": 128, "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 32,
        "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 64, "n_routed_experts": 16,
        "num_experts_per_tok": 4, "routed_scaling_factor": 5.0, "norm_topk_prob": True, "moe_latent_size": 32,
        "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48, "experts_held": 4,
        "first_expert_held": 4, "compute_dtype": "float32", **overrides,
    })


def sizes_of(config: ModelConfig) -> dict:
    """reference.py's keyword arguments for `config`."""
    return {
        "head": config.head_dim, "ssm_head": config.mamba_d_head, "groups": config.mamba_n_groups,
        "first": config.first_expert_held, "top_k": config.num_experts_per_tok,
        "scaling": config.routed_scaling_factor, "norm_topk": config.norm_topk_prob, "eps": config.layer_norm_eps,
    }


def rows(n: int, config: ModelConfig, seed: int = 3) -> dict:
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 1 << 40, size=(n, config.num_fields), dtype=np.int64)
    return {"feat_ids": (ids % config.vocab_size).astype(np.int32),
            "feat_wts": rng.random((n, config.num_fields), dtype=np.float32)}


def unit_gain(params, config: ModelConfig):
    """The tree with its matrices scaled so that a product keeps a unit input
    at the size it has at the published width of 4096 (router logits and a
    score logit of deviation near 1, not 0.16): as drawn, a tiny model's
    router hardly tells its experts apart. The convolution's taps and every
    vector but the score stay as drawn."""
    gain = (4096 / config.embed_dim) ** 0.5

    def scale(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        keep = name in ("embedding", "conv_w") or (leaf.ndim < 2 and name != "score")
        return leaf if keep else leaf * jnp.asarray(gain, leaf.dtype)

    return jax.tree_util.tree_map_with_path(scale, params)


def load(name: str):
    spec = importlib.util.spec_from_file_location(f"nemotron_h_{name}", os.path.join(CONFIG_DIR, name + ".py"))
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


def reference_logits(reference, params, batch, config):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p, b: reference.logits(p, b, **sizes_of(config)))(params, batch))


def model_and_tree(config: ModelConfig, seed: int = 0):
    model = build_model("nemotron_h", config)
    return model, unit_gain(model.init(jax.random.PRNGKey(seed)), config)


# ------------------------------------------------- the family and the reference


@pytest.mark.parametrize("pattern,length,held,first", [
    ("MEME*EM", LENGTH, 4, 4), ("MEMEMEM*EMEM", 70, 2, 6), ("ME*E", 33, 8, 8), ("MEMEE", 64, 16, 0),
    ("EE", 9, 4, 12), ("M", 40, 4, 0), ("*", 40, 4, 0)],
    ids=["the last layer a Mamba-2 one", "the published first twelve", "an attention cut to one query, then a block",
         "a Mamba-2 cut to its state walk, then two blocks; every expert held", "no layer mixes along the row",
         "one Mamba-2 layer", "one attention layer"])
def test_float32_logits_match_the_plain_reference(reference, pattern, length, held, first):
    """Through `model.apply`; the reference computes every layer at every
    position and the recurrence position by position, the program the trailing
    layers at the last position alone and the SSD in chunks."""
    config = tiny_config(hybrid_override_pattern=pattern, num_hidden_layers=len(pattern), num_fields=length,
                         experts_held=held, first_expert_held=first)
    model, params = model_and_tree(config)
    batch = rows(3, config)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(params, batch)["logits"])
    want = reference_logits(reference, params, batch, config)
    assert np.abs(want).max() > 0.05  # a score that says something
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_the_layers_run_are_the_patterns_first(reference):
    """`num_hidden_layers` of a longer pattern: the tree, the plan and the
    scores are the shorter pattern's."""
    long = tiny_config(hybrid_override_pattern="MEME*EM*EME", num_hidden_layers=5)
    short = tiny_config(hybrid_override_pattern="MEME*", num_hidden_layers=5)
    (model, params), batch = model_and_tree(long), rows(2, long)
    assert model.layer_plan == build_model("nemotron_h", short).layer_plan == (
        "mamba", "latent/moe", "mamba", "latent/moe", "attention")
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(params, batch)["logits"])
    np.testing.assert_allclose(got, reference_logits(reference, params, batch, long), atol=1e-5)


def served_scores(model, params, batch, notes=None):
    def served(p, b):
        with interpreted([] if notes is None else notes, grouped=[], ssd=[]):
            return model.apply(p, b)["prediction_node"]

    return np.asarray(jax.jit(served)(params, batch))


def test_the_served_step_in_bfloat16_is_within_the_tolerance(reference, tolerance):
    """Three bfloat16 pieces an activation, the kernels interpreted (the
    attention's, the SSD's at 16-wide heads, the grouped pair at the ungated
    form over 128-wide latent rows): inside the configuration's tolerance of
    the float32 reference's scores."""
    config = tiny_config(compute_dtype="bfloat16", param_dtype="bfloat16", moe_latent_size=128)
    model, params = model_and_tree(config)
    batch = rows(2, config)
    got = served_scores(model, params, batch)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.forward(p, b, **sizes_of(config)))(params, batch))
    assert np.abs(got - want).max() < tolerance


@pytest.mark.parametrize("name,mixer", [
    # 4 heads of 64 (half a lane tile, as published) over 2 groups of 128: x, B and C windows of the convolution's array
    ("windows of the one array", {"mamba_d_ssm": 256, "mamba_n_heads": 4, "mamba_d_head": 64, "mamba_d_state": 128}),
    ("three arrays split out of it", {}),
])
def test_the_served_step_through_the_convolutions_kernel_is_within_the_tolerance(reference, tolerance, name, mixer):
    """160 tokens a row, whole sublane tiles: every Mamba-2 layer's
    convolution is the kernel that reads the input projection where it lies
    (interpreted), the SSD's kernel behind it, at three pieces; inside the
    configuration's tolerance of the float32 reference's scores, and of the
    same step through XLA's paths to the kernels' rounding."""
    config = tiny_config(compute_dtype="bfloat16", param_dtype="bfloat16", moe_latent_size=128, num_fields=160, **mixer)
    model, params = model_and_tree(config)
    batch = rows(2, config)
    convs, ssds = [], []

    def served(p, b):
        with interpreted([], grouped=[], ssd=ssds, conv=convs):
            return model.apply(p, b)["prediction_node"]

    got = np.asarray(jax.jit(served)(params, batch))
    s = nemotron_h._sizes(config)
    lanes = 256 if mixer else 128  # 256 | 512 channels; 128 | 256
    assert convs == [{"path": "pallas", "lanes": lanes, "positions": 160}] and s["channels"] % lanes == 0
    assert sorted(c["path"] for c in ssds) == ["pallas", "xla"]  # the last Mamba-2 layer's hand-overs are XLA's scan
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.forward(p, b, **sizes_of(config)))(params, batch))
    assert np.abs(got - want).max() < tolerance
    assert np.abs(got - np.asarray(jax.jit(model.apply)(params, batch)["prediction_node"])).max() < tolerance / 4


def test_the_convolutions_choice_at_the_published_widths_and_where_it_says_no():
    """Nemotron-H's mixer: 8,192 channels of z before 10,240 convolved ones,
    8 and 10 blocks of 1,024 lanes, 2,048 positions four blocks of 512; the
    small TOML's 150 tokens are no whole sublane tiles, and a `d_ssm` that is
    no whole lane tile keeps XLA's form whatever the length."""
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        shape = json.load(f)["toml"]["model"]
    s = nemotron_h._sizes(ModelConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in shape.items()}))
    small = nemotron_h._sizes(load_config(os.path.join(ROOT, "configs", "nemotron_h_small.toml"))["model"])
    xla = {"path": "xla", "lanes": 0, "positions": 0}
    assert (s["d_ssm"], s["channels"], small["d_ssm"], small["channels"]) == (8192, 10240, 256, 384)
    assert falcon_h1.conv_choice(2048, s) == xla
    with interpreted([]):
        assert falcon_h1.conv_choice(2048, s) == {"path": "pallas", "lanes": 1024, "positions": 512}
        assert falcon_h1.conv_choice(150, small) == dict(xla, why="positions")
        assert falcon_h1.conv_choice(152, small) == {"path": "pallas", "lanes": 128, "positions": 152}
        assert falcon_h1.conv_choice(2048, dict(s, d_ssm=8256, channels=10304)) == dict(xla, why="lanes")


def test_one_bfloat16_piece_is_the_precision_below(reference, monkeypatch):
    """The served step at ONE piece an activation misses the reference by a
    bfloat16's rounding, far more than at this family's three."""
    config = tiny_config(compute_dtype="bfloat16", param_dtype="bfloat16", moe_latent_size=128)
    model, params = model_and_tree(config)
    batch = rows(4, config)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.forward(p, b, **sizes_of(config)))(params, batch))
    three = np.abs(served_scores(model, params, batch) - want).max()
    monkeypatch.setattr(nemotron_h, "OPERAND_PIECES", 1)
    one = np.abs(served_scores(model, params, batch) - want).max()
    assert one > 20 * three and one > 1e-3, (one, three)


@pytest.mark.parametrize("mixer", ["mamba", "attention"])
def test_the_last_position_form_is_the_all_positions_form_cut(mixer):
    config = tiny_config()
    s = nemotron_h._sizes(config)
    layer = nemotron_h._layer_init(jax.random.PRNGKey(1), mixer, s, jnp.float32)
    a = jnp.asarray(np.random.default_rng(2).standard_normal((2, LENGTH, 64)), jnp.float32)
    if mixer == "mamba":
        mix = lambda last: falcon_h1.ssm(layer["ssm"], a, s, jnp.float32, 1e-5, last, 3)  # noqa: E731
    else:
        mix = lambda last: nemotron_h.attention(layer["attn"], a, s, jnp.float32, last)  # noqa: E731
    whole, last = mix(False), mix(True)
    assert last.shape == (2, 1, 64)
    np.testing.assert_allclose(np.asarray(last), np.asarray(whole[:, -1:]), atol=1e-6)


def test_the_positions_plan_follows_the_pattern():
    plan = lambda pattern: nemotron_h.positions_plan(tuple(nemotron_h.KINDS[c] for c in pattern))  # noqa: E731
    assert plan("MEMEMEM*EMEM") == ("all",) * 11 + ("cut",)
    assert plan("MEMEMEM*EME") == ("all",) * 9 + ("cut", "last")
    assert plan("M*EE") == ("all", "cut", "last", "last")
    assert plan("EE") == ("last", "last") and plan("M") == ("cut",)


# ------------------------------------------------------------- the routed block


def _a_layer(config, seed=4):
    s = nemotron_h._sizes(config)
    return s, unit_gain(nemotron_h._layer_init(jax.random.PRNGKey(seed), "latent/moe", s, jnp.float32), config)["moe"]


def test_the_eight_shares_and_the_shared_expert_add_up_to_the_whole_layer(reference):
    """Experts 0-3, 4-7, ... 28-31 of 32, each share's partial sum through
    ITS OWN pass of `W_out_lat` as `latent_moe` computes it, plus the shared
    expert counted once, against the reference with every expert held."""
    config = tiny_config(n_routed_experts=32, num_experts_per_tok=6, experts_held=32, first_expert_held=0)
    s, layer = _a_layer(config)
    a = jnp.asarray(np.random.default_rng(5).standard_normal((3, 50, 64)), jnp.float32)
    no_shared = jax.tree.map(jnp.zeros_like, layer["shared"])
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(reference.moe_mix(layer, a, first=0, top_k=6))
        shared_once = np.asarray(reference.moe_mix(
            {**layer, "experts": jax.tree.map(lambda w: w[:0], layer["experts"])}, a, first=0, top_k=6))
        parts, hit, pairs = [], 0, 0
        for share in range(8):
            mine = {**layer, "shared": no_shared,
                    "experts": jax.tree.map(lambda w: w[4 * share:4 * share + 4], layer["experts"])}
            out, counts = nemotron_h.latent_moe(mine, a, dict(s, held=4, first=4 * share), jnp.float32)
            parts.append(np.asarray(out))
            hit, pairs = hit + int(counts[4]), pairs + int(counts[1])
            assert int(counts[0]) == 150 and int(counts[1]) <= int(counts[3])
    assert hit == 32 and pairs == 150 * 6  # every expert took a token; every (token, expert) pair on exactly one share
    np.testing.assert_allclose(sum(parts) + shared_once, whole, atol=2e-5)
    assert np.abs(shared_once).max() > 0.01 and np.abs(sum(parts)).max() > 0.01


def test_route_with_a_selection_bias_chooses_by_it_and_weighs_without_it():
    rng = np.random.default_rng(6)
    router = jnp.asarray(rng.standard_normal((64, 16)) * 0.2, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(16) * 0.3, jnp.float32)
    x = jnp.asarray(rng.standard_normal((40, 64)), jnp.float32)
    scores = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64) @ np.asarray(router, np.float64))))
    order = np.argsort(-(scores + np.asarray(bias, np.float64)), axis=-1)[:, :4]
    plain = np.argsort(-scores, axis=-1)[:, :4]
    assert (np.sort(order) != np.sort(plain)).any()  # the bias changes who is chosen
    chosen, gates, seen = routed.route(router, x, 4, 5.0, bias=bias)
    assert np.asarray(chosen).tolist() == order.tolist()
    picked = np.take_along_axis(scores, order, -1)
    np.testing.assert_allclose(np.asarray(gates), 5.0 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(seen), scores, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(routed.route(router, x, 4, 5.0, normalise=False, bias=bias)[1]),
                               5.0 * picked, rtol=1e-5)
    # a zero bias is no bias, bit for bit
    for got, want in zip(routed.route(router, x, 4, 2.5, bias=jnp.zeros(16)), routed.route(router, x, 4, 2.5)):
        assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kernel", [False, True], ids=["XLA's loop", "the kernels interpreted"])
def test_held_experts_at_the_ungated_form_is_the_sum_an_expert(kernel):
    """Experts of two matrices (no `gate` leaf) over rows of a latent's
    width, not the residual's: against every held expert's `relu(x U)^2 D`
    over every token times its gate; a token on every one of its k held
    experts, an expert no token chose."""
    rng = np.random.default_rng(11)
    tokens, latent, width, held, experts, top_k = 300, 128, 96, 16, 64, 6
    p = {name: jnp.asarray(rng.standard_normal(shape) * 0.1, jnp.float32) for name, shape in
         (("up", (held, latent, width)), ("down", (held, width, latent)))}
    x = jnp.asarray(rng.standard_normal((tokens, latent)), jnp.float32)
    others = [e for e in range(experts) if e != 1]
    chosen = np.stack([rng.permutation(others)[:top_k] for _ in range(tokens)])
    chosen[0] = [e for e in range(held) if e != 1][:top_k]
    chosen, gates = jnp.asarray(chosen.astype(np.int32)), jnp.asarray(rng.random((tokens, top_k)) + 0.1, jnp.float32)

    def run():
        if not kernel:
            return routed.held_experts(p, x, chosen, gates, 0, jnp.float32, block=16, count=3)
        with interpreted([], grouped=(notes := [])):
            out = routed.held_experts(p, x, chosen, gates, 0, jnp.float32, count=3)
        assert notes == [{"kernel": "pallas", "tile": 128, "pieces": 3, "held": 16,
                          "rows": (300 * 6 // 128 + 16) * 128, "form": "relu2", "width": 128}]
        return out

    with jax.default_matmul_precision("highest"):
        got, took, computed = jax.jit(run)()
        want = np.zeros((tokens, latent), np.float32)
        for e in range(held):
            gate = np.where(np.asarray(chosen) == e, np.asarray(gates), 0.0).sum(-1)
            y = np.square(np.maximum(np.asarray(x) @ np.asarray(p["up"][e]), 0.0)) @ np.asarray(p["down"][e])
            want += gate[:, None] * y
    mask = (np.asarray(chosen)[:, :, None] == np.arange(held)).any(1)
    assert took.tolist() == mask.sum(0).tolist() and int(took[1]) == 0 and mask[0].sum() == top_k
    tile = 128 if kernel else 16
    assert int(computed) == sum(-(-int(n) // tile) * tile for n in took)
    np.testing.assert_allclose(np.asarray(got), want, atol=5e-5)
    assert routed.expert_form(p) == "relu2" and routed.expert_form(dict(p, gate=p["up"])) == "gated_silu"


# ------------------------------------------------------------- planted faults


def _planted_block(fault: str):
    """`latent_moe` with the shared expert moved into the latent, or the
    router moved onto the latent input (a tree whose latent is as wide as the
    residual takes either)."""
    def block(p, a, s, cd, live=None):
        x = a.reshape(-1, a.shape[-1])
        latent = nemotron_h._dot(x, p["latent_in"], cd)
        chosen, gates, _ = nemotron_h.route(p["router"], p["router_bias"], latent if fault == "router" else x, s)
        held = routed.held_experts(p["experts"], latent, chosen, gates, s["first"], cd, count=3)[0]
        if fault == "shared":
            out = nemotron_h._dot(held + routed.relu2_mlp(p["shared"], latent, cd, 3), p["latent_out"], cd)
        else:
            out = routed.relu2_mlp(p["shared"], x, cd, 3) + nemotron_h._dot(held, p["latent_out"], cd)
        return out.reshape(a.shape), jnp.zeros((len(routed.STEP_STATS),), jnp.int32)
    return block


def _gates_from_the_biased_scores(_route):
    def planted(router, bias, x, s):
        chosen, _, scores = routed.route(router, x, s["top_k"], s["scaling"], bias=bias)
        top = jnp.take_along_axis(scores + bias, chosen, axis=-1)
        return chosen, top / jnp.sum(top, axis=-1, keepdims=True) * s["scaling"], scores
    return planted


def _under_the_wrong_group(ssd):
    """Head h reads group `h % G` for `h // (H / G)`: the heads handed over in
    the order that puts head h in group `h % G`'s run, and `y` put back."""
    def planted(x, dt, a, b, c, *rest, **kw):
        heads, groups = x.shape[2], b.shape[2]
        order = jnp.arange(heads).reshape(heads // groups, groups).T.reshape(-1)  # position g * per + i: head g + G i
        y, state = ssd(x[:, :, order], dt[:, :, order], a[order], b, c, *rest, **kw)
        back = jnp.argsort(order)
        return y[:, :, back], state[:, back]
    return planted


def _the_norm_before_the_gate(_gated_norm):
    def planted(p, y, z, s, eps):
        grouped = y.reshape(y.shape[:-1] + (s["groups"], -1))
        return routed.rms_norm(p["norm"].reshape(s["groups"], -1), grouped, eps).reshape(y.shape) * jax.nn.silu(z)
    return planted


def _a_rotary_turn(blocked_attention):
    def planted(q, k, v, *rest, **kw):
        cos, sin = routed.rope_table(k.shape[1], q.shape[-1], 10000.0)
        queries = q.shape[1]
        q = routed.rotate(q, cos[k.shape[1] - queries:, None, None, :], sin[k.shape[1] - queries:, None, None, :])
        return blocked_attention(q, routed.rotate(k, cos[:, None, :], sin[:, None, :]), v, *rest, **kw)
    return planted


def _act(f):
    """`routed.relu2_mlp` with another activation."""
    return lambda _mlp: lambda p, x, cd, count: routed.dot(f(routed.dot(x, p["up"], cd, count)), p["down"], cd, count)


# name -> (the module, the name in it that is replaced, what takes its place given what was there)
PATCHES = {
    "gates from the biased scores": (nemotron_h, "route", _gates_from_the_biased_scores),
    "silu for relu squared": (routed, "relu2_mlp", _act(jax.nn.silu)),
    "relu not squared": (routed, "relu2_mlp", _act(jax.nn.relu)),
    "a gated expert": (routed, "relu2_mlp", _act(lambda u: jax.nn.silu(u) * u)),
    "the shared expert in the latent": (nemotron_h, "latent_moe", lambda _moe: _planted_block("shared")),
    "the router on the latent input": (nemotron_h, "latent_moe", lambda _moe: _planted_block("router")),
    "head h % G for h // (H / G)": (falcon_h1, "ssd", _under_the_wrong_group),
    "the norm before the gate": (falcon_h1, "gated_norm", _the_norm_before_the_gate),
    "a rotary turn": (sequence, "blocked_attention", _a_rotary_turn),
}
# name -> the configuration's keys that say something else than the published file
MISCONFIGURED = {
    "the top-k not normalised": {"norm_topk_prob": False},
    "the scaling left out": {"routed_scaling_factor": 1.0},
}


@pytest.mark.parametrize("fault", sorted(PATCHES) + sorted(MISCONFIGURED))
def test_a_planted_fault_is_refused_by_the_tolerance(reference, tolerance, fault, monkeypatch):
    """The float32 step scores inside a twentieth of the tolerance of the
    reference; with one fault planted, outside the tolerance. (The latent as
    wide as the residual here, so that a shared expert or a router moved into
    it finds weights of its shape.)"""
    config = tiny_config(moe_latent_size=64)
    model, params = model_and_tree(config)
    batch = rows(4, config)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.forward(p, b, **sizes_of(config)))(params, batch))
        sound = np.asarray(jax.jit(model.apply)(params, batch)["prediction_node"])
        if fault in PATCHES:
            module, name, planted = PATCHES[fault]
            monkeypatch.setattr(module, name, planted(getattr(module, name)))
        else:
            model = build_model("nemotron_h", dataclasses.replace(config, **MISCONFIGURED[fault]))
        # a new function: `jax.jit(model.apply)` above is traced and would not be traced again
        faulty = np.asarray(jax.jit(lambda p, b: model.apply(p, b))(params, batch)["prediction_node"])
    assert np.abs(sound - want).max() < tolerance / 20
    assert np.abs(faulty - want).max() > tolerance, fault


# ------------------------------------------------- plans, counters and stamps


def test_plans_and_a_share_that_cannot_be_cut():
    config = load_config(os.path.join(ROOT, "configs", "nemotron_h_small.toml"))["model"]
    model = build_model("nemotron_h", config)
    assert model.layer_plan == ("mamba", "latent/moe", "mamba", "latent/moe", "attention", "latent/moe", "mamba")
    assert dict(model.expert_plan) == {"published": 16, "held": 4, "first": 4, "top_k": 4, "heads_published": 4,
                                       "heads_held": 4, "chips_sharing_layer": 4}
    mamba, block, full = (dict(model.attention_plan[i]) for i in (0, 1, 4))
    assert mamba == {"kind": "ssd", "chunk": 64, "handovers_a_row": 3, "state_bytes_a_row": 8 * 32 * 32 * 4}
    assert block == {"kind": "latent/moe", "latent": 128, "form": "relu2"}
    assert full == {"kind": "full", "window": 0, "block": 150, "keys_a_block": 150, "kv_heads": 2, "rotary_dims": 0}
    assert model.step_stats == routed.STEP_STATS + (
        "attn.scores_computed", "attn.scores_seen", "ssd.rows", "ssd.handovers", "ssd.positions")
    for wrong, match in (({"hybrid_override_pattern": "MEMX*EM"}, "letter"), ({"num_hidden_layers": 8}, "num_hidden_layers"),
                         ({"num_hidden_layers": 0}, "num_hidden_layers"), ({"experts_held": 5}, "divides"),
                         ({"mamba_n_groups": 3}, "whole groups"), ({"mamba_d_ssm": 200}, "mamba_d_ssm"),
                         ({"num_key_value_heads": 3}, "whole groups"), ({"moe_latent_size": 0}, "positive")):
        with pytest.raises(ValueError, match=match):
            build_model("nemotron_h", dataclasses.replace(config, **wrong))


@pytest.mark.parametrize("pattern,blocks_at_all,blocks_at_one,queries", [
    ("MEME*EM", 3, 0, LENGTH), ("ME*EE", 1, 2, 1)], ids=["the last layer mixes", "two trailing blocks"])
def test_the_steps_counters_follow_the_work(pattern, blocks_at_all, blocks_at_one, queries):
    """A padded row is in no counter; the routing's five are summed over the
    routed layers (a trailing one at one position a row), the hand-overs and
    positions over the Mamba-2 layers, the score pairs over the attention
    layer (one query a row where it is the layer cut to the last position)."""
    config = tiny_config(hybrid_override_pattern=pattern, num_hidden_layers=len(pattern))
    model, params = model_and_tree(config)
    batch = rows(3, config)
    batch["feat_wts"][2] = 0.0
    out, stats = jax.jit(model.apply_stats)(params, batch)
    named = dict(zip(model.step_stats, np.asarray(stats).tolist()))
    mambas = pattern.count("M")
    assert float(out["logits"][2]) == 0.0
    assert named["moe.tokens"] == 2 * (blocks_at_all * LENGTH + blocks_at_one)
    assert named["moe.assignments_here"] <= named["moe.rows_computed"]
    assert blocks_at_all * 4 <= named["moe.experts_hit"] <= (blocks_at_all + blocks_at_one) * 4
    assert (named["ssd.rows"], named["ssd.handovers"], named["ssd.positions"]) == (2, 2 * mambas * 3, 2 * mambas * LENGTH)
    seen = LENGTH * (LENGTH + 1) // 2 if queries == LENGTH else LENGTH
    assert named["attn.scores_seen"] == 2 * seen <= named["attn.scores_computed"]


def test_the_batcher_stamps_the_three_choices_and_counts_the_steps_counters(monkeypatch):
    """Three rows in a bucket of four: the padded row is in no counter."""
    from distributed_tf_serving_tpu.serving import batcher as batcher_mod
    from distributed_tf_serving_tpu.serving.server import build_stack
    from distributed_tf_serving_tpu.utils.tracing import request_trace

    cfgs = load_config(os.path.join(ROOT, "configs", "nemotron_h_small.toml"))
    config = dataclasses.replace(cfgs["model"], name="N")
    cfg = dataclasses.replace(cfgs["server"], model_name="N", warmup=False)
    monkeypatch.setattr(batcher_mod, "serving_attention", interpreted)
    _registry, batcher, impl, servable, _mesh, _watcher = build_stack(cfg, model_config=config)
    try:
        count = lambda name: request_trace.snapshot().get(name, {}).get("count", 0)  # noqa: E731
        before = {name: count(name) for name in servable.model.step_stats}
        rng = np.random.RandomState(3)
        payload = {"feat_ids": rng.randint(0, 1 << 40, size=(3, config.num_fields)).astype(np.int64),
                   "feat_wts": rng.rand(3, config.num_fields).astype(np.float32)}
        scores = batcher.submit(servable, payload).result(timeout=600)["prediction_node"]
        startup = impl.runtime_stats()["startup"]
        counted = {name: count(name) - before[name] for name in servable.model.step_stats}
    finally:
        batcher.stop()
    assert scores.shape == (3,) and np.isfinite(scores).all()
    model = build_model("nemotron_h", config)
    want = np.asarray(model.apply(servable.params, {
        "feat_ids": jnp.asarray(payload["feat_ids"] % config.vocab_size, jnp.int32),
        "feat_wts": jnp.asarray(payload["feat_wts"])})["prediction_node"])
    np.testing.assert_allclose(scores, want, atol=2e-3)  # the kernels' sums in another order, in bfloat16 pieces
    assert startup["attention"]["N:1"] == {"kernel": "pallas", "block": 256, "pieces": 3}
    assert startup["ssd"]["N:1"] == {"path": "pallas", "chunk": 64, "state_bytes_a_row": 8 * 32 * 32 * 4,
                                     "heads": [8, 32, 32]}
    grouped = startup["grouped"]["N:1"]
    assert grouped == {"kernel": "pallas", "tile": 128, "pieces": 3, "held": 4, "rows": grouped["rows"],
                       "form": "relu2", "width": 128}
    assert grouped["rows"] == routed.layout_tiles(4 * config.num_fields, 4, 4, 128) * 128
    # 150 positions are no whole sublane tiles: the convolution stays XLA's, and the stamp says why
    assert startup["conv"]["N:1"] == {"path": "xla", "lanes": 0, "positions": 0, "why": "positions"}
    assert startup["layer_plan"]["N:1"] == {"mamba": 3, "latent/moe": 3, "attention": 1}
    assert startup["expert_plan"]["N:1"]["held"] == 4
    assert counted["moe.tokens"] == 3 * 3 * config.num_fields and 8 <= counted["moe.experts_hit"] <= 12
    assert (counted["ssd.rows"], counted["ssd.handovers"]) == (3, 3 * 3 * 3)
