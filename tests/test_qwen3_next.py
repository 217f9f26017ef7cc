"""The qwen3_next family (Qwen3-Next-80B-A3B-Instruct as a pointwise sequence
ranker: gated-delta-rule layers with two value heads a key head, three to one
gated full-attention layer with zero-centred head norms and a partial rotary,
every layer a routed block behind a softmax router with a gated shared expert)
at tiny widths on the CPU: against the benchmark's plain reference through
`model.apply` and down the served path with the kernels interpreted, the
chunked rule against the position-by-position recurrence, the last-position
cut, the four shares of a routed layer against the uncut layer, the router's
two scorings, the pairs' layout at 16 and 128 held experts, the kernels at
this family's shapes, what fits the attention kernel's VMEM, what the
benchmark's tolerance catches, and the step's counters and stamps."""

import dataclasses
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import ModelConfig, build_model, olmo_hybrid, qwen3_next, routed, sequence
from distributed_tf_serving_tpu.ops import attention_kernel, delta_kernel
from distributed_tf_serving_tpu.utils.config import load_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "qwen3_next_80b_rerank")
LENGTH = 150  # no multiple of the rule's chunk of 64: 3 hand-overs a row
interpreted = functools.partial(sequence.serving_attention, interpret=True)


def tiny_config(**overrides) -> ModelConfig:
    return ModelConfig(**{
        "name": "M", "num_fields": LENGTH, "vocab_size": 1000, "embed_dim": 64, "num_hidden_layers": 5,
        "full_attention_interval": 4, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "partial_rotary_factor": 0.25, "rope_theta": 1e7, "layer_norm_eps": 1e-6, "linear_num_key_heads": 2,
        "linear_num_value_heads": 4, "linear_key_head_dim": 16, "linear_value_head_dim": 24,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": False, "num_experts": 16, "num_experts_per_tok": 4,
        "norm_topk_prob": True, "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "experts_held": 4, "first_expert_held": 4, "compute_dtype": "float32", **overrides,
    })


def sizes_of(config: ModelConfig) -> dict:
    """reference.py's keyword arguments for `config`."""
    return {
        "first": config.first_expert_held, "top_k": config.num_experts_per_tok, "head": config.head_dim,
        "rotary": int(config.head_dim * config.partial_rotary_factor), "theta": config.rope_theta,
        "key_dim": config.linear_key_head_dim, "eps": config.layer_norm_eps,
        "neg_eigval": config.linear_allow_neg_eigval,
    }


def rows(n: int, config: ModelConfig, seed: int = 3) -> dict:
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 1 << 40, size=(n, config.num_fields), dtype=np.int64)
    return {"feat_ids": (ids % config.vocab_size).astype(np.int32),
            "feat_wts": rng.random((n, config.num_fields), dtype=np.float32)}


def unit_gain(params, config: ModelConfig):
    """The tree with its matrices scaled so that a product keeps a unit input
    at the size it has at the published width of 2048 (router logits and a
    score logit of deviation near 1, not 0.16): as drawn, a tiny model's
    router hardly tells its experts apart and its gates sit at 0.5."""
    gain = (2048 / config.embed_dim) ** 0.5

    def scale(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        return leaf if name == "embedding" or (leaf.ndim < 2 and name not in ("shared_gate", "score")) else leaf * gain

    return jax.tree_util.tree_map_with_path(scale, params)


def load(name: str):
    spec = importlib.util.spec_from_file_location(f"qwen3_next_{name}", os.path.join(CONFIG_DIR, name + ".py"))
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
    model = build_model("qwen3_next", config)
    return model, unit_gain(model.init(jax.random.PRNGKey(seed)), config)


# ------------------------------------------------- the family and the reference


@pytest.mark.parametrize("layers,length,held,first", [
    (5, LENGTH, 4, 4), (4, 70, 16, 0), (8, 33, 8, 8), (1, 9, 4, 12), (2, 64, 16, 0)],
    ids=["LLLFL", "LLLF: the last layer a full one", "two periods", "one layer", "every expert held"])
def test_float32_logits_match_the_plain_reference(reference, layers, length, held, first):
    """Through `model.apply`; the reference computes every layer at every
    position and the rule position by position, the program the last layer's
    tail at the last position alone and the rule in chunks."""
    config = tiny_config(num_hidden_layers=layers, num_fields=length, experts_held=held, first_expert_held=first)
    model, params = model_and_tree(config)
    batch = rows(3, config)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(params, batch)["logits"])
    want = reference_logits(reference, params, batch, config)
    assert np.abs(want).max() > 0.05  # a score that says something
    np.testing.assert_allclose(got, want, atol=5e-6)


def test_the_served_step_in_bfloat16_is_within_the_tolerance(reference, tolerance):
    """Three bfloat16 pieces an activation, the kernels interpreted: inside the
    configuration's tolerance of the float32 reference's scores."""
    config = tiny_config(compute_dtype="bfloat16", param_dtype="bfloat16")
    model, params = model_and_tree(config)
    batch = rows(2, config)

    def served(p, b):
        with interpreted([], grouped=[], delta=[]):
            return model.apply(p, b)["prediction_node"]

    got = np.asarray(jax.jit(served)(params, batch))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.forward(p, b, **sizes_of(config)))(params, batch))
    assert np.abs(got - want).max() < tolerance


def test_the_chunked_rule_at_two_value_heads_a_key_head_is_the_recurrence(reference):
    """`gated_delta_rule` with 2 key heads for 4 value heads, on XLA's path
    and through the kernel (interpreted), against the reference's
    position-by-position recurrence, in which value head h reads key head
    h // 2."""
    rng = np.random.default_rng(0)
    n, length, keys, values, dk, dv = 2, 150, 2, 4, 16, 24
    q = olmo_hybrid.l2_norm(jnp.asarray(rng.standard_normal((n, length, keys, dk)), jnp.float32)) * dk ** -0.5
    k = olmo_hybrid.l2_norm(jnp.asarray(rng.standard_normal((n, length, keys, dk)), jnp.float32))
    v = jnp.asarray(rng.standard_normal((n, length, values, dv)), jnp.float32)
    g = -jnp.asarray(rng.uniform(0.01, 1.5, (n, length, values)), jnp.float32)
    b = jnp.asarray(rng.uniform(0.0, 1.0, (n, length, values)), jnp.float32)
    want = np.asarray(reference.delta_rule(q, k, v, jnp.exp(g), b))
    rule = lambda: olmo_hybrid.gated_delta_rule(q, k, v, g, b, cd=jnp.float32, count=3)[0]  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(rule)())

        def served():
            with interpreted([], delta=(notes := [])):
                out = rule()
            assert notes == [{"kernel": "pallas", "chunk": 64, "pieces": 3, "key_heads": 2, "value_heads": 4, "shared": 2}]
            return out

        through_the_kernel = np.asarray(jax.jit(served)())
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(through_the_kernel, want, atol=2e-5)
    # a value head under the wrong key head is another rule
    wrong = np.asarray(olmo_hybrid.gated_delta_rule(jnp.tile(q, (1, 1, 2, 1)), jnp.tile(k, (1, 1, 2, 1)), v, g, b)[0])
    assert np.abs(wrong - want).max() > 100 * np.abs(got - want).max()
    with pytest.raises(ValueError, match="whole groups"):
        olmo_hybrid.gated_delta_rule(q, k, v[:, :, :3], g[..., :3], b[..., :3])


@pytest.mark.parametrize("mixer", ["linear", "full"])
def test_the_last_position_form_is_the_all_positions_form_cut(mixer):
    config = tiny_config()
    s = qwen3_next._sizes(config)
    layer = qwen3_next._layer_init(jax.random.PRNGKey(1), mixer, s, jnp.float32)
    a = jnp.asarray(np.random.default_rng(2).standard_normal((2, LENGTH, 64)), jnp.float32)
    mix = qwen3_next.gated_delta_net if mixer == "linear" else qwen3_next.gated_attention
    p = layer["linear" if mixer == "linear" else "attn"]
    whole = mix(p, a, s, jnp.float32, 1e-6)
    last = mix(p, a, s, jnp.float32, 1e-6, last_only=True)
    assert last.shape == (2, 1, 64)
    np.testing.assert_allclose(np.asarray(last), np.asarray(whole[:, -1:]), atol=1e-6)


# ------------------------------------------------------------- the routed block


def test_the_four_shares_and_the_gated_shared_expert_add_up_to_the_whole_layer(reference):
    """Experts 0-3, 4-7, 8-11 and 12-15 of 16, each share's held part through
    `routed_ffn` as the family calls it, plus the gated shared expert counted
    once, against the reference with every expert held."""
    config = tiny_config(experts_held=16, first_expert_held=0)
    s = qwen3_next._sizes(config)
    layer = unit_gain(qwen3_next._layer_init(jax.random.PRNGKey(4), "linear", s, jnp.float32), config)
    b = jnp.asarray(np.random.default_rng(5).standard_normal((3, 50, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(reference.moe(layer, b, first=0, top_k=4))
        shared_once = np.asarray(reference.moe(
            {**layer, "experts": jax.tree.map(lambda w: w[:0], layer["experts"])}, b, first=0, top_k=4))
        parts, hit = [], 0
        for share in range(4):
            mine = {**layer, "experts": jax.tree.map(lambda w: w[4 * share:4 * share + 4], layer["experts"])}
            mine.pop("shared"), mine.pop("shared_gate")
            out, counts = routed.routed_ffn(mine, b, 4, 4 * share, 1.0, jnp.float32, 3, router=qwen3_next.route)
            parts.append(np.asarray(out))
            hit += int(counts[4])
            assert int(counts[0]) == 150 and int(counts[1]) == int(counts[3] > 0) * int(counts[1]) <= int(counts[3])
    assert hit == 16  # every expert of every share took a token
    np.testing.assert_allclose(sum(parts) + shared_once, whole, atol=2e-5)
    assert np.abs(shared_once).max() > 0.01 and np.abs(sum(parts)).max() > 0.01


def test_route_with_a_softmax_is_a_plain_top_k_of_a_softmax_and_with_a_sigmoid_what_it_was():
    rng = np.random.default_rng(6)
    router = jnp.asarray(rng.standard_normal((64, 16)) * 0.2, jnp.float32)
    x = jnp.asarray(rng.standard_normal((40, 64)), jnp.float32)
    logits = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    order = np.argsort(-probs, axis=-1)[:, :4]
    chosen, gates, scores = routed.route(router, x, 4, 1.0, "softmax")
    assert np.asarray(chosen).tolist() == order.tolist()
    picked = np.take_along_axis(probs, order, -1)
    np.testing.assert_allclose(np.asarray(gates), picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(scores), probs, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-6)
    plain = routed.route(router, x, 4, 1.0, "softmax", normalise=False)[1]  # norm_topk_prob false
    np.testing.assert_allclose(np.asarray(plain), picked, rtol=1e-5)

    def sigmoid_route_as_it_was(router, x, top_k, scaling):  # routed.route before the scoring was an argument
        scores = jax.nn.sigmoid(jnp.einsum(
            "th,he->te", x, router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32))
        top, chosen = jax.lax.top_k(scores, top_k)
        return chosen, top / jnp.sum(top, axis=-1, keepdims=True) * scaling, scores

    for got, want in zip(routed.route(router, x, 4, 2.5), sigmoid_route_as_it_was(router, x, 4, 2.5)):
        assert np.array_equal(np.asarray(got), np.asarray(want))  # bit for bit
    with pytest.raises(ValueError, match="scoring"):
        routed.route(router, x, 4, 1.0, "tanh")


def _a_routing(tokens, held, experts, top_k, rng):
    """Every token on `top_k` distinct experts of `experts`; token 0 on top_k
    HELD experts; the held expert 1 chosen by no token."""
    others = [e for e in range(experts) if e != 1]
    chosen = np.stack([rng.permutation(others)[:top_k] for _ in range(tokens)])
    chosen[0] = [e for e in range(held) if e != 1][:top_k]
    return jnp.asarray(chosen.astype(np.int32)), jnp.asarray(rng.random((tokens, top_k)) + 0.1, jnp.float32)


@pytest.mark.parametrize("held,kernel", [(16, False), (128, False), (128, True)],
                         ids=["16 held", "128 held", "128 held, the kernels interpreted"])
def test_held_experts_is_the_sum_an_expert(held, kernel):
    """One layout whatever `held` is, against every held expert's gated MLP
    over every token times its gate: a token on 10 held experts, an expert no
    token chose, and a load an expert (about 6) far under a tile."""
    rng = np.random.default_rng(held)
    tokens, hidden, width, experts, top_k = 300, 128, 128, 512 if held == 128 else 32, 10
    p = {name: jnp.asarray(rng.standard_normal(shape) * 0.1, jnp.float32) for name, shape in
         (("gate", (held, hidden, width)), ("up", (held, hidden, width)), ("down", (held, width, hidden)))}
    x = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    chosen, gates = _a_routing(tokens, held, experts, top_k, rng)

    def run():
        if not kernel:
            return routed.held_experts(p, x, chosen, gates, 0, jnp.float32, block=16, count=3)
        with interpreted([], grouped=(notes := [])):
            out = routed.held_experts(p, x, chosen, gates, 0, jnp.float32, count=3)
        assert notes == [{"kernel": "pallas", "tile": 128, "pieces": 3, "held": 128,
                          "rows": (300 * 10 // 128 + 128) * 128,  # not 128 x 384
                          "form": "gated_silu", "width": 128}]
        return out

    with jax.default_matmul_precision("highest"):
        got, took, computed = jax.jit(run)()
        want = np.zeros((tokens, hidden), np.float32)
        for e in range(held):
            gate = np.where(np.asarray(chosen) == e, np.asarray(gates), 0.0).sum(-1)
            want += gate[:, None] * np.asarray(routed.gated_mlp({n: w[e] for n, w in p.items()}, x, jnp.float32, 3))
    mask = (np.asarray(chosen)[:, :, None] == np.arange(held)).any(1)
    assert took.tolist() == mask.sum(0).tolist() and int(took[1]) == 0 and mask[0].sum() == top_k
    tile = 128 if kernel else 16
    assert int(computed) == sum(-(-int(n) // tile) * tile for n in took)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_the_delta_kernel_at_128_wide_heads_and_two_value_heads_a_key_head():
    """ops/delta_kernel.py at dk = dv = 128 (every head a lane block: a group
    of 8 value heads over 4 key heads by its own rule) against XLA's chunk
    walk, a key head's q and k read by its two value heads as they lie."""
    assert delta_kernel.heads_a_step(32, 128, 128, 2) == 8 and delta_kernel.heads_a_step(4, 128, 128, 2) == 4
    rng = np.random.default_rng(7)
    n, length, keys, values, d = 1, 128, 2, 4, 128
    q = olmo_hybrid.l2_norm(jnp.asarray(rng.standard_normal((n, length, keys, d)), jnp.float32)) * d ** -0.5
    k = olmo_hybrid.l2_norm(jnp.asarray(rng.standard_normal((n, length, keys, d)), jnp.float32))
    v = jnp.asarray(rng.standard_normal((n, length, values, d)), jnp.float32)
    g = -jnp.asarray(rng.uniform(0.01, 1.0, (n, length, values)), jnp.float32)
    b = jnp.asarray(rng.uniform(0.0, 1.0, (n, length, values)), jnp.float32)
    rule = lambda: olmo_hybrid.gated_delta_rule(q, k, v, g, b, cd=jnp.bfloat16, count=3)  # noqa: E731

    def served():
        with interpreted([], delta=[]):
            return rule()

    (want, state), (got, state_here) = jax.jit(rule)(), jax.jit(served)()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    np.testing.assert_allclose(np.asarray(state_here), np.asarray(state), atol=2e-6)


# (keys, window, the parts' widths, the values' width, query heads a key-value head, pieces): what each cell's
# attention at all positions hands the kernel (tests/test_tpu_compile.py ATTENTION_SHAPES has the arrays)
CELLS_ATTENTION = {
    "phi4_mini_flash_rerank": (1024, 512, (64,), 128, 2, 2),
    "pangu_ultra_moe_rerank": (1024, None, (128, 64), 128, 1, 3),
    "k_exaone_moe_rerank full": (2048, None, (128,), 128, 8, 3),
    "k_exaone_moe_rerank window": (2048, 128, (128,), 128, 8, 3),
    "olmo_hybrid_rerank": (2048, None, (128,), 128, 1, 2),
    "mimo_v2_5_rerank full": (2048, None, (192,), 128, 16, 3),
    "mimo_v2_5_rerank window": (2048, 128, (192,), 128, 8, 3),
    "falcon_h1_34b_rerank": (2048, None, (128,), 128, 5, 2),
    "qwen3_next_80b_rerank full": (2048, None, (256,), 256, 8, 3),
    "nemotron3_super_120b_rerank": (2048, None, (128,), 128, 16, 3),
}
# The one shape of a cell that does not fit VMEM with the keys' pieces a pair and a head's float32 block there: held
# compact (PR 61); every other keeps the program it had.
COMPACT = {"qwen3_next_80b_rerank full"}


@pytest.mark.parametrize("cell", sorted(CELLS_ATTENTION))
def test_the_vmem_rule_answers_pallas_for_every_cells_shapes(cell, monkeypatch):
    keys, window, widths, dv, shared, count = CELLS_ATTENTION[cell]
    monkeypatch.setattr(sequence.jax, "default_backend", lambda: "tpu")
    heads = sequence.Heads(widths, dv, shared, jnp.bfloat16)
    with sequence.serving_attention([]):
        choice = sequence.attention_choice(keys, keys, window, count, heads)
        assert choice == sequence.attention_choice(keys, keys, window, count)  # as before the rule
    assert choice == {"kernel": "pallas", "block": attention_kernel.tile(keys, window), "pieces": count}
    compact = attention_kernel.held_compact(keys, window, *heads, count)
    assert attention_kernel.vmem_bytes(keys, window, *heads, count, compact=compact) <= attention_kernel.VMEM_LIMIT


@pytest.mark.parametrize("cell", sorted(CELLS_ATTENTION))
def test_every_cell_keeps_the_form_it_had_and_the_one_that_had_none_is_held_compact(cell):
    keys, window, widths, dv, shared, count = CELLS_ATTENTION[cell]
    assert attention_kernel.held_compact(keys, window, widths, dv, shared, jnp.bfloat16, count) == (cell in COMPACT)


def test_the_vmem_rule_takes_256_wide_heads_at_three_pieces_held_compact(monkeypatch):
    """The published full layer: keys and values 256 wide, 8 query heads a
    key-value head, three pieces over 2,048 keys. In pairs 22.5 MiB of
    scratch, blocks and a score tile, which the chip refused at warm-up (PR
    58); compact (the keys' pieces once each, the float32 keys and values
    read from HBM a chunk at a time) 15.5 MiB by the same count, the stack of
    a key block's work counted in (Mosaic's own: 15.45), which the chip takes (PERF.md section 6, PR 61): the rule answers `pallas`, the
    note carries no `"why"`, and the counters count the kernel's tiles. So
    does the float32 stand-in of the readings (one piece). The last layer's
    one query keeps XLA's path as everywhere."""
    monkeypatch.setattr(sequence.jax, "default_backend", lambda: "tpu")
    heads = sequence.Heads((256,), 256, 8, jnp.bfloat16)
    assert attention_kernel.vmem_bytes(2048, None, (256,), 256, 8, jnp.bfloat16, 3) == 22544384 + (1 << 20)
    assert attention_kernel.vmem_bytes(2048, None, (256,), 256, 8, jnp.bfloat16, 3, compact=True) == 31 << 19
    took = {"kernel": "pallas", "block": 512, "pieces": 3}
    with sequence.serving_attention(notes := []):
        assert sequence.attention_choice(2048, 2048, None, 3, heads) == took
        assert sequence.takes_kernel(2048, 2048, None, 3, heads)
        assert sequence.blocked_pairs(2048, 2048, None, 3, heads)[0] == attention_kernel.tile_pairs(2048, 2048) == 10 << 18
        assert sequence.attention_choice(2048, 2048, None, 3, heads._replace(cd=jnp.float32)) == took
        assert sequence.attention_choice(1, 2048, None, 3, heads) == {"kernel": "xla", "block": 0, "pieces": 3}
    assert notes == [took]


# (keys, the parts' widths, the values' width, query heads a key-value head): what fits neither way at three pieces
TOO_LARGE = {"512 wide": (2048, (512,), 512, 8), "384 wide": (2048, (384,), 384, 8), "3,072 keys 256 wide": (3072, (256,), 256, 8)}


@pytest.mark.parametrize("shape", sorted(TOO_LARGE))
def test_the_vmem_rule_keeps_what_fits_neither_way_off_the_kernel(shape, monkeypatch):
    """Past the VMEM a kernel has even compact: XLA's blocks serve, the stamp
    says why and the counters count XLA's blocks (a shape that does not fit
    is refused by the chip at warm-up, not by the compiler)."""
    keys, widths, dv, shared = TOO_LARGE[shape]
    monkeypatch.setattr(sequence.jax, "default_backend", lambda: "tpu")
    heads = sequence.Heads(widths, dv, shared, jnp.bfloat16)
    assert attention_kernel.held_compact(keys, None, *heads, 3)
    assert attention_kernel.vmem_bytes(keys, None, *heads, 3, compact=True) > attention_kernel.VMEM_LIMIT
    with sequence.serving_attention(notes := []):
        assert not sequence.takes_kernel(keys, keys, None, 3, heads)
        assert sequence.blocked_pairs(keys, keys, None, 3, heads)[0] == sum(
            (stop - start) * last for start, stop, _, last in sequence.query_blocks(keys, keys))
    assert notes == [{"kernel": "xla", "block": 0, "pieces": 3, "why": "vmem"}]


# ------------------------------------------------------------- planted faults


def _without_the_shared_gate(ffn):
    return lambda layer, *a, **kw: ffn({k: v for k, v in layer.items() if k != "shared_gate"}, *a, **kw)


def _under_the_wrong_key_head(rule):
    def planted(q, k, v, g, b, *rest, **kw):
        r = v.shape[2] // q.shape[2]
        return rule(jnp.tile(q, (1, 1, r, 1)), jnp.tile(k, (1, 1, r, 1)), v, g, b, *rest, **kw)
    return planted


# name -> (the module, the name in it that is replaced, what takes its place given what was there)
PATCHES = {
    "w for 1 + w": (qwen3_next, "rms0", lambda _rms0: routed.rms_norm),
    "the attention's gate left out": (qwen3_next, "attention_gate", lambda _gate: lambda o, gate: o),
    "the shared expert's gate left out": (routed, "routed_ffn", _without_the_shared_gate),
    "sigmoid for softmax": (qwen3_next, "route", lambda _route: lambda router, x, k, scaling, normalise=True:
                            routed.route(router, x, k, scaling, "sigmoid", normalise)),
    "key head h % 2 for h // 2": (olmo_hybrid, "gated_delta_rule", _under_the_wrong_key_head),
}
# name -> the configuration's keys that say something else than the published file
MISCONFIGURED = {
    "the top-k not normalised": {"norm_topk_prob": False},
    "b doubled": {"linear_allow_neg_eigval": True},
    "rotary on all of a head's dims": {"partial_rotary_factor": 1.0},
}


@pytest.mark.parametrize("fault", sorted(PATCHES) + sorted(MISCONFIGURED))
def test_a_planted_fault_is_refused_by_the_tolerance(reference, tolerance, fault, monkeypatch):
    """The float32 step scores inside a twentieth of the tolerance of the
    reference; with one fault planted, outside the tolerance."""
    config = tiny_config()
    model, params = model_and_tree(config)
    batch = rows(4, config)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, b: reference.forward(p, b, **sizes_of(config)))(params, batch))
        sound = np.asarray(jax.jit(model.apply)(params, batch)["prediction_node"])
        if fault in PATCHES:
            module, name, planted = PATCHES[fault]
            monkeypatch.setattr(module, name, planted(getattr(module, name)))
        else:
            model = build_model("qwen3_next", dataclasses.replace(config, **MISCONFIGURED[fault]))
        # a new function: `jax.jit(model.apply)` above is traced and would not be traced again
        faulty = np.asarray(jax.jit(lambda p, b: model.apply(p, b))(params, batch)["prediction_node"])
    assert np.abs(sound - want).max() < tolerance / 20
    assert np.abs(faulty - want).max() > tolerance, fault


# ------------------------------------------------- plans, counters and stamps


def test_plans_and_a_share_that_cannot_be_cut():
    config = load_config(os.path.join(ROOT, "configs", "qwen3_next_small.toml"))["model"]
    model = build_model("qwen3_next", config)
    assert model.layer_plan == ("linear", "linear", "linear", "full", "linear")
    assert dict(model.expert_plan) == {"published": 16, "held": 4, "first": 4, "top_k": 4, "heads_published": 4,
                                       "heads_held": 4, "chips_sharing_layer": 4}
    linear, full = dict(model.attention_plan[0]), dict(model.attention_plan[3])
    assert linear == {"kind": "linear", "chunk": 64, "handovers_a_row": 3, "state_bytes_a_row": 4 * 16 * 24 * 4,
                      "solve_block": 16, "key_heads": 2, "value_heads": 4}
    assert full == {"kind": "full", "window": 0, "block": 150, "keys_a_block": 150, "kv_heads": 2, "rotary_dims": 8,
                    "theta": 1e7, "gate": True}
    assert model.step_stats == routed.STEP_STATS + (
        "attn.scores_computed", "attn.scores_seen", "delta.rows", "delta.handovers", "delta.positions")
    assert routed.STEP_STATS[-1] == "moe.experts_hit"
    kinds = ("linear_attention", "full_attention")
    assert qwen3_next.layer_plan(dataclasses.replace(config, num_hidden_layers=2, layer_types=kinds)) == ("linear", "full")
    for wrong, match in (({"linear_num_value_heads": 3}, "whole groups"), ({"experts_held": 5}, "divides"),
                         ({"partial_rotary_factor": 0.1}, "pairs"), ({"full_attention_interval": 0}, "interval"),
                         ({"layer_types": ("full_attention",)}, "layer_types")):
        with pytest.raises(ValueError, match=match):
            build_model("qwen3_next", dataclasses.replace(config, **wrong))


def test_the_steps_counters_follow_the_work():
    """A padded row is in no counter; the routing's five are summed over the
    five routed layers (the last at one position a row), the hand-overs and
    positions over the four linear layers, the score pairs over the full one."""
    config = tiny_config()
    model, params = model_and_tree(config)
    batch = rows(3, config)
    batch["feat_wts"][2] = 0.0
    out, stats = jax.jit(model.apply_stats)(params, batch)
    named = dict(zip(model.step_stats, np.asarray(stats).tolist()))
    assert float(out["logits"][2]) == 0.0
    assert named["moe.tokens"] == 2 * (4 * LENGTH + 1)
    assert named["moe.assignments_here"] <= named["moe.rows_computed"] and 0 < named["moe.experts_hit"] <= 5 * 4
    assert named["moe.experts_hit"] >= 4 * 4  # every held expert of the four layers at all positions
    assert (named["delta.rows"], named["delta.handovers"], named["delta.positions"]) == (2, 2 * 4 * 3, 2 * 4 * LENGTH)
    assert named["attn.scores_seen"] == 2 * LENGTH * (LENGTH + 1) // 2 <= named["attn.scores_computed"]
    routing = np.asarray(jax.jit(lambda p, b: routed.route(
        p["layers"][0]["router"], jnp.zeros((8, 64)), 4, 1.0, "softmax")[1])(params, batch))
    np.testing.assert_allclose(routing, 0.25)  # a softmax over equal logits, its top-4 normalised


def test_the_batcher_stamps_the_three_choices_and_counts_the_new_counter(monkeypatch):
    from distributed_tf_serving_tpu.serving import batcher as batcher_mod
    from distributed_tf_serving_tpu.serving.server import build_stack
    from distributed_tf_serving_tpu.utils.tracing import request_trace

    cfgs = load_config(os.path.join(ROOT, "configs", "qwen3_next_small.toml"))
    config = dataclasses.replace(cfgs["model"], name="Q")
    cfg = dataclasses.replace(cfgs["server"], model_name="Q", warmup=False)
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
    assert scores.shape == (2,) and np.isfinite(scores).all()
    assert startup["attention"]["Q:1"] == {"kernel": "pallas", "block": 256, "pieces": 3}
    assert startup["delta_rule"]["Q:1"] == {
        "kernel": "pallas", "chunk": 64, "pieces": 3, "key_heads": 2, "value_heads": 4, "shared": 2}
    grouped = startup["grouped"]["Q:1"]
    assert grouped == {"kernel": "pallas", "tile": 128, "pieces": 3, "held": 4, "rows": grouped["rows"],
                       "form": "gated_silu", "width": 128}
    assert grouped["rows"] == routed.layout_tiles(2 * config.num_fields, 4, 4, 128) * 128
    assert startup["layer_plan"]["Q:1"] == {"linear": 4, "full": 1}
    assert counted["moe.tokens"] == 2 * (4 * config.num_fields + 1) and 16 <= counted["moe.experts_hit"] <= 20
    assert counted["delta.handovers"] == 2 * 4 * 3
