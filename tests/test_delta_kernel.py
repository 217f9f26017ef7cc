"""The gated delta rule's chunk pass as one Pallas kernel (ops/delta_kernel.py),
run here in interpret mode: against XLA's `gated_delta_rule` on the same
operands to float32 rounding and against the rule position by position in
float64, at the published heads (keys of 96, values of 192 one to one; 128 /
128 with two value heads a key head) and at a small one; a length that is no
multiple of the chunk, a state handed in and the one handed back, a padded
row, one key repeated at b = 2; groups of value heads that read ONE key
head's q and k (never repeated on the way: the traced rule says so); the
planted precisions told apart THROUGH the kernel; who takes it, and what the
batcher stamps and counts. Times come from the chip (PERF.md section 6, PR 52); the compile for
a v5e is in test_tpu_compile.py."""

import contextlib
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import olmo_hybrid, sequence
from distributed_tf_serving_tpu.ops import delta_kernel
from distributed_tf_serving_tpu.serving import batcher as batcher_mod
from distributed_tf_serving_tpu.utils.config import load_config

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs")
interpreted = functools.partial(sequence.serving_attention, interpret=True)

# name -> (rows, length, value heads, dk, dv, chunk, compute dtype, a state handed in, value heads a key head)
SHAPES = {
    "the published head, a few of them": (1, 192, 4, 96, 192, 64, jnp.bfloat16, False, 1),
    "the published head, a length that is no multiple of the chunk": (2, 150, 6, 96, 192, 64, jnp.bfloat16, True, 1),
    "heads that are no whole number of groups (10 of 8)": (1, 130, 10, 96, 192, 64, jnp.bfloat16, True, 1),
    "a small head": (2, 75, 3, 8, 12, 16, jnp.bfloat16, False, 1),
    "a small head, a state handed in": (2, 75, 3, 8, 12, 16, jnp.bfloat16, True, 1),
    "a row shorter than a chunk": (2, 40, 3, 8, 12, 64, jnp.bfloat16, True, 1),
    "float32 compute dtype": (2, 75, 3, 8, 12, 16, jnp.float32, True, 1),
    "the published 128 / 128 head, 4 value heads over 2 key heads": (1, 192, 4, 128, 128, 64, jnp.bfloat16, False, 2),
    "a small head at 3 value heads a key head, a state handed in, not in whole chunks": (
        2, 75, 6, 8, 12, 16, jnp.bfloat16, True, 3),
    "value heads in no whole number of groups at 2 a key head (10 of 8)": (1, 130, 10, 96, 192, 64, jnp.bfloat16, True, 2),
}


def rule_inputs(n, length, heads, dk, dv, seed=0, state=False, shared=1):
    """q, k (unit length; a key head for every `shared` value heads), v, g <= 0,
    b in (0, 2) and a start state (or None), float32."""
    rng = np.random.default_rng(seed)
    draw = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    k = draw(n, length, heads // shared, dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    return (draw(n, length, heads // shared, dk) / dk ** 0.5, k, draw(n, length, heads, dv),
            -np.abs(draw(n, length, heads)) ** 2, (2 * rng.random((n, length, heads))).astype(np.float32),
            draw(n, heads, dk, dv) if state else None)


def rule_by_position(q, k, v, g, b, state=None):
    """S_t = a_t (I - b_t k_t k_t') S_{t-1} + b_t k_t v_t', o_t = S_t' q_t, in
    float64, a position at a time; a key head's q and k repeated HERE for the
    value heads that read it."""
    q, k, v, g, b = (np.asarray(x, np.float64) for x in (q, k, v, g, b))
    q, k = (np.repeat(x, v.shape[2] // x.shape[2], axis=2) for x in (q, k))
    n, length, heads, dk = q.shape
    state = np.zeros((n, heads, dk, v.shape[-1])) if state is None else np.asarray(state, np.float64)
    out = []
    for t in range(length):
        a_t, b_t, k_t = np.exp(g[:, t])[..., None, None], b[:, t][..., None, None], k[:, t]
        read = np.einsum("nhd,nhde->nhe", k_t, state)
        state = a_t * (state - b_t * k_t[..., :, None] * read[..., None, :])
        state = state + b_t * k_t[..., :, None] * v[:, t][..., None, :]
        out.append(np.einsum("nhde,nhd->nhe", state, q[:, t]))
    return np.stack(out, axis=1), state


def through_the_kernel(arrays, chunk=olmo_hybrid.DELTA_CHUNK, cd=jnp.bfloat16, notes=None, entry=interpreted):
    """(o, the last state) of the rule inside a served entry whose kernels
    run interpreted; `through_xla` is the same call outside any entry."""
    q, k, v, g, b, state = (None if x is None else jnp.asarray(x) for x in arrays)
    with entry([], delta=notes):
        o, last = olmo_hybrid.gated_delta_rule(q, k, v, g, b, state, chunk=chunk, cd=cd)
    return np.asarray(o), np.asarray(last)


through_xla = functools.partial(through_the_kernel, entry=lambda *a, **kw: contextlib.nullcontext())


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_the_kernel_is_xlas_rule_to_float32_rounding(name):
    """Outputs and the state handed back: the same pieces in the same pairs,
    float32 sums in another order."""
    n, length, heads, dk, dv, chunk, cd, state, shared = SHAPES[name]
    arrays = rule_inputs(n, length, heads, dk, dv, seed=len(name), state=state, shared=shared)
    notes = []
    got, last = through_the_kernel(arrays, chunk, cd, notes)
    want, want_last = through_xla(arrays, chunk, cd)
    assert notes == [{"kernel": "pallas", "chunk": min(chunk, length), "pieces": 2, "key_heads": heads // shared,
                      "value_heads": heads, "shared": shared}]
    assert got.shape == (n, length, heads, dv) and last.shape == (n, heads, dk, dv)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-6 * np.abs(want).max())
    np.testing.assert_allclose(last, want_last, rtol=1e-5, atol=2e-6 * np.abs(want_last).max())


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_the_kernel_is_the_rule_position_by_position_in_float64(name):
    """Two bfloat16 pieces an operand carry sixteen bits of it: a few parts
    in 1e5 of the largest output, as XLA's path reads."""
    n, length, heads, dk, dv, chunk, cd, state, shared = SHAPES[name]
    arrays = rule_inputs(n, length, heads, dk, dv, seed=len(name), state=state, shared=shared)
    got, last = through_the_kernel(arrays, chunk, cd)
    want, want_last = rule_by_position(*arrays)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4 * np.abs(want).max())
    np.testing.assert_allclose(last, want_last, rtol=1e-3, atol=1e-4 * np.abs(want_last).max())


@pytest.mark.parametrize("cut", [16, 37, 64])
def test_a_row_split_with_its_state_handed_over_is_the_whole_row(cut):
    """The state the kernel writes out once, after a row's last chunk, is the
    one it starts the next part from."""
    arrays = rule_inputs(2, 75, 3, 8, 12, seed=cut)[:5]
    whole, state = through_the_kernel(arrays + (None,), 16, jnp.float32)
    head, handed = through_the_kernel(tuple(x[:, :cut] for x in arrays) + (None,), 16, jnp.float32)
    tail, last = through_the_kernel(tuple(x[:, cut:] for x in arrays) + (handed,), 16, jnp.float32)
    np.testing.assert_allclose(np.concatenate([head, tail], axis=1), whole, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(last, state, rtol=1e-4, atol=1e-5)
    assert np.abs(handed).max() > 0.1  # a state worth handing over


def test_a_padded_row_leaves_the_state_as_it_was():
    """k = v = 0, b = 0, g = 0 (what a short row's last chunk is padded with,
    and a padded row's every position): the state handed back is the one
    handed in, to the bit, and every output reads it."""
    q, _, _, _, _, state = rule_inputs(2, 130, 3, 8, 12, seed=4, state=True)
    zeros = lambda *shape: np.zeros(shape, np.float32)  # noqa: E731
    arrays = (q, zeros(2, 130, 3, 8), zeros(2, 130, 3, 12), zeros(2, 130, 3), zeros(2, 130, 3), state)
    got, last = through_the_kernel(arrays, 64, jnp.float32)
    np.testing.assert_array_equal(last, state)
    np.testing.assert_allclose(got, np.einsum("nhde,nlhd->nlhe", state, q), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("g,b", [(0.0, 2.0), (-80.0, 2.0), (-1e4, 1.0), (-30.0, 0.0)])
def test_one_key_repeated_over_a_chunk_at_b_two_stays_finite(g, b):
    """The reflection (I - 2 k k'), whose powers neither grow nor die, and
    decays whose exp(-G) alone would overflow: every exponent the kernel
    takes is a difference under its mask; the loop in float64 agrees."""
    q, k, v, _, _, _ = rule_inputs(1, 130, 3, 8, 12, seed=5)
    k[:, 64:] = k[:, 64:65]  # one key repeated over a chunk and more
    gs, bs = np.full((1, 130, 3), g, np.float32), np.full((1, 130, 3), b, np.float32)
    want, state = rule_by_position(q, k, v, gs, bs)
    got, last = through_the_kernel((q, k, v, gs, bs, None), 64, jnp.float32)
    assert np.isfinite(got).all() and np.isfinite(last).all()
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=2e-3)
    np.testing.assert_allclose(last, state, rtol=1e-2, atol=2e-3)


@pytest.mark.parametrize("plant,value", [("OPERAND_PIECES", 1), ("STATE_DTYPE", jnp.bfloat16)],
                         ids=["one-piece operands", "a bfloat16 state"])
def test_a_planted_precision_reaches_the_kernel(monkeypatch, plant, value):
    """The kernel reads `olmo_hybrid.OPERAND_PIECES` and `STATE_DTYPE` when it
    is traced, as `_product` and the scan do: planted by name, its answer
    moves as XLA's path moves, far beyond what separates the two paths."""
    arrays = rule_inputs(1, 192, 4, 96, 192, seed=8, state=True)
    served, _ = through_the_kernel(arrays)
    exact, _ = rule_by_position(*arrays)
    monkeypatch.setattr(olmo_hybrid, plant, value)
    notes = []
    planted, planted_state = through_the_kernel(arrays, notes=notes)
    xla, xla_state = through_xla(arrays)
    assert notes[0]["pieces"] == olmo_hybrid.OPERAND_PIECES
    miss = lambda x: np.abs(x - exact).max()  # noqa: E731
    assert miss(planted) > 20 * miss(served)  # told apart through the kernel
    assert np.abs(planted - xla).max() < 0.2 * miss(planted)  # and it moves where XLA's path moves
    if plant == "STATE_DTYPE":  # the state handed back is one the planted dtype holds
        np.testing.assert_array_equal(planted_state, planted_state.astype(jnp.bfloat16).astype(np.float32))
        assert np.abs(planted_state - xla_state).max() < 0.02 * np.abs(xla_state).max()


@pytest.mark.parametrize("heads,dk,dv,want,shared", [
    (30, 96, 192, 8, 1), (10, 96, 192, 8, 1), (7, 96, 192, 7, 1), (32, 128, 128, 8, 1), (30, 64, 64, 8, 1),
    (3, 8, 12, 3, 1), (64, 96, 160, 8, 1),
    # value heads over key heads: 32 over 16 at 128 / 128 (8 over 4 key heads: 512 lanes of q and k a step); 3 a
    # key head makes the step 9 over 3; keys of 96 under 2 value heads a key head need 4 key heads for whole
    # lanes, so 8 over 4 where one to one 4 would do; and every head where the group is no fewer than all
    (32, 128, 128, 8, 2), (48, 128, 128, 9, 3), (32, 96, 64, 8, 2), (4, 128, 128, 4, 2), (6, 8, 12, 6, 3)])
def test_a_step_takes_heads_whose_columns_are_whole_lanes(heads, dk, dv, want, shared):
    """Groups of 4 at the published head (96 and 192 columns a head), two of
    them a step; the whole axis where that is all the heads there are; whole
    key heads' groups of value heads, whose KEY columns are whole lanes too."""
    got = delta_kernel.heads_a_step(heads, dk, dv, shared)
    assert got == want and got % shared == 0
    assert got == heads or (got // shared * dk % 128 == 0 and got * dv % 128 == 0)


# ------------------------------------- a key head's work once, and no copy of q or k


def _equations(jaxpr, into=("pjit", "jit", "custom_jvp_call", "custom_vjp_call", "closed_call", "remat")):
    """Every equation of a traced function, those of the functions it calls
    among them (a Pallas kernel's body left out: its blocks are its own)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name in into:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _equations(sub, into)


@pytest.mark.parametrize("where", ["the kernel's path", "xla's path"])
def test_at_32_value_heads_over_16_key_heads_q_and_k_are_never_repeated(where):
    """The rule traced at the published 32 over 16 (keys of 128; values of 64
    here, to tell them apart): `K K'` is made with 16 heads on both paths,
    and on the way into the kernel nothing holds q or k 32 heads wide, as
    `[.., 32, .., 128]` or as `[.., 32 x 128]`: the kernel's q and k operands
    are the 16 key heads as the projections lie. (XLA's path scales a key
    head's k by each value head's own decays after `T`: a value head's.)"""
    n, length, keys, heads, dk, dv, chunk = 1, 192, 16, 32, 128, 64, 64
    shaped = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    arrays = (shaped(n, length, keys, dk), shaped(n, length, keys, dk), shaped(n, length, heads, dv),
              shaped(n, length, heads), shaped(n, length, heads))
    entry = interpreted if where == "the kernel's path" else lambda *a, **kw: contextlib.nullcontext()

    def rule(*arrays):
        with entry([], delta=[]):
            return olmo_hybrid.gated_delta_rule(*arrays, cd=jnp.bfloat16, count=3)

    equations = list(_equations(jax.make_jaxpr(rule)(*arrays).jaxpr))
    grams = [eqn for eqn in equations if eqn.primitive.name == "dot_general"
             and all(v.aval.shape[-1] == dk and v.aval.shape[-2] % chunk == 0 for v in eqn.invars)]  # pieces of k (or q) on both sides
    assert grams and all(eqn.outvars[0].aval.shape[:3] == (n, length // chunk, keys) for eqn in grams)
    if where == "the kernel's path":
        shapes = [v.aval.shape for eqn in equations for v in eqn.outvars]
        wide = [s for s in shapes if s and ((s[-1] == dk and heads in s[:-1]) or s[-1] == heads * dk)]
        assert not wide, wide
        (call,) = [eqn for eqn in equations if eqn.primitive.name == "pallas_call"]
        assert [v.aval.shape for v in call.invars[1:3]] == [(n, length, keys * dk)] * 2
        assert len(grams) == 3  # K K' alone, three products of stacked pieces: Q K' is the kernel's


# --------------------------------------------------------- who takes the kernel


def test_outside_a_served_entry_the_rule_is_xlas(monkeypatch):
    """`model.apply` as the mesh executors, `shard_map` and the trainer trace
    it holds no kernel whatever the backend; a served entry on a CPU notes
    XLA's path; on a backend that answers `tpu` it notes the kernel's, once."""
    arrays = tuple(map(jnp.asarray, rule_inputs(1, 75, 3, 8, 12)[:5]))
    lowered = lambda: jax.jit(lambda *a: olmo_hybrid.gated_delta_rule(*a, chunk=16)).lower(*arrays).as_text()  # noqa: E731
    assert olmo_hybrid.delta_choice(75, 2, 16) == {"kernel": "xla", "chunk": 16, "pieces": 2}
    assert olmo_hybrid.delta_choice(75, 2, 16, (16, 32))["shared"] == 2  # the value heads that read one key head
    assert "delta_rule" not in lowered()
    with sequence.serving_attention([], delta=(notes := [])):
        assert not olmo_hybrid.takes_kernel(2048, 2) and "delta_rule" not in lowered()
    assert notes == [{"kernel": "xla", "chunk": 64, "pieces": 2},
                     {"kernel": "xla", "chunk": 16, "pieces": 2, "key_heads": 3, "value_heads": 3, "shared": 1}]  # the rule's heads
    monkeypatch.setattr(sequence.jax, "default_backend", lambda: "tpu")
    assert not olmo_hybrid.takes_kernel(2048, 2)  # outside it, on a TPU: the trainer's, an executor's
    with sequence.serving_attention([], delta=(notes := [])):
        assert olmo_hybrid.takes_kernel(2048, 2) and olmo_hybrid.takes_kernel(2048, 2)
    assert notes == [{"kernel": "pallas", "chunk": 64, "pieces": 2}]
    with sequence.serving_attention([]):  # an entry that keeps no notes of the rule still takes the kernel
        assert olmo_hybrid.takes_kernel(2048, 2)


# ------------------------------------------------- what the batcher stamps


def _serve(payloads):
    from distributed_tf_serving_tpu.serving.server import build_stack
    from distributed_tf_serving_tpu.utils.tracing import request_trace

    cfgs = load_config(os.path.join(CONFIGS, "olmo_hybrid_small.toml"))
    config = dataclasses.replace(cfgs["model"], name="M")
    cfg = dataclasses.replace(cfgs["server"], model_name="M", warmup=False)
    _registry, batcher, impl, servable, _mesh, _watcher = build_stack(cfg, model_config=config)
    try:
        count = lambda: request_trace.snapshot().get("batch.delta_kernel", {}).get("count", 0)  # noqa: E731
        before = count()
        scores = [batcher.submit(servable, p).result(timeout=600)["prediction_node"] for p in payloads]
        return np.concatenate(scores), batcher.stats, count() - before, impl.runtime_stats()["startup"]["delta_rule"]
    finally:
        batcher.stop()


def test_batcher_stamps_the_delta_rule_and_counts_its_batches(monkeypatch):
    """`startup.delta_rule` per servable on the runtime block and the batches
    that ran the kernel, beside `batches`; the scores are the XLA entry's to
    what a stack 128 wide with keys of 16 makes of float32 rounding in another
    order (3e-5 here; the tolerance at the published widths is
    test_olmo_hybrid.py's)."""
    fields = load_config(os.path.join(CONFIGS, "olmo_hybrid_small.toml"))["model"].num_fields
    rng = np.random.RandomState(3)
    payloads = [{
        "feat_ids": rng.randint(0, 1 << 40, size=(n, fields)).astype(np.int64),
        "feat_wts": rng.rand(n, fields).astype(np.float32),
    } for n in (1, 3)]
    want, stats, counted, stamp = _serve(payloads)
    assert stats.batches == 2 and stats.delta_kernel_batches == 0 and counted == 0
    assert stamp == {"M:1": {"kernel": "xla", "chunk": 64, "pieces": 2, "key_heads": 3, "value_heads": 3, "shared": 1}}
    monkeypatch.setattr(batcher_mod, "serving_attention", interpreted)
    got, stats, counted, stamp = _serve(payloads)
    assert stats.batches == 2 and stats.delta_kernel_batches == 2 and counted == 2
    assert stamp == {"M:1": {"kernel": "pallas", "chunk": 64, "pieces": 2, "key_heads": 3, "value_heads": 3, "shared": 1}}
    np.testing.assert_allclose(got, want, atol=1e-4)
