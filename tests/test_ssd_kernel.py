"""Mamba-2's SSD chunk walk as one Pallas kernel (ops/ssd_kernel.py), run here
in interpret mode: against XLA's `falcon_h1.ssd` on the same operands to
float32 rounding and against the recurrence position by position in float64,
at the published head and state (128 by 256) and at small ones; a length that
is no multiple of the chunk, a state handed in and the one handed back, one
group and two, a group whose heads are no multiple of a step's, heads of 64 (half a lane tile) sixteen a step, the fastest heads
of Mamba-2's own init; the pieces and the planted state told apart THROUGH the
kernel; who takes it. What the batcher stamps and counts is in
test_falcon_h1.py. Times come from the chip (PERF.md section 6, PR 55); the
compile for a v5e is in test_tpu_compile.py."""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import falcon_h1, sequence
from distributed_tf_serving_tpu.ops import ssd_kernel

interpreted = functools.partial(sequence.serving_attention, interpret=True)

# name -> (rows, length, heads, a head's width, groups, the state's width, chunk, compute dtype, a state handed in)
SHAPES = {
    "the published head and state, two groups, a step a group": (1, 300, 16, 128, 2, 256, 128, jnp.bfloat16, False),
    "the published head, a length that is no multiple of the chunk": (2, 150, 8, 128, 1, 256, 64, jnp.bfloat16, True),
    "a group of 12 heads, 6 a step": (1, 130, 12, 128, 1, 64, 64, jnp.bfloat16, True),
    # Nemotron-H's (PR 60): a head half a lane tile wide, a state `[64, 128]`, 16 heads a group and a step
    "heads of 64, a whole group of 16 a step": (1, 200, 32, 64, 2, 128, 128, jnp.bfloat16, True),
    "narrow heads, every head and both groups in one step": (2, 75, 4, 16, 2, 32, 16, jnp.bfloat16, False),
    "narrow heads, a state handed in": (2, 75, 4, 16, 2, 32, 16, jnp.bfloat16, True),
    "one group": (2, 75, 4, 16, 1, 32, 16, jnp.bfloat16, True),
    "a row shorter than a chunk": (2, 40, 4, 16, 2, 32, 64, jnp.bfloat16, True),
    "float32 compute dtype": (2, 75, 4, 16, 2, 32, 16, jnp.float32, True),
}


def ssd_inputs(n, length, heads, width, groups, wide, seed=0, state=False):
    """x, dt > 0, a < 0 (from a head whose state lasts the row to one that
    forgets inside a chunk), B, C and a start state (or None), float32."""
    rng = np.random.default_rng(seed)
    draw = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (n, length, heads))).astype(np.float32)
    a = -np.exp(np.linspace(np.log(0.05), np.log(16.0), heads)).astype(np.float32)
    return (draw(n, length, heads, width), dt, a, draw(n, length, groups, wide), draw(n, length, groups, wide),
            draw(n, heads, width, wide) if state else None)


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


def through_the_kernel(arrays, chunk, cd=jnp.bfloat16, entry=interpreted, **kw):
    """(y, the last state) of the SSD inside a served entry whose kernels run
    interpreted; `through_xla` is the same call outside any entry."""
    x, dt, a, b, c, state = (None if v is None else jnp.asarray(v) for v in arrays)
    with entry([]):
        y, last = falcon_h1.ssd(x, dt, a, b, c, state, chunk=chunk, cd=cd, **kw)
    return np.asarray(y), np.asarray(last)


through_xla = functools.partial(through_the_kernel, entry=lambda *a, **kw: contextlib.nullcontext())


def traced(arrays, chunk, **kw) -> str:
    """The SSD's jaxpr: a `pallas_call` where the kernel walks the chunks, a `scan` where XLA's path does."""
    args = [jnp.asarray(v) for v in arrays if v is not None]
    return str(jax.make_jaxpr(lambda *v: falcon_h1.ssd(*v, chunk=chunk, **kw))(*args))


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_the_kernel_is_xlas_ssd_to_float32_rounding(name):
    """Outputs and the state handed back: the same pieces in the same pairs,
    float32 sums of up to 256 terms in another order (a part in 1e5 of the
    largest output; two pieces an operand leave ten times that)."""
    n, length, heads, width, groups, wide, chunk, cd, state = SHAPES[name]
    arrays = ssd_inputs(n, length, heads, width, groups, wide, seed=len(name), state=state)
    got, last = through_the_kernel(arrays, chunk, cd)
    want, want_last = through_xla(arrays, chunk, cd)
    assert got.shape == (n, length, heads, width) and last.shape == (n, heads, width, wide)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * np.abs(want).max())
    np.testing.assert_allclose(last, want_last, rtol=1e-5, atol=1e-5 * np.abs(want_last).max())


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_the_kernel_is_the_recurrence_position_by_position_in_float64(name):
    """Two bfloat16 pieces an operand carry sixteen bits of it: a few parts
    in 1e5 of the largest output, as XLA's path reads."""
    n, length, heads, width, groups, wide, chunk, cd, state = SHAPES[name]
    arrays = ssd_inputs(n, length, heads, width, groups, wide, seed=len(name), state=state)
    got, last = through_the_kernel(arrays, chunk, cd)
    want, want_last = ssd_by_position(*arrays)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4 * np.abs(want).max())
    np.testing.assert_allclose(last, want_last, rtol=1e-3, atol=1e-4 * np.abs(want_last).max())


@pytest.mark.parametrize("cut", [16, 37, 64])
def test_a_row_split_with_its_state_handed_over_is_the_whole_row(cut):
    """The state the kernel writes out once, after a row's last chunk, is the
    one it starts the next part from."""
    arrays = ssd_inputs(2, 75, 4, 16, 2, 32, seed=cut)[:5]
    split = lambda lo, hi: tuple(v if v.ndim == 1 else v[:, lo:hi] for v in arrays)  # noqa: E731 - `a` is a head's
    whole, state = through_the_kernel(arrays + (None,), 16, jnp.float32)
    head, handed = through_the_kernel(split(0, cut) + (None,), 16, jnp.float32)
    tail, last = through_the_kernel(split(cut, 75) + (handed,), 16, jnp.float32)
    np.testing.assert_allclose(np.concatenate([head, tail], axis=1), whole, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(last, state, rtol=1e-4, atol=1e-5)
    assert np.abs(handed).max() > 0.01  # a state worth handing over


def test_a_padded_row_leaves_the_state_as_it_was():
    """dt = 0 (what a short row's last chunk is padded with, and a padded
    row's every position) feeds nothing and forgets nothing: the state handed
    back is the one handed in, to the bit, and every output reads it."""
    x, _, a, b, c, state = ssd_inputs(2, 130, 4, 16, 2, 32, seed=4, state=True)
    got, last = through_the_kernel((x, np.zeros((2, 130, 4), np.float32), a, b, c, state), 64, jnp.float32)
    np.testing.assert_array_equal(last, state)
    want = np.einsum("nhps,nlhs->nlhp", state, np.repeat(c, 2, axis=2))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("a,dt", [(-16.0, 0.1), (-16.0, 30.0), (-1e4, 1.0), (0.0, 1.0), (-1e-6, 1e-3)],
                         ids=["the fastest heads of Mamba-2's own init", "a decay of exp(-480) a position",
                              "a decay of exp(-1e4)", "a decay of 1", "a decay of 1 - 1e-9"])
def test_decays_near_zero_and_near_one_stay_finite(a, dt):
    """exp(-cum_j) alone overflows float32 past a running sum of 88 (`A` 16
    at `dt` 0.1 passes it inside a chunk of 64): every exponent the kernel
    takes is a difference under its mask; the loop in float64 agrees."""
    x, _, _, b, c, _ = ssd_inputs(1, 130, 4, 16, 2, 32, seed=5)
    dts, heads = np.full((1, 130, 4), dt, np.float32), np.full((4,), a, np.float32)
    want, state = ssd_by_position(x, dts, heads, b, c)
    got, last = through_the_kernel((x, dts, heads, b, c, None), 64, jnp.float32)
    assert np.isfinite(got).all() and np.isfinite(last).all()
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(last, state, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("count", [1, 2, 3])
def test_the_pieces_reach_the_kernel(monkeypatch, count):
    """The kernel reads `falcon_h1.OPERAND_PIECES` when it is traced, as
    `_product` does: one piece misses the recurrence by a bfloat16's rounding,
    two by sixteen bits', three by float32's, and each is XLA's path at the
    same pieces far more closely than the pieces differ."""
    arrays = ssd_inputs(1, 192, 8, 128, 1, 256, seed=8, state=True)
    exact, _ = ssd_by_position(*arrays)
    monkeypatch.setattr(falcon_h1, "OPERAND_PIECES", count)
    got, _ = through_the_kernel(arrays, 64)
    xla, _ = through_xla(arrays, 64)
    miss = np.abs(got - exact).max() / np.abs(exact).max()
    low, high = {1: (3e-4, 2e-2), 2: (1e-6, 1e-4), 3: (0.0, 3e-6)}[count]
    assert low <= miss < high
    assert np.abs(got - xla).max() <= max(0.1 * np.abs(got - exact).max(), 2e-6 * np.abs(exact).max())


def test_a_planted_bfloat16_state_reads_what_xlas_planted_state_reads(monkeypatch):
    """`falcon_h1.STATE_DTYPE` planted by name (the benchmark's readings do):
    the kernel rounds the state after every chunk as the scan's carry is, so
    its answer moves where XLA's path moves, far beyond what separates the two
    paths, and the state handed back is one the planted dtype holds."""
    arrays = ssd_inputs(1, 192, 8, 128, 1, 256, seed=8, state=True)
    arrays = arrays[:2] + (arrays[2] * 0.02,) + arrays[3:]  # heads slow enough for the state to matter
    served, _ = through_the_kernel(arrays, 64)
    exact, _ = ssd_by_position(*arrays)
    monkeypatch.setattr(falcon_h1, "STATE_DTYPE", jnp.bfloat16)
    planted, planted_state = through_the_kernel(arrays, 64)
    xla, xla_state = through_xla(arrays, 64)
    miss = lambda v: np.abs(v - exact).max()  # noqa: E731
    assert miss(planted) > 20 * miss(served)  # told apart through the kernel
    assert np.abs(planted - xla).max() < 0.2 * miss(planted)  # and it moves where XLA's path moves
    np.testing.assert_array_equal(planted_state, planted_state.astype(jnp.bfloat16).astype(np.float32))
    assert np.abs(planted_state - xla_state).max() < 0.02 * np.abs(xla_state).max()


@pytest.mark.parametrize("heads,groups,width,wide,want", [
    (32, 2, 128, 256, 8), (32, 1, 128, 256, 8), (24, 1, 64, 512, 8), (12, 1, 128, 256, 6), (14, 2, 128, 256, 7),
    (6, 2, 128, 256, 3), (16, 2, 16, 32, 8), (4, 2, 16, 32, 4), (8, 2, 16, 32, 8),
    (128, 8, 64, 128, 16), (24, 1, 64, 256, 12), (128, 8, 64, 2048, 2)])
def test_a_steps_heads_divide_a_groups_and_are_whole_lanes(heads, groups, width, wide, want):
    """8 of a group's 16 at Falcon-H1's published widths and a whole group of
    16 at Nemotron-H's, whose states are a quarter the bytes; the most whose
    states fit STATE_BYTES that divide a group's heads, so a step never
    straddles two groups nor hangs over the array's edge (two heads of 64 at
    the least: whole lanes); every head, and every group with them, where no
    such count's columns are whole lanes."""
    got = ssd_kernel.heads_a_step(heads, groups, width, wide)
    assert got == want and (got == heads or (heads // groups % got == 0 and got * width % 128 == 0))


@pytest.mark.parametrize("heads,groups,width,wide,lanes,fit", [
    (32, 2, 128, 256, 256, True),  # Falcon-H1's: B 16 and C 18 blocks of 256 lanes in
    (128, 8, 64, 128, 128, True),  # Nemotron-H's: 64 and 72 blocks of 128
    (4, 2, 64, 128, 128, True), (16, 2, 128, 256, 256, True),
    (8, 2, 16, 32, 64, False),  # every head a step: the whole axis is a block of its own array alone
    (4, 2, 16, 32, 64, False), (12, 1, 128, 64, 64, False),  # B and C half a lane tile wide
])
def test_where_x_b_and_c_can_cross_as_windows_of_one_array(heads, groups, width, wide, lanes, fit):
    """Whole lanes a block and B and C starting at whole blocks of theirs
    (ops/conv_kernel.py leaves x | B | C side by side); where they do not,
    `falcon_h1.ssd` hands the three arrays as before."""
    assert ssd_kernel.groups_lanes(heads, groups, width, wide) == lanes
    assert ssd_kernel.windows_fit(heads, groups, width, wide) is fit


@pytest.mark.parametrize("name", ["the published head and state, two groups, a step a group",
                                  "heads of 64, a whole group of 16 a step"])
def test_three_windows_of_one_array_are_the_three_arrays(name):
    """`chunk_walk` handed x | B | C in one array and no `b` or `c` reads
    what it reads handed the three apart, to the bit: the same blocks, found
    at other indices."""
    n, length, heads, width, groups, wide, chunk, cd, _ = SHAPES[name]
    length = -(-length // chunk) * chunk
    x, dt, a, b, c, _ = ssd_inputs(n, length, heads, width, groups, wide, seed=3)
    steps = jnp.moveaxis(jnp.asarray(dt).reshape(n, length // chunk, chunk, heads), 3, 1)
    total = jnp.cumsum(steps * jnp.asarray(a)[:, None, None], axis=3)
    flat = [jnp.asarray(v).reshape(n, length, -1) for v in (x, b, c)]
    walk = functools.partial(ssd_kernel.chunk_walk, heads=heads, groups=groups, cd=jnp.dtype(cd), count=2, interpret=True)
    start = jnp.zeros((n, heads, width, wide), jnp.float32)
    apart = walk(steps, total, *flat, start)
    whole = walk(steps, total, jnp.concatenate(flat, axis=-1), None, None, start)
    assert ssd_kernel.windows_fit(heads, groups, width, wide) and whole[0].shape == (n, length, heads * width)
    for got, want in zip(whole, apart):
        np.testing.assert_array_equal(got, want)


# --------------------------------------------------------- who takes the kernel


def test_outside_a_served_entry_the_ssd_is_xlas(monkeypatch):
    """`model.apply` as the mesh executors, `shard_map` and the trainer trace
    it holds no kernel whatever the backend; a served entry on a CPU says
    XLA's path; on a backend that answers `tpu` it says the kernel's, and the
    last layer's hand-overs (`last_only`) stay XLA's scan there too."""
    arrays = ssd_inputs(1, 75, 4, 16, 2, 32)[:5]
    s = {"chunk": 16, "ssm_heads": 4, "ssm_head": 16, "state": 32}
    xla = {"path": "xla", "chunk": 16, "state_bytes_a_row": 4 * 16 * 32 * 4, "heads": [4, 16, 32]}
    assert falcon_h1.ssd_choice(75, s) == xla and "pallas_call" not in traced(arrays, 16)
    with sequence.serving_attention([], ssd=(notes := [])):
        falcon_h1.note_ssd(75, s)
        assert "pallas_call" not in traced(arrays, 16)
    assert notes == [xla]
    monkeypatch.setattr(sequence.jax, "default_backend", lambda: "tpu")
    assert falcon_h1.ssd_choice(75, s) == xla  # outside it, on a TPU: the trainer's, an executor's
    assert "pallas_call" not in traced(arrays, 16) and "scan" in traced(arrays, 16)
    with sequence.serving_attention([], ssd=(notes := [])):
        falcon_h1.note_ssd(75, s)
        falcon_h1.note_ssd(75, s)
        assert "pallas_call" in traced(arrays, 16) and "scan" not in traced(arrays, 16)
        assert "pallas_call" not in traced(arrays, 16, last_only=True) and "scan" in traced(arrays, 16, last_only=True)
    assert notes == [dict(xla, path="pallas")] and not falcon_h1.takes_kernel()
    with sequence.serving_attention([]):  # an entry that keeps no notes of the SSD still takes the kernel
        falcon_h1.note_ssd(75, s)
        assert "pallas_call" in traced(arrays, 16)


def test_the_last_position_alone_is_the_kernels_last_position():
    """`last_only` inside a served entry (XLA's hand-overs and one read) is
    the last `y` of the kernel's walk over the same row."""
    arrays = ssd_inputs(2, 75, 4, 16, 2, 32, seed=2, state=True)
    whole, state = through_the_kernel(arrays, 16, jnp.float32)
    only, last = through_the_kernel(arrays, 16, jnp.float32, last_only=True)
    assert only.shape == (2, 1, 4, 16)
    np.testing.assert_allclose(only, whole[:, -1:], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(last, state, rtol=1e-4, atol=1e-5)
