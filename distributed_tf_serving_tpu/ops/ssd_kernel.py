"""Mamba-2's SSD walk over a row's chunks as one Pallas TPU kernel whose
states never leave VMEM between a row's first chunk and its last.

XLA's path (`models/falcon_h1.py::ssd`) makes every product for ALL chunks of
a layer at once and writes the results to HBM: the chunks' own states and the
states handed in exist there as `[n, Z, H, P, N]` float32 each (268 MB at the
published widths and 4 rows), written and read back, with a `scan` between
them that hands 4.19 MB a row through HBM a step (PERF.md section 6, PR 54
and PR 55). Here a grid step is one (row, group of heads, chunk), the chunk
axis last and in order, and a head's chunk is the module docstring's algebra
of `models/falcon_h1.py` to the letter:

  M = exp(cum_i - cum_j) where j <= i, else 0        from the chunk's running sum, [1, C] as it lies
  Y = (M o (C B')) (dt x) + exp(cum) o (C S')        `C B'` ONCE a step: the step's heads share B and C
  S <- exp(cum_last) S + (exp(cum_last - cum) dt x)' B

every exponent a difference <= 0 under its mask. The state of the heads in
flight is float32 VMEM scratch, held TURNED, `S' [N, P]`: the read is then
`C S'` and the update `B' (...)` with `B'` turned once a step for all its
heads, so no product turns an operand a head. The start state is turned in at
a row's chunk 0 and the state after the last chunk turned out once; the
chunks' own states and the states handed in never exist in HBM. `+ D x`, the
gate and the gated norm stay XLA's, as do `dt` and its running sum.

Operands enter the MXU as `count` pieces of the compute dtype in the pairs
`i + j < count`, accumulation and everything else is float32, and the state
is rounded to `state_dtype` after every chunk (float32: a no-op) as the XLA
path's carry is: the result is that path's to float32 rounding in another
order of additions (tests/test_ssd_kernel.py, interpreted on the CPU;
tests/test_tpu_compile.py compiles it for a v5e).

x and y cross the kernel as they lie, `[n, L, H x P]`, B and C as
`[n, L, G x N]`, with no turn on either side: a block is a chunk's rows and
the columns of a step's heads (a head's tile a static slice of the block's
lanes: whole lane tiles at Falcon-H1's 128-wide heads, HALF a lane tile at
Nemotron-H's 64, which Mosaic takes as it takes the delta rule's 96) or of
their group. The heads a step divide a group's, so a step never
straddles two groups and none hangs over the array's edge; where no such
count has whole lanes (a test's narrow heads) one step takes every head and
every group, and the whole axis is a legal block whatever its width.

Where the convolution's kernel leaves x, B and C side by side in ONE array
`[n, L, H x P | G x N | G x N]` (ops/conv_kernel.py), that array crosses
three times and the three blocks are windows of it: x's at the lanes it had,
B's and C's offset by the whole blocks that lie before them (`windows_fit`:
64 and 72 blocks of 128 lanes at Nemotron-H's widths, 16 and 18 of 256 at
Falcon-H1's). No copy of x, B or C out of it exists (1.34 GB a layer read and
written at Nemotron-H's widths: ISSUE 63); the kernel's body is the same.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention_kernel import LANES, _pieces, pieces_held

# The float32 bytes of state a grid step holds in scratch at most, which sets
# the heads a step (`heads_a_step`): 8 of Falcon-H1's `[128, 256]` states (128
# KB each, x and y tiles 64 KB each a chunk of 128: the states, the start and
# end blocks and the step's blocks twice for the pipeline are 8.1 MB in the
# compiled step, inside the 16 MiB a kernel has by default; what a kernel
# claims beyond it is taken from XLA's prefetch of the step's weights, PERF.md
# section 6, PR 48; 16 heads, a whole group, ask for 18.4 MB; on the v5e the
# SSD of a layer read 2.85 ms at 8 heads a step and 3.06 at 4, by the host's
# clock, a call alone: PERF.md section 6, PR 55), and a whole group of 16 of
# Nemotron-H's `[64, 128]` states (32 KB each, half a lane tile wide: the
# step's x and y blocks are the same 1,024 lanes as Falcon-H1's 8 heads of
# 128, its B and C one group's 128; PERF.md section 6, PR 60 has the chip's
# reading).
STATE_BYTES = 1 << 20


def _stacked(xs: list) -> list:
    """For each piece j of the other side, x's pieces that pair with it
    (`i + j < pieces`) one under the other: one product a piece of the other
    side."""
    return [xs[0] if len(xs) - j == 1 else jnp.concatenate(xs[:len(xs) - j], axis=0) for j in range(len(xs))]


def _product(tops: list, ys: list) -> jax.Array:
    """`sum over i + j < pieces of xs[i] ys[j]` in float32 from `_stacked(xs)`
    and y's pieces (x's rows are the result's)."""
    rows, out = tops[-1].shape[0], None
    for top, y in zip(tops, ys):
        wide = jnp.dot(top, y, preferred_element_type=jnp.float32)
        for i in range(top.shape[0] // rows):
            part = wide[i * rows:(i + 1) * rows]
            out = part if out is None else out + part
    return out


def _kernel(dt_ref, total_ref, x_ref, b_ref, c_ref, start_ref, y_ref, end_ref, state, *,
            held, cd, state_dtype, chunk, heads, per, width, wide):
    z = pl.program_id(2)
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    diagonal, lower = i == j, j <= i
    cut = functools.partial(_pieces, cd=cd, held=held)

    @pl.when(z == 0)
    def _start():
        for h in range(heads):
            state[h] = start_ref[h].T

    shared = []  # a group of the step: (C B' [C, C], C's pieces stacked, B' [N, C]'s pieces stacked)
    for g in range(heads // per):
        lanes = slice(g * wide, (g + 1) * wide)
        b_turned, c = cut(b_ref[:, lanes].T), _stacked(cut(c_ref[:, lanes]))
        shared.append((_product(c, b_turned), c, _stacked(b_turned)))

    for h in range(heads):  # a head's columns of the step's lanes
        scores, c, b_turned = shared[h // per]
        columns = slice(h * width, (h + 1) * width)
        across = total_ref[h, pl.ds(z, 1), :]  # cum_j [1, C] as it lies; cum_i and dt_i [C, 1] from the diagonal
        down = jnp.sum(jnp.where(diagonal, across, 0.0), axis=1, keepdims=True)
        steps = jnp.sum(jnp.where(diagonal, dt_ref[h, pl.ds(z, 1), :], 0.0), axis=1, keepdims=True)
        left = down[chunk - 1:chunk, :]  # cum_last [1, 1]
        # M_ij = exp(cum_i - cum_j) where j <= i (there the difference is <= 0), else 0
        decay = jnp.where(lower, jnp.exp(jnp.minimum(down - across, 0.0)), 0.0)
        fed = x_ref[:, columns] * steps  # dt x
        s = state[h]
        y_ref[:, columns] = _product(_stacked(cut(decay * scores)), cut(fed)) + jnp.exp(down) * _product(c, cut(s))
        s = jnp.exp(left) * s + _product(b_turned, cut(fed * jnp.exp(left - down)))
        state[h] = s.astype(state_dtype).astype(jnp.float32)

    @pl.when(z == pl.num_programs(2) - 1)
    def _end():
        for h in range(heads):
            end_ref[h] = state[h].T


def heads_a_step(heads: int, groups: int, width: int, wide: int) -> int:
    """Heads a grid step takes: the most whose `[width, wide]` float32 states
    fit STATE_BYTES, that divide a group's heads and whose columns are whole
    lanes (an even count of 64-wide heads); every head (and every group) where
    no count does (a test's narrow heads: the whole axis is a legal block
    whatever its width)."""
    per = heads // groups
    most = min(per, max(1, STATE_BYTES // (4 * width * wide)))
    fit = [h for h in range(1, most + 1) if per % h == 0 and h * width % LANES == 0]
    return max(fit) if fit else heads


def groups_lanes(heads: int, groups: int, width: int, wide: int) -> int:
    """Lanes of a grid step's B and C blocks: its heads' group's, or every
    group's where a step takes every head."""
    per = heads // groups
    return -(-heads_a_step(heads, groups, width, wide) // per) * wide


def windows_fit(heads: int, groups: int, width: int, wide: int) -> bool:
    """Whether x, B and C can cross as three windows of ONE array
    `[n, L, H x P | G x N | G x N]`: a step's blocks are whole lanes (no
    whole-axis block, which in the one array is another axis) and B and C
    start at whole blocks of theirs."""
    lanes = groups_lanes(heads, groups, width, wide)
    return (heads_a_step(heads, groups, width, wide) * width % LANES == 0 and lanes % LANES == 0
            and heads * width % lanes == 0 and groups * wide % lanes == 0)


@functools.partial(jax.jit, static_argnames=("heads", "groups", "cd", "count", "state_dtype", "interpret"))
def chunk_walk(dt, total, x, b, c, start, *, heads: int, groups: int, cd, count: int, state_dtype=jnp.float32,
               interpret: bool = False):
    """The SSD's chunks in order: `y [n, L, H x P]` and the state after the
    last chunk `[n, H, P, N]`, float32.

    dt     `[n, H, Z, C]` float32, the time steps of a row's Z chunks of C positions (the caller pads with
           dt = 0, which feeds nothing and forgets nothing)
    total  `[n, H, Z, C]` float32, the running sum of `dt a` inside each chunk
    x      `[n, L, H x P]` float32 as the convolution leaves it, L = Z x C
    b, c   `[n, L, G x N]` float32; head h reads group `h // (H / G)`. Or both None, and `x` is
           `[n, L, H x P | G x N | G x N]`: x, B and C side by side, read where they lie (`windows_fit` asked first)
    start  `[n, H, P, N]` float32, the state before the first position

    Activations enter the products as `count` pieces of `cd` in the pairs
    `i + j < count`; the state is rounded to `state_dtype` after every chunk."""
    n, _, steps, chunk = total.shape
    width, wide = start.shape[2:]
    held, group = pieces_held(cd, count), heads_a_step(heads, groups, width, wide)
    per = min(group, heads // groups)  # heads of a step that share a group; the step spans `group // per` groups
    pairs = held * (held + 1) // 2

    def a_row(*shape):  # fetched once a (row, group of heads): the index does not move with the chunk
        return pl.BlockSpec((None, group) + shape, lambda r, g, z: (r, g, 0, 0))

    heads_lanes = pl.BlockSpec((None, chunk, group * width), lambda r, g, z: (r, z, g))
    lanes = groups_lanes(heads, groups, width, wide)

    def of_groups(before: int):  # The step's groups, the one its heads lie in or all of them, `before` lanes in
        return pl.BlockSpec((None, chunk, lanes), lambda r, g, z: (r, z, before // lanes + g * group // (heads // groups)))

    one = b is None  # x | B | C in one array: three windows of it
    sizes = 2 * n * steps * chunk * heads * width, 2 * n * steps * chunk * wide * heads // per
    x, b, c = (x, x, x) if one else (x, b, c)
    products = chunk * chunk * width + 2 * chunk * width * wide
    return pl.pallas_call(
        functools.partial(_kernel, held=held, cd=cd, state_dtype=state_dtype, chunk=chunk, heads=group, per=per,
                          width=width, wide=wide),
        out_shape=(jax.ShapeDtypeStruct((n, steps * chunk, heads * width), jnp.float32),
                   jax.ShapeDtypeStruct(start.shape, jnp.float32)),
        grid=(n, heads // group, steps),
        in_specs=[a_row(steps, chunk), a_row(steps, chunk), heads_lanes,
                  of_groups(heads * width if one else 0), of_groups(heads * width + groups * wide if one else 0),
                  a_row(width, wide)],
        out_specs=(heads_lanes, a_row(width, wide)),
        scratch_shapes=[pltpu.VMEM((group, wide, width), jnp.float32)],
        # A row's chunks in order: the state in scratch is the last chunk's.
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * pairs * n * steps * (heads * products + heads // per * chunk * chunk * wide),
            transcendentals=n * heads * steps * chunk * (chunk + 3),
            bytes_accessed=4 * (dt.size + total.size + sum(sizes) + 2 * start.size)),
        interpret=interpret,
        name="ssd_chunks",
    )(dt, total, x, b, c, start)
