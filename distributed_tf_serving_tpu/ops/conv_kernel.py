"""The Mamba-2 mixer's causal depthwise convolution, its bias and its silu as
one Pallas TPU kernel that reads its channels where they lie in a wider array
and writes them once.

XLA's form (`models/sequence.py::causal_conv`, which stays the plain form
this kernel is tested against and what every other path runs) wants an array
of its own: in `models/falcon_h1.py::ssm` the channels `x | B | C` of the
input projection `[n, L, z | x | B | C | dt]` were first sliced out of it
(read and written once), then padded, read at four sublane-misaligned
windows and written, and then x, B and C each copied out of the result for
the SSD's kernel: 4.0 GB a layer at Nemotron-H's widths where the work needs
the 10,240 channels read once and written once, 1.34 GB (ISSUE 63). Here the
kernel's blocks stand at the lane offset of the channels in the projection's
array, which crosses whole, and the result is ONE array `[n, L, channels]`
that `ops/ssd_kernel.py::chunk_walk` reads three windows of.

A grid step is one (row, block of lanes, block of positions), every axis
parallel: the `taps - 1` positions before a block come in as a second block of
the same array, the 8 rows (one float32 sublane tile) that end where the block
starts, read as zeros at a row's first block, so nothing is carried from step
to step. Inside a step the block is walked a sublane tile at a time, a few
lane tiles wide, so that a tile's whole chain (the windows, the taps, the
bias, the silu) stays in registers: window `s` (the positions `s` before) of a
tile is the tile rolled down by `s` sublanes, its first `s` rows taken from
the tile before rolled likewise, which the walk carries: one roll a tile and
window, no misaligned read.

Float32 throughout; the taps in `causal_conv`'s order (k = 0, the oldest
position, first) and the bias before the silu: the result is that function's
to float32 rounding (tests/test_conv_kernel.py, interpreted on the CPU;
tests/test_tpu_compile.py compiles it for a v5e).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention_kernel import LANES

SUBLANES = 8  # a float32 tile's rows: the walk's step, and the rows of the block that holds the positions before
# Lanes a grid step's blocks are wide at most: the widest of these that divides both the channels and their offset.
BLOCK_LANES = (8 * LANES, 4 * LANES, 2 * LANES, LANES)
# The float32 bytes of a grid step's block at most (`positions_a_block`): the block in and the block out, twice each
# for the pipeline, are 8 MiB of the 16 a kernel has by default (what a kernel claims beyond it is taken from XLA's
# prefetch of the step's weights: PERF.md section 6, PR 48), and a step moves 4 MiB against its fixed 0.35 us.
BLOCK_BYTES = 2 << 20
UNROLL = (8, 4, 2, 1)  # tiles a turn of the walk's loop: the most of these that divides a block's
WALK_LANES = 256  # lanes of the walk's tile: two vregs an array, so the taps, the carried rolls and the chain fit the 64


def lanes_a_block(offset: int, channels: int) -> int:
    """Lanes of a grid step's blocks: the widest of BLOCK_LANES that divides
    both the `channels` convolved and their `offset` in the array they lie
    in; 0 where none does (the caller keeps XLA's form)."""
    return next((lanes for lanes in BLOCK_LANES if offset % lanes == 0 and channels % lanes == 0), 0)


def positions_a_block(length: int, lanes: int) -> int:
    """Positions of a grid step's blocks: the most whole sublane tiles that
    divide `length` and keep a block of `lanes` lanes inside BLOCK_BYTES; 0
    where `length` is no whole number of sublane tiles."""
    most = BLOCK_BYTES // (4 * lanes)
    return next((rows for rows in range(min(most, length) // SUBLANES * SUBLANES, 0, -SUBLANES)
                 if length % rows == 0), 0)


def whole_blocks(offset: int, channels: int, length: int, taps: int) -> tuple[int, int, str]:
    """(the lanes and the positions of a grid step's blocks, "") where the
    kernel takes the shapes, else (0, 0, why): `lanes` (the channels or
    their offset are no whole blocks of lanes), `positions` (`length` is no
    whole number of sublane tiles) or `taps` (the `taps - 1` positions
    before a block are more than the one tile that holds them)."""
    lanes = lanes_a_block(offset, channels)
    rows = positions_a_block(length, lanes) if lanes else 0
    why = "lanes" if not lanes else "positions" if not rows else "" if 0 < taps <= SUBLANES + 1 else "taps"
    return (0, 0, why) if why else (lanes, rows, "")


def _kernel(x_ref, before_ref, w_ref, *rest, taps: int, walk: int):
    bias_ref, o_ref = rest if len(rest) == 2 else (None, rest[0])
    rows, lanes = o_ref.shape
    first = pl.program_id(2) == 0
    row = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, walk), 0)
    tiles = rows // SUBLANES
    unroll = next(u for u in UNROLL if tiles % u == 0)

    def rolled(tile):  # the tile `s` sublanes down, for each window but the tile's own
        return tuple(pltpu.roll(tile, s, 0) for s in range(1, taps))

    for at in range(0, lanes, walk):
        columns = slice(at, at + walk)
        w = [jnp.broadcast_to(w_ref[k:k + 1, columns], (SUBLANES, walk)) for k in range(taps)]
        bias = None if bias_ref is None else jnp.broadcast_to(bias_ref[:, columns], (SUBLANES, walk))

        def step(i, before):
            for u in range(unroll):  # tiles a turn of the loop: the scheduler's room to hide a tile's reads and rolls
                at_row = pl.multiple_of((i * unroll + u) * SUBLANES, SUBLANES)
                tile = x_ref[pl.ds(at_row, SUBLANES), columns]
                here = rolled(tile)
                y = None
                for k in range(taps):  # tap k reads the positions taps - 1 - k before: causal_conv's order
                    s = taps - 1 - k
                    window = tile if s == 0 else jnp.where(row < s, before[s - 1], here[s - 1])
                    y = window * w[k] if y is None else y + window * w[k]
                o_ref[pl.ds(at_row, SUBLANES), columns] = jax.nn.silu(y if bias is None else y + bias)
                before = here
            return before

        # before a row's first position: zeros
        jax.lax.fori_loop(0, tiles // unroll, step, rolled(jnp.where(first, 0.0, before_ref[:, columns])))


@functools.partial(jax.jit, static_argnames=("offset", "channels", "interpret"))
def causal_conv(x, w, b=None, *, offset: int = 0, channels: int | None = None, interpret: bool = False):
    """silu of the causal depthwise convolution along the positions of the
    `channels` columns of `x [n, L, W]` from column `offset` on (all of them
    where None), float32: `[n, L, channels]`, as
    `sequence.causal_conv(x[..., offset:offset + channels], w, b)`.

    w  `[channels, taps]`: tap k reads position t - (taps - 1) + k
    b  `[channels]` or None, added before the silu

    The shapes are whole blocks (`whole_blocks`): the caller asks first
    (`models/falcon_h1.py::conv_choice`)."""
    n, length, _ = x.shape
    channels = x.shape[-1] - offset if channels is None else channels
    taps = w.shape[1]
    lanes, rows, why = whole_blocks(offset, channels, length, taps)
    if why:
        raise ValueError(f"{channels} channels at {offset} over {length} positions, {taps} taps: no whole blocks ({why})")
    shift, tiles = offset // lanes, rows // SUBLANES
    operands = [x, x, w.astype(jnp.float32).T]
    specs = [
        pl.BlockSpec((None, rows, lanes), lambda r, j, t: (r, t, shift + j)),
        # the sublane tile that ends where the block starts (a row's first block reads its own first: zeros then)
        pl.BlockSpec((None, SUBLANES, lanes), lambda r, j, t: (r, jnp.maximum(t * tiles - 1, 0), shift + j)),
        pl.BlockSpec((taps, lanes), lambda r, j, t: (0, j)),
    ]
    if b is not None:
        operands.append(b.astype(jnp.float32).reshape(1, channels))
        specs.append(pl.BlockSpec((1, lanes), lambda r, j, t: (0, j)))
    size = n * length * channels
    return pl.pallas_call(
        functools.partial(_kernel, taps=taps, walk=min(WALK_LANES, lanes)),
        out_shape=jax.ShapeDtypeStruct((n, length, channels), jnp.float32),
        grid=(n, channels // lanes, length // rows),
        in_specs=specs,
        out_specs=pl.BlockSpec((None, rows, lanes), lambda r, j, t: (r, t, j)),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=size * (2 * taps + 4), transcendentals=size, bytes_accessed=4 * (2 * size + size * SUBLANES // rows)),
        interpret=interpret,
        name="causal_conv",
    )(*operands)
