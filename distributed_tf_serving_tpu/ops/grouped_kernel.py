"""The held experts' part of a routed layer as ONE pass of Pallas TPU kernels
over row tiles, each of which finds its expert's weights.

XLA's path (`models/routed.py::held_experts`' loop) walks the tiles in a loop,
a padded tile of 256 rows a step: a fifth to a third of the rows computed
padding, a 256-row scatter-add into a carried `[T, H]` a tile (PERF.md section
6, PR 51). Here the (token, held expert) pairs of a layer, laid out once,
expert by expert, each expert's run rounded up to whole tiles of TILE rows
(`models/routed.py::lay_out`: one sort, whatever the number of experts held),
are walked by two kernels over the tiles that hold a token (a grid whose
length the routing decides: a dynamic grid bound) with the tile's expert read
from scalar memory by the weights' index maps, so that no weight is sliced or
copied in HBM:

- `grouped_gate_up`: a tile's rows are gathered from the tokens by row copies
  HBM -> VMEM (no sorted copy of the tokens exists in HBM), cut into `count`
  pieces of the compute dtype there, and the pieces, one under the other, meet
  each `[K_BLOCK, N_BLOCK]` block of `gate` and of `up` in one product a
  weight; float32 accumulators over the hidden axis; `silu(g) * u` in float32
  is what goes back to HBM, `[rows, F]`. Experts of the UNGATED form (two
  matrices, no `gate`: nemotron_h's) take the same kernel against `up` alone,
  under the name `grouped_up`, and `relu(u)^2` goes back.
- `grouped_down`: the tile's `[TILE, F]` float32 rows are cut into pieces once
  a tile and meet `down` a `[F, columns]` block a step; meanwhile the rows of
  the `[T, H]` result that the tile's tokens own have been copied in, each
  step's columns times the rows' gates are added to them in VMEM, and after
  the last step the rows are copied back: the combine, in place, with no
  sorted copy of the result in HBM either. A tile's tokens are distinct (a
  tile is one expert's), and a tile's copies are waited for before the next
  tile's start, so no row is read while it is written. The gates are gathered
  the same way, a `[1, 128]` row a token that holds its gate for every held
  expert; the tile's expert picks the lane.

H is the width of the rows the experts take and give, any whole number of
lanes: the residual's in five families, a latent's 1,024 in nemotron_h.
A row of a `[T, H]` float32 array is one sublane of each of H / 128 tiles of
(8, 128), and a copy may not slice a tiled axis of a wider array off its
tiling: the tokens and the result cross the kernels' boundary as
`[T / 8, H / 128, 8, 128]`, the SAME bytes in the same order (XLA makes it a
bitcast, no copy: PERF.md section 6, PR 51), where row t is
`[t // 8, :, t % 8, :]`, one strided copy; the rows gathered in VMEM are laid
out the same way, and the kernels read and write a 128-column chunk of them by
its index.

Every buffer whose size the routing decides is either never made (the sorted
tokens, the sorted result) or sized for the worst case the shapes allow, every
token on `min(k, held)` held experts and a tile of padding an expert
(`routed.layout_tiles`): `[tiles x TILE, F]` float32 between the kernels (at
128 held of 512 and top-10 a twelfth of `[held, T, F]`), of which only the
tiles that hold a token are written or read, and a tile table as long (with
ALL of 128 experts held at top-8, sdar_moe's, the bound is `T x 8` rows and a
tile an expert, 147,456 rows of 768 at 16,384 tokens, and the routing fills
nearly all of it). No token is
dropped whatever the routing and nothing is approximated: `count` pieces of
every activation, the weights rounded once, float32 accumulation and gating:
`routed.gated_mlp`'s arithmetic to float32 rounding in another order of
additions (tests/test_grouped_kernel.py, interpreted on the CPU).

The counters are the second kernel's: the rows it added back a held expert,
and the rows of the tiles it walked, counted where the copies are started.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention_kernel import LANES, _pieces, _round_up, pieces_held

# Rows a tile. An expert's last tile is padded to it, so the padding of a
# layer is half a tile an expert on average: 128 rows keep it near a tenth of
# the rows at five cells' 250-704 tokens an expert where 256 made it a fifth to
# a third. (Since PR 64 one cell, sdar_30b_a3b_rerank-bulk, holds a layer whole
# at a MEAN of 1,024 tokens an expert, a deployment's mean; under its seeded
# router the loads are skewed, the busiest expert at five times the mean, as
# no trained router's are. Eight or nine tiles an expert on average, so an
# expert's weights cross into VMEM that many times a layer and the padding is
# a sixteenth; whether a larger tile pays there is that cell's open question,
# to be read a load bucket at a time: PERF.md section 7.) With `count` pieces one under the other a weight block still meets
# 384 rows, above the 240 operations a byte at which a v5e's MXU waits for
# HBM, and a tile's gathered rows (`[TILE, H]` float32, 3.75 MiB at H 7680)
# fit beside the weights' blocks in the 16 MiB a kernel has by default.
TILE = 128
# The hidden columns a step takes at most (those gate/up contracts, those of
# the result `down` makes) and the columns of an expert's width a step of
# gate/up makes: two `[K_BLOCK, N_BLOCK]` bfloat16 blocks of each of `gate` and
# `up` are 4 MiB in flight, two `[F, K_BLOCK]` of `down` as much at F 2,048.
# (PERF.md section 6, PR 51, has the readings; a block twice as large either
# way does not fit the default VMEM beside a tile's gathered rows, which is
# why a float32 compute dtype takes half of K_BLOCK: the same bytes.)
K_BLOCK = 512
N_BLOCK = 1024


def _block(size: int, most: int) -> int:
    """The largest whole number of lanes that divides `size`, at most `most`;
    `size` whole where none does (a test's small widths)."""
    for width in range(min(most, size) // LANES * LANES, 0, -LANES):
        if size % width == 0:
            return width
    return size


def _row(ref, r):
    """Row r of a `[rows, 128]` array, or of a `[rows / 8, chunks, 8, 128]`
    one (a `[rows, chunks * 128]` array in tiles of (8, 128), tile by tile)."""
    return ref.at[pl.ds(r, 1)] if len(ref.shape) == 2 else ref.at[r // 8, :, pl.ds(r % 8, 1), :]


def _rows_in(tokens, rows, source, ring, sem):
    """Start a copy of row `tokens[0, r]` of `source` (HBM) into row r of
    `ring` (VMEM) for the tile's `rows` first rows."""
    def start(r, carry):
        pltpu.make_async_copy(_row(source, tokens[0, r]), _row(ring, r), sem).start()
        return carry

    jax.lax.fori_loop(0, rows, start, None)


def _wait_rows(rows, source, ring, sem):
    """Wait for `rows` row copies that signal `sem`, each of a row of `ring`."""
    def wait(r, carry):
        pltpu.make_async_copy(_row(source, 0), _row(ring, r), sem).wait()
        return carry

    jax.lax.fori_loop(0, rows, wait, None)


def _stacked_product(stacked, w_ref, held: int, tile: int):
    """The pieces, one under the other, times the weight block: one product,
    its `held` parts added up."""
    wide = jnp.dot(stacked, w_ref[...], preferred_element_type=jnp.float32)
    out = wide[:tile]
    for j in range(1, held):
        out = out + wide[j * tile:(j + 1) * tile]
    return out


def _first_kernel(expert, rows, tokens, x_hbm, *refs, held, cd, tile, chunks):
    """`refs`: the blocks of the first pass's weights (`gate` and `up` of the
    gated form, `up` alone of the ungated one), the result's block, the ring
    of gathered rows, its semaphore, and a float32 accumulator a weight."""
    del expert
    count = (len(refs) - 3) // 2
    weights, (h_ref, ring, sem), accs = refs[:count], refs[count:count + 3], refs[count + 3:]
    i, n, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((n == 0) & (k == 0))
    def _gather():
        # Rows past the tile's last token keep what the ring held: every row
        # of the products is its own, and the combine leaves those out.
        _rows_in(tokens, rows[i], x_hbm, ring, sem)
        _wait_rows(rows[i], x_hbm, ring, sem)

    x = jnp.concatenate([ring[:, k * chunks + c].reshape(tile, ring.shape[3]) for c in range(chunks)], axis=1)
    stacked = jnp.concatenate(_pieces(x, cd, held), axis=0)
    products = [_stacked_product(stacked, w_ref, held, tile) for w_ref in weights]

    @pl.when(k == 0)
    def _first():
        for acc, y in zip(accs, products):
            acc[...] = y

    @pl.when(k > 0)
    def _add():
        for acc, y in zip(accs, products):
            acc[...] += y

    @pl.when(k == pl.num_programs(2) - 1)
    def _activated():
        if count == 2:  # silu(g) * u
            g = accs[0][...]
            h_ref[...] = g * jax.nn.sigmoid(g) * accs[1][...]
        else:  # relu(u)^2
            u = jnp.maximum(accs[0][...], 0.0)
            h_ref[...] = u * u


def _down_kernel(expert, rows, tokens, h_ref, down_ref, gates_hbm, zeros_hbm, out_hbm, counts, cut, ring,
                 lanes, sem, gate_sem, *, held, experts, cd, tile, chunks):
    del zeros_hbm  # the result's own buffer, zero where no row is added
    i, n = pl.program_id(0), pl.program_id(1)
    taken = rows[i]

    @pl.when(n == 0)
    def _start():
        @pl.when(i == 0)
        def _zero():
            for j in range(experts + 1):
                counts[j] = 0

        _rows_in(tokens, taken, gates_hbm, lanes, gate_sem)
        _rows_in(tokens, taken, out_hbm, ring, sem)
        counts[expert[i]] += taken
        counts[experts] += tile
        for j, piece in enumerate(_pieces(h_ref[...], cd, held)):
            cut[j * tile:(j + 1) * tile, :] = piece

    y = _stacked_product(cut[...], down_ref, held, tile)

    @pl.when(n == 0)
    def _arrived():
        _wait_rows(taken, gates_hbm, lanes, gate_sem)
        _wait_rows(taken, out_hbm, ring, sem)

    lane = jax.lax.broadcasted_iota(jnp.int32, lanes.shape, 1)
    gate = jnp.sum(jnp.where(lane == expert[i], lanes[...], 0.0), axis=1, keepdims=True)
    y = y * gate
    width = ring.shape[3]
    for c in range(chunks):
        ring[:, n * chunks + c] += y[:, c * width:(c + 1) * width].reshape(tile // 8, 8, width)

    @pl.when(n == pl.num_programs(1) - 1)
    def _back():
        def back(r, carry):
            pltpu.make_async_copy(_row(ring, r), _row(out_hbm, tokens[0, r]), sem).start()
            return carry

        jax.lax.fori_loop(0, taken, back, None)
        _wait_rows(taken, out_hbm, ring, sem)


@functools.partial(jax.jit, static_argnames=("cd", "count", "tile", "interpret"))
def grouped_experts(gate, up, down, x, gate_of, orders, expert, rows, live, *, cd, count: int, tile: int = TILE,
                    interpret: bool = False):
    """`sum over the held experts e that chose token t of gate_of[t, e] *
    expert_e(x[t])`, `[T, H]` float32; the rows that were added back a held
    expert, `[held]` int32; and the rows of the tiles the pass walked.

    gate, up  `[held, H, F]` in the compute dtype `cd`; down `[held, F, H]`;
              `gate` None for experts of the ungated form, `relu(x up)^2 down`
              (the first kernel then meets one weight and is named
              `grouped_up`). H is the width of the rows the experts take and
              give, whole lanes: the residual's, or a latent's
    x         `[T, H]` float32, the tokens
    gate_of   `[T, held]` float32, a token's gate for each held expert
    orders    `[tiles, tile]` int32, the token of every row of every tile a
              pass may walk: an expert's tokens in order, its run in whole
              tiles (what stands after a tile's `rows` is not read)
    expert    `[tiles]` int32, the held expert of each tile
    rows      `[tiles]` int32, the rows of each tile that hold a token: the
              tiles that hold one first (`routed.lay_out` makes the three)
    live      how many tiles hold a token
    """
    held, hidden, width = up.shape
    first = [up] if gate is None else [gate, up]
    tokens, tiles = x.shape[0], orders.shape[0]
    pieces = pieces_held(cd, count)
    # One tile at least, of no rows where no token came here: its step zeroes
    # the counters.
    walked = jnp.maximum(live, 1)
    order_tiles = orders.reshape(tiles, 1, tile)
    # A row as chunks of whole lanes (one chunk, a test's narrow row), eight
    # rows a tile: the bytes of `[T, H]` as they lie, where T is whole eights.
    lanes = LANES if hidden % LANES == 0 else hidden
    k_block, n_block = _block(hidden, K_BLOCK * 2 // jnp.dtype(cd).itemsize), _block(width, N_BLOCK)
    eights = -(-tokens // 8)
    x = jnp.pad(x, ((0, eights * 8 - tokens), (0, 0))).reshape(eights, 8, hidden // lanes, lanes).transpose(0, 2, 1, 3)
    ring = pltpu.VMEM((tile // 8, hidden // lanes, 8, lanes), jnp.float32)
    params = functools.partial(pltpu.CompilerParams, disable_bounds_checks=True)

    def a_tiles_tokens():
        return pl.BlockSpec((None, 1, tile), lambda i, *rest: (i, 0, 0), memory_space=pltpu.SMEM)

    with jax.named_scope("grouped"):
        h = pl.pallas_call(
            functools.partial(_first_kernel, held=pieces, cd=cd, tile=tile, chunks=k_block // lanes),
            out_shape=jax.ShapeDtypeStruct((tiles * tile, width), jnp.float32),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(walked, width // n_block, hidden // k_block),
                in_specs=[a_tiles_tokens(), pl.BlockSpec(memory_space=pl.ANY)] + [
                    pl.BlockSpec((None, k_block, n_block), lambda i, n, k, e, r: (e[i], k, n)) for _ in first],
                out_specs=pl.BlockSpec((tile, n_block), lambda i, n, k, e, r: (i, n)),
                scratch_shapes=[ring, pltpu.SemaphoreType.DMA(())] + [
                    pltpu.VMEM((tile, n_block), jnp.float32) for _ in first]),
            compiler_params=params(dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
            interpret=interpret,
            name="grouped_up" if gate is None else "grouped_gate_up",
        )(expert, rows, order_tiles, x, *first)
    # A token's gates, a row of whole lanes: what a row copy can bring.
    gate_lanes = jnp.pad(gate_of, ((0, 0), (0, _round_up(held, LANES) - held)))
    with jax.named_scope("combine"):
        out, counts = pl.pallas_call(
            functools.partial(_down_kernel, held=pieces, experts=held, cd=cd, tile=tile, chunks=k_block // lanes),
            out_shape=(jax.ShapeDtypeStruct(x.shape, jnp.float32), jax.ShapeDtypeStruct((held + 1,), jnp.int32)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(walked, hidden // k_block),
                in_specs=[
                    a_tiles_tokens(),
                    pl.BlockSpec((tile, width), lambda i, n, e, r: (i, 0)),
                    pl.BlockSpec((None, width, k_block), lambda i, n, e, r: (e[i], 0, n)),
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=(pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pltpu.SMEM)),
                scratch_shapes=[
                    pltpu.VMEM((pieces * tile, width), cd),
                    ring,
                    pltpu.VMEM((tile, gate_lanes.shape[1]), jnp.float32),
                    pltpu.SemaphoreType.DMA(()),
                    pltpu.SemaphoreType.DMA(()),
                ]),
            # operand 6 (after the two tables): the zeros the result starts as
            input_output_aliases={6: 0},
            compiler_params=params(dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="grouped_down",
        )(expert, rows, order_tiles, h, down, gate_lanes, jnp.zeros(x.shape, jnp.float32))
    return out.transpose(0, 2, 1, 3).reshape(eights * 8, hidden)[:tokens], counts[:held], counts[held]
