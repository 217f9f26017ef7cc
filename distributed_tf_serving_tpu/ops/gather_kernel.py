"""The embedding gather as a Pallas TPU kernel that keeps row copies in flight.

XLA's gather of whole 128-lane rows is bound by rows, not bytes: one looked-up
512-byte row costs 9.6-12.5 ns on a v5e (PERF.md section 6, PR 25 and PR 39).
The scalar core can start a row copy every four bundles (2.6 ns) and the DMA
engines keep up as long as their queue never runs dry, so this kernel is a DMA
pipeline and little else (3.8 ns a row inside the served step):

- the table stays in HBM; the row numbers reach scalar memory a block a grid
  step;
- grid step i starts one copy for every row of block i, HBM row -> its place
  in half i % 2 of a two-block VMEM ring, while block i - 1's rows, whose
  copies were started a step ago into the other half, are waited for, cast
  and stored (so the grid has one step more than there are blocks): a whole
  block of copies (1,400-2,000 rows) is in flight at any time and the queue
  is never drained at a block's end (the grid axis is sequential; the ring
  and the semaphores are scratch that lives across steps);
- rows are waited for a UNIT at a time (the rows of one candidate, or 16 of a
  flat list): the unit's copies signal one DMA semaphore, and one wait for
  the unit's bytes takes them all;
- consuming a unit is the cast to the output dtype (round to nearest even, as
  XLA's convert) and one dense store into the output block, which Pallas's
  pipeline writes back to HBM behind the gather.

What a row costs is the scalar work of starting its copy, so the starts are
unrolled (every offset but the row's own a constant), the ring and semaphores
are flat (one base a unit), and Mosaic's bounds checks are off (they were 11
of a start's 15 bundles; the rows are clipped into the table instead).

The output is `jnp.take(table, rows, axis=0).astype(dtype)` bit for bit
(tests/test_gather_kernel.py, in interpret mode on the CPU).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# Rows a block (a grid step) at most: what is in flight while a block is
# consumed. Chosen on the chip (PERF.md section 6, PR 39).
BLOCK_ROWS = 2048
# Rows a unit of a flat list of rows: one bfloat16 tile, so the flat output
# is the unit-shaped one's bytes in the same order.
FLAT_UNIT = 16
# The longest last axis taken as a unit (its starts are unrolled); a longer
# one is read as a flat list.
MAX_UNIT = 256
# Starts unrolled a loop step, for a unit of at least two such chunks.
START_CHUNK = 32


def block_units(units: int, unit_rows: int) -> int:
    """Units a block: the largest power of two whose rows fit BLOCK_ROWS, at
    most what there is."""
    cap = max(1, BLOCK_ROWS // unit_rows)
    return min(1 << (cap.bit_length() - 1), units)


def _unit_rows(shape: tuple[int, ...]) -> int:
    return shape[-1] if len(shape) >= 2 and FLAT_UNIT <= shape[-1] <= MAX_UNIT else FLAT_UNIT


def rows_in_flight(shape: tuple[int, ...]) -> int:
    """Row copies in flight while a block of rows `shape` is consumed: a
    block's."""
    unit_rows = _unit_rows(shape)
    return block_units(-(-math.prod(shape) // unit_rows), unit_rows) * unit_rows


def _kernel(idx_ref, table_ref, out_ref, ring, sems, *, units, unit_rows, stride):
    # Grid step i starts block i's copies into half i % 2 of the ring and
    # consumes block i - 1 from the other half: one step more than blocks.
    step = pl.program_id(0)
    last = pl.num_programs(0) - 1
    half = step % 2

    def unit(u, carry):
        # The ring is flat, [2 * units * stride, 128] with a unit every
        # `stride` rows (whole tiles), and so are the semaphores: one base
        # a unit and a constant a row keep a start to three scalar bundles.
        @pl.when(step < last)
        def _start():
            slot = half * units + u
            first, base = u * unit_rows, slot * stride

            def start(k):
                pltpu.make_async_copy(
                    table_ref.at[pl.ds(idx_ref[0, first + k], 1)],
                    ring.at[pl.ds(base + k, 1)],
                    sems.at[slot],
                ).start()

            # Unrolled, so that every offset but the row's own is a constant,
            # and by the lowering, not here: traced inline a start cost 4 ms
            # of Python on the chip's host, an entry and a rung (17 s of
            # warm-up at 214 rows a unit). A long unit is unrolled a chunk a
            # loop step, which keeps its lowering short too.
            def starts(first_k, count):
                def one(k, carry):
                    start(first_k + k)
                    return carry

                jax.lax.fori_loop(0, count, one, None, unroll=True)

            chunks = unit_rows // START_CHUNK if unit_rows >= 2 * START_CHUNK else 0

            def chunk(c, carry):
                starts(c * START_CHUNK, START_CHUNK)
                return carry

            jax.lax.fori_loop(0, chunks, chunk, None)
            starts(chunks * START_CHUNK, unit_rows - chunks * START_CHUNK)

        @pl.when(step > 0)
        def _consume():
            slot = (1 - half) * units + u
            rows = ring.at[pl.ds(pl.multiple_of(slot * stride, stride), unit_rows)]
            # One wait for the unit's bytes: its copies share the semaphore.
            pltpu.make_async_copy(table_ref.at[pl.ds(0, unit_rows)], rows, sems.at[slot]).wait()
            out_ref[u] = rows[...].astype(out_ref.dtype)

        return carry

    jax.lax.fori_loop(0, units, unit, None)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def gather_rows(table: jax.Array, rows: jax.Array, dtype, interpret: bool = False) -> jax.Array:
    """table[rows] in `dtype`: [..., 128] for rows [...] int32 in [0, V).

    table  [V, 128] float32, left in HBM (bfloat16 too when interpreted; on
           the chip Mosaic refuses a one-row copy of a packed sublane)
    rows   [..., S]: the last axis is a unit (a candidate's lookups) when it
           holds at least FLAT_UNIT rows, else the rows are taken as a flat
           list in units of FLAT_UNIT
    """
    shape = rows.shape
    unit_rows = _unit_rows(shape)
    flat = jnp.clip(rows.reshape(-1).astype(jnp.int32), 0, table.shape[0] - 1)
    units = -(-flat.size // unit_rows)
    per_block = block_units(units, unit_rows)
    blocks = -(-units // per_block)
    padded = blocks * per_block * unit_rows
    if padded != flat.size:
        flat = jnp.pad(flat, (0, padded - flat.size))  # row 0: any valid row
    idx = flat.reshape(blocks, 1, per_block * unit_rows)
    stride = -(-unit_rows // 16) * 16  # whole tiles of either dtype
    out = pl.pallas_call(
        functools.partial(_kernel, units=per_block, unit_rows=unit_rows, stride=stride),
        out_shape=jax.ShapeDtypeStruct((blocks * per_block, unit_rows, LANES), dtype),
        grid=(blocks + 1,),
        in_specs=[
            pl.BlockSpec(
                (None, 1, per_block * unit_rows),
                lambda i: (jnp.minimum(i, blocks - 1), 0, 0),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (per_block, unit_rows, LANES), lambda i: (jnp.maximum(i - 1, 0), 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((2 * per_block * stride, LANES), table.dtype),
            pltpu.SemaphoreType.DMA((2 * per_block,)),
        ],
        # Mosaic's two bounds checks a copy (source and destination, six
        # dependent scalar bundles each) are three quarters of what a row
        # costs with them on; the rows are clipped into the table above.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True
        ),
        interpret=interpret,
        name="embed_gather",
    )(idx, table)
    if unit_rows == FLAT_UNIT:
        # One bfloat16 tile a unit: the same bytes in the same order.
        return out.reshape(-1, LANES)[: rows.size].reshape(*shape, LANES)
    return out[:units].reshape(*shape, LANES)
