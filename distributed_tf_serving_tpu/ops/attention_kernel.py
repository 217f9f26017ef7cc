"""Causal attention at all positions of a row as one Pallas TPU kernel whose
score tile never leaves VMEM.

XLA's path writes a block of queries' float32 score tile to HBM, reads and
writes it for the softmax, and writes and reads the probabilities' pieces for
`p v`: three crossings of the largest array of the step (PERF.md section 6,
PR 44 and PR 48). Here a grid step is one (row, query heads that read the
same keys and values, block of queries); the heads' blocks stand one under
the other as the rows of one tile (a window of 128 alone would stream 128
rows through a product: what the MXU's weights cost to load again):

- a key-value head's float32 keys and values reach VMEM once (their block's
  index changes with the head alone, so the pipeline copies them once a head)
  and are cut into pieces of the compute dtype THERE, once a head, into
  scratch that lives across grid steps: the keys' pieces side by side along
  the contracted axis, a column chunk a pair (i, j), i + j < pieces, so that
  the pairs of a score product add up in ONE product's own accumulation; the
  values' pieces side by side along the axis the result keeps;
- the block's queries are cut the same way every step (`[queries, d]`: small);
- the keys are walked in blocks, and only those the block's causal reach (and
  its window's) holds: the `[queries, keys]` float32 tile of a block is one
  product, scaled and masked, and enters a running maximum and sum (the same
  softmax in another order of additions); its exponentials are cut into
  pieces and multiplied with the values' pieces, a product a piece of `p`,
  into a float32 `[queries, d_v]` accumulator;
- only that accumulator over the sum goes back to HBM.

Where that does not fit the 16 MiB of VMEM a kernel has (heads 256 wide at
three pieces: a head's keys in six pairs are 6.3 MB and its float32 keys and
values in two buffers 8.4), the same kernel holds a head COMPACT, chosen from
the shapes alone (`held_compact`, `vmem_bytes`): the keys' pieces stand ONCE
each, and the tile is `pieces` products whose float32 results add, the
queries' piece i (repeated) against the keys' pieces 0 .. pieces - 1 - i, the
form `p v` has, mirrored: the same pairs in the same passes of the MXU
(`columns` has both layouts); and the float32 keys and values stay in HBM,
the cut copying PIECE_ROWS rows at a time into two small buffers, one on its
way while the other is cut. No `vmem_limit` is raised either way (ROWS has
why).

Operands enter the MXU in the compute dtype, everything else is float32, and
the pieces and pairs are `models/sequence.py::product`'s: the result is the
XLA path's to float32 rounding (tests/test_attention_kernel.py, interpreted
on the CPU; tests/test_tpu_compile.py compiles it for a v5e).

A score may be a SUM of products over parts (`pangu_moe`: the heads' own
`nope` part and a rotary part whose keys all heads share): the parts' chunks
stand side by side in the same contracted axis. Each part of the keys, and
the values, may have fewer heads than the queries: query head h reads head
`h // (heads / theirs)` of each.

The mask is "up to my own position" (`seen`: `k_pos <= q_pos`), narrowed by a
`window` (phi4flash's, exaone_moe's and mimo_v2's window layers), widened by a
`sink` (below), or widened by a `span` to the END of the query's block of
`span` positions (`sdar_moe`'s block mask, `u // span <= t // span`: the one
mask that looks ahead). The key blocks walked are the causal mask's either
way: where the tile, the row and the queries' first position are whole spans a
block of queries reads nothing past its own last position (`check_span`).

A softmax may hold one more term a head, a learned logit that no key carries
(`sink [heads]`, `mimo_v2`'s window layers): it is where a row's running state
STARTS (maximum the logit, sum 1, accumulator 0, where without one it starts
at MASKED, 0, 0), so it joins the maximum and the denominator and gives no
value. The heads a grid step stacks each take their own logit, read from
SMEM. With a sink the kernel also writes the sink's share of every query's
softmax, from its own running maximum and sum (`attn.sink_mass_ppm`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# Queries, and keys, a tile at most. A window's tiles are no larger than the
# window (rounded up to whole lanes): a block of 128 queries of a window of
# 128 reads two key blocks, one of 512 would read five.
BLOCK = 512
# Rows a tile at most: the query heads of a group a grid step takes together
# are as many as fit (4 of `exaone_moe`'s 8 at a window of 128). At 512 rows
# the kernel fits the 16 MiB of VMEM a kernel has by default and asks for no
# more: what a kernel claims is taken from what XLA prefetches the step's
# weights into, and a claim of 64 MiB slowed `olmo_hybrid`'s MLPs by 22 ms a
# step where the kernel saved 6 (PERF.md section 6, PR 48).
ROWS = 512
# Rows of keys or values cut into pieces at a time (bounds the kernel's code).
PIECE_ROWS = 256
# What a masked score is set to: finite, so that a block of keys none of
# which a query sees leaves its running maximum a number (exp(-inf + inf)
# is not one); the first key it does see wipes what that block added.
MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)


def _round_up(x: int, to: int) -> int:
    return -(-x // to) * to


def tile(keys: int, window: int | None) -> int:
    """The side of a score tile: BLOCK, or the window's or the row's length
    in whole lanes where that is less."""
    return min(BLOCK, _round_up(min(window or keys, keys), LANES))


def key_blocks(start: int, block: int, keys: int, window: int | None) -> tuple[int, int]:
    """(first, one past the last) key block that the `block` queries from
    position `start` on read: up to their own positions, and from their
    window's first key."""
    last = min(-(-(start + block) // block), -(-keys // block))
    first = 0 if window is None else max(0, start - window + 1) // block
    return first, last


def check_span(queries: int, keys: int, span: int, block: int, window: int | None = None) -> None:
    """Refuse a block mask of `span` positions (position t sees up to the end
    of its span) that the causal mask's tiles would cut, for the last
    `queries` positions of `keys` in blocks of `block` queries: a block of
    queries reads the keys up to its own last position and no further, which
    is all such a mask keeps only where the row, the queries' first position
    and a block are whole spans (all positions of a row; a lone last query of
    a row of whole spans is the last of its span and sees every key), and
    where no window narrows the reach (no family asks for both: refused
    rather than guessed at). Raised where a path is chosen
    (`sequence.attention_choice`, for XLA's blocks and the kernel alike), by
    the kernel for a caller that asks it directly, and by a family at build:
    nothing falls back to the causal mask."""
    if (window is not None or span <= 0 or keys % span
            or (queries > 1 and ((keys - queries) % span or block % span))):
        raise ValueError(
            f"a span of {span} over the last {queries} of {keys} positions in blocks of {block}, window {window}: "
            "the row, the queries' first position and a block of queries have to be whole spans, and no window "
            "narrows a span's reach")


def tile_pairs(queries: int, keys: int, window: int | None = None) -> int:
    """(query, key) pairs the kernel's tiles compute over a row for the last
    `queries` positions of `keys`."""
    block, offset = tile(keys, window), keys - queries
    total = 0
    for start in range(0, queries, block):
        first, last = key_blocks(offset + start, block, keys, window)
        total += block * block * (last - first)
    return total


def pieces_held(cd, count: int) -> int:
    """Pieces a float32 activation enters a product as: `count`, or one where
    the compute dtype holds it whole (`sequence.pieces`' rule)."""
    return 1 if jnp.finfo(cd).bits >= 32 else count


def _pieces(x: jax.Array, cd, held: int) -> list[jax.Array]:
    """`sequence.pieces` inside the kernel: x's rounding to the compute
    dtype, then the rounding of what that left (Mosaic folds no cast away)."""
    out = []
    for i in range(held):
        out.append(x.astype(cd))
        if i + 1 < held:
            x = x - out[-1].astype(jnp.float32)
    return out


def heads_a_step(shared: int, block: int) -> int:
    """Query heads a grid step takes: the largest divisor of `shared` (the
    heads that read the same keys and values) whose blocks fit ROWS."""
    return max(h for h in range(1, shared + 1) if shared % h == 0 and h * block <= max(ROWS, block))


def columns(widths: tuple[int, ...], held: int, compact: bool):
    """Where the pieces stand along the contracted axis of the queries' and
    the keys' scratch, and the products that make a score tile of them:
    `(the queries' columns, the keys', the queries' places, the keys', products)`,
    a place `(part, piece, first column)` and a product `(the queries' first
    column, the keys', columns)`, whose float32 results add up to the tile.

    In PAIRS a column chunk is a pair (i, j), i + j < held, a part after the
    other: the queries' piece i there and the keys' piece j, so ONE product
    adds the pairs up in its own accumulation, and the keys' piece j stands
    `held - j` times. COMPACT, the keys' pieces stand once each, piece after
    piece (the parts side by side inside one): the queries' piece i, repeated
    `held - i` times, meets pieces 0 .. held - 1 - i in one product, `held`
    products a tile over the same pairs. A product's columns are whole lanes:
    past its pairs the queries' are zeros (and the keys' another piece's, or
    zeros past the last)."""
    span = sum(widths)
    if not compact:
        pairs = [(i, j) for i in range(held) for j in range(held) if i + j < held]
        q_places, k_places, column = [], [], 0
        for part, width in enumerate(widths):
            for c, (i, j) in enumerate(pairs):
                q_places.append((part, i, column + c * width))
                k_places.append((part, j, column + c * width))
            column += len(pairs) * width
        total = _round_up(column, LANES)
        return total, total, q_places, k_places, [(0, 0, total)]
    firsts = [sum(widths[:part]) for part in range(len(widths))]
    k_places = [(part, j, j * span + first) for j in range(held) for part, first in enumerate(firsts)]
    q_places, products, column = [], [], 0
    for i in range(held):
        q_places += [(part, i, column + r * span + first) for r in range(held - i) for part, first in enumerate(firsts)]
        products.append((column, 0, _round_up((held - i) * span, LANES)))
        column += products[-1][2]
    return column, _round_up(held * span, LANES), q_places, k_places, products


def _gaps(places, widths, total: int) -> list[tuple[int, int]]:
    """The column ranges of `total` that no place fills: zeros stand there."""
    out, at = [], 0
    for first, stop in sorted((first, first + widths[part]) for part, _, first in places):
        if first > at:
            out.append((at, first))
        at = max(at, stop)
    return out + ([(at, total)] if at < total else [])


def vmem_bytes(keys: int, window: int | None, widths: tuple[int, ...], dv: int, shared: int, cd, count: int,
               compact: bool = False) -> int:
    """The VMEM bytes a call of `attention` asks for, from its shapes alone:
    the scratch (the queries' and the keys' pieces as `columns` lays them out,
    the values' side by side a piece, the running maximum and sum, a lane row
    each, and the float32 accumulator), the float32 blocks in two buffers each
    (the step's queries a part and the result; a head's keys a part and its
    values, or, `compact`, a chunk of PIECE_ROWS of them in whole lanes) and
    one float32 score tile; `compact`, also what else of a key block's work
    Mosaic keeps on its stack: the tile's exponentials, a piece of them and
    that piece's products with the values' pieces side by side.
    `keys` positions a row, the parts' `widths`, the values' `dv`, `shared`
    query heads a key-value head (the fewest over the parts and the values),
    `count` pieces of `cd`. Mosaic's own count is not this one. In pairs it
    stands 1.9 MiB over this one at heads 128 wide and 1.0 to 2.7 under at 192
    and 256 (its blocks of a head count less than two buffers there, its stack
    more than one tile), and the limit's verdicts are the chip's all the same;
    compact, nothing is over-counted to cover the stack, so it is counted, and
    Mosaic stands from 1.2 MiB over this one (a window's tiles of 128 with a
    sink at four pieces: 12.2 for 11.0) to 1.3 under at eleven shapes, and at
    the one cell's that is held so 15.45 where this counts 15.5. This one is
    read against VMEM_LIMIT and agrees with the chip's verdict on every shape
    a cell or a float32 stand-in of one has run, in both forms (PERF.md
    section 6, PR 58 and PR 61)."""
    held, block = pieces_held(cd, count), tile(keys, window)
    tall, k_len = heads_a_step(shared, block) * block, _round_up(keys, block)
    q_width, k_width = columns(widths, held, compact)[:2]
    item = jnp.dtype(cd).itemsize
    scratch = (tall * q_width + k_len * k_width + k_len * held * dv) * item + tall * (2 * LANES + dv) * 4
    a_head, stack = k_len * (sum(widths) + dv), tall * block * 4
    if compact:
        a_head = math.gcd(block, PIECE_ROWS) * sum(_round_up(d, LANES) for d in (*widths, dv))
        stack += tall * block * (4 + item) + tall * held * dv * 4
    return scratch + 2 * 4 * (tall * (sum(widths) + dv) + a_head) + stack


# What a kernel has by default on a v5e, which this one never raises (ROWS has
# why), and what `held_compact` and `fits` read `vmem_bytes` against. The
# widest head a cell runs in pairs, `mimo_v2`'s 192-wide keys over 128-wide
# values at three pieces, counts 15.1 MiB and is taken by the chip; 256 wide
# both ways counts 22.5 MiB in pairs at three pieces and 16.5 at one float32
# piece (the readings' stand-in), and the chip refused both at run time
# (PERF.md section 6, PR 50 (a) and PR 58); compact, three pieces count 15.5
# MiB (Mosaic: 15.45) and the chip takes them (PR 61).
VMEM_LIMIT = 16 << 20


def held_compact(keys: int, window: int | None, widths: tuple[int, ...], dv: int, shared: int, cd, count: int) -> bool:
    """Whether a call holds a key-value head COMPACT (`columns`, `vmem_bytes`):
    where the pairs' form is past VMEM_LIMIT. One kernel, its operands' layout
    chosen from the shapes: every shape that fits in pairs keeps the program
    it had."""
    return vmem_bytes(keys, window, widths, dv, shared, cd, count) > VMEM_LIMIT


def fits(keys: int, window: int | None, widths: tuple[int, ...], dv: int, shared: int, cd, count: int) -> bool:
    """Whether a call fits VMEM_LIMIT as `held_compact` would have it held:
    what `sequence.attention_choice` asks before it says `pallas`."""
    compact = held_compact(keys, window, widths, dv, shared, cd, count)
    return vmem_bytes(keys, window, widths, dv, shared, cd, count, compact) <= VMEM_LIMIT


def _kernel(*refs, widths, reps, rep_v, dv, held, cd, scale, window, offset, block, keys, stacked, compact, sunk=False,
            span=None):
    parts = len(widths)
    tall = stacked * block
    q_refs, k_refs, v_ref = refs[:parts], refs[parts:2 * parts], refs[2 * parts]
    # With a sink: its logits [heads] in SMEM after the values, and the
    # share's block after the result's.
    sink_ref, o_ref, share_ref = (refs[2 * parts + 1:2 * parts + 4] if sunk else (None, refs[2 * parts + 1], None))
    if compact:  # a chunk's two buffers an operand (the keys' parts, the values) and their copies' semaphores
        *refs, sems = refs
        refs, bufs = refs[:-(parts + 1)], refs[-(parts + 1):]
    qcat, kcat, vcat, m_ref, l_ref, acc_ref = refs[-6:]
    row, head, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    _, _, q_places, k_places, products = columns(widths, held, compact)

    def cut(operand, ref, rep, rows, store):
        # The whole key range of a head, PIECE_ROWS at a time: from the head's
        # block in VMEM, or, compact, from the array in HBM through two small
        # buffers, a chunk on its way while the one before it is cut.
        chunks = keys // rows

        def copy(c, slot):
            return pltpu.make_async_copy(
                ref.at[row, head // rep, pl.ds(c * rows, rows), :], bufs[operand].at[slot],
                sems.at[operand, slot])

        def chunk(c, carry):
            at = pl.ds(pl.multiple_of(c * rows, rows), rows)
            if compact:
                @pl.when(c + 1 < chunks)
                def _next():
                    copy(c + 1, (c + 1) % 2).start()

                copy(c, c % 2).wait()
                x = bufs[operand][c % 2][:, :(*widths, dv)[operand]]
            else:
                x = ref[at, :]
            store(at, _pieces(x.astype(jnp.float32), cd, held))
            return carry

        if compact:
            copy(0, 0).start()
        jax.lax.fori_loop(0, chunks, chunk, None)

    rows = math.gcd(block, PIECE_ROWS)
    for part, (width, rep) in enumerate(zip(widths, reps)):
        @pl.when((qi == 0) & (head % rep == 0))
        def _keys(part=part, width=width, rep=rep):
            def store(at, ks):
                for _, j, first in (place for place in k_places if place[0] == part):
                    kcat[at, first:first + width] = ks[j]
                if part == 0:
                    for first, stop in _gaps(k_places, widths, kcat.shape[1]):
                        kcat[at, first:stop] = jnp.zeros((rows, stop - first), cd)

            cut(part, k_refs[part], rep, rows, store)

        qs = _pieces(q_refs[part][...].reshape(tall, width).astype(jnp.float32), cd, held)
        for _, i, first in (place for place in q_places if place[0] == part):
            qcat[:, first:first + width] = qs[i]
    for first, stop in _gaps(q_places, widths, qcat.shape[1]):
        qcat[:, first:stop] = jnp.zeros((tall, stop - first), cd)

    @pl.when((qi == 0) & (head % rep_v == 0))
    def _values():
        def store(at, vs):
            for j, piece in enumerate(vs):
                vcat[at, j * dv:(j + 1) * dv] = piece

        cut(parts, v_ref, rep_v, rows, store)

    def sink_logits():
        # Row r is of the step's head r // block: each head's own logit.
        of_head = jax.lax.broadcasted_iota(jnp.int32, (stacked, block, 1), 0).reshape(tall, 1)
        logit = jnp.full((tall, 1), sink_ref[head * stacked], jnp.float32)
        for j in range(1, stacked):
            logit = jnp.where(of_head == j, sink_ref[head * stacked + j], logit)
        return logit

    if sunk:
        m_ref[...] = sink_logits()
        l_ref[...] = jnp.ones(l_ref.shape, jnp.float32)
    else:
        m_ref[...] = jnp.full(m_ref.shape, MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    q_first = offset + qi * block
    # A head's block under the last one's: row r is query r % block.
    q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32, (stacked, block, 1), 1).reshape(tall, 1)
    # Under a block mask, one past the last key a query sees: the end of its
    # span (positions are not negative, so the division is the floor).
    q_end = None if span is None else (jax.lax.div(q_pos, jnp.int32(span)) + 1) * span

    def key_block(kb, carry):
        start = pl.multiple_of(kb * block, block)
        at = pl.ds(start, block)
        s = None
        for q_first, k_first, depth in products:
            pairs = jax.lax.dot_general(
                qcat[:, q_first:q_first + depth], kcat[at, k_first:k_first + depth], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = pairs if s is None else s + pairs
        s = s * scale
        k_pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
        seen = k_pos <= q_pos if span is None else k_pos < q_end
        if window is not None:
            seen &= q_pos - k_pos < window
        s = jnp.where(seen, s, MASKED)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        added = None
        for i, piece in enumerate(_pieces(p, cd, held)):
            # Piece i of p against the values' pieces 0 .. held - 1 - i, side
            # by side: one product, its parts added up.
            wide = jnp.dot(piece, vcat[at, :(held - i) * dv], preferred_element_type=jnp.float32)
            for j in range(held - i):
                part = wide[:, j * dv:(j + 1) * dv]
                added = part if added is None else added + part
        acc_ref[...] = alpha * acc_ref[...] + added
        m_ref[...] = m_new
        return carry

    first = 0 if window is None else jnp.maximum(0, q_first - window + 1) // block
    last = jnp.minimum((q_first + block - 1) // block + 1, keys // block)
    jax.lax.fori_loop(first, last, key_block, None)
    o_ref[...] = (acc_ref[...] / l_ref[...]).reshape(stacked, block, dv)
    if sunk:
        share_ref[...] = (jnp.exp(sink_logits() - m_ref[...]) / l_ref[...]).reshape(stacked, block, 1)


@functools.partial(jax.jit, static_argnames=("scale", "window", "cd", "count", "interpret", "compact", "span"))
def attention(qs, ks, v, *, scale: float, window: int | None, cd, count: int, interpret: bool = False,
              sink: jax.Array | None = None, compact: bool | None = None, span: int | None = None):
    """softmax(sum over the parts of `q k'` * scale | causal, window, span) v.

    qs    a tuple of `[n, H, Lq, d_p]` float32, a part each: the queries stand
          at the LAST Lq positions of the keys' range
    ks    a tuple of `[n, H_p, Lk, d_p]`, H_p dividing H
    v     `[n, H_v, Lk, d_v]`, H_v dividing H
    sink  `[H]` float32, a logit a head that joins its softmax's maximum and
          denominator and gives no value; None for a softmax over the keys alone
    returns `[n, H, Lq, d_v]` float32; with a sink, that and the sink's share
    of every query's softmax, `[n, H, Lq, 1]`

    Activations enter the products as `count` pieces of `cd`, in the pairs
    `i + j < count`; position t sees `t - window + 1 .. t` (all up to t
    without a window). With `span`, position t sees every key up to the END
    of its block of `span` positions, `u // span <= t // span` (`sdar_moe`'s
    block mask, the one mask that looks ahead): the tiles walked are the
    causal mask's, which hold all of it where the tile, the row and the
    queries' first position are whole spans (`check_span` raises otherwise).
    `compact` is `held_compact`'s answer at these shapes unless given (the tests and the chip's readings run both forms)."""
    n, heads, queries, _ = qs[0].shape
    keys, dv = v.shape[2], v.shape[3]
    widths = tuple(q.shape[-1] for q in qs)
    held = pieces_held(cd, count)
    pairs = held * (held + 1) // 2  # (i, j), i + j < held
    block = tile(keys, window)
    if span is not None:
        check_span(queries, keys, span, block, window)
    shared = min(heads // x.shape[1] for x in (*ks, v))
    stacked = heads_a_step(shared, block)
    if compact is None:
        compact = held_compact(keys, window, widths, dv, shared, cd, count)
    q_len, k_len = _round_up(queries, block), _round_up(keys, block)

    def padded(x, length):  # along the positions, with zeros
        return x if x.shape[2] == length else jnp.pad(x, ((0, 0), (0, 0), (0, length - x.shape[2]), (0, 0)))

    # Queries padded at their END keep their positions; so do the keys. The
    # queries' heads in the groups a step takes together.
    qs = tuple(padded(q, q_len).reshape(n, heads // stacked, stacked, q_len, q.shape[-1]) for q in qs)
    ks = tuple(padded(k, k_len) for k in ks)
    v = padded(v, k_len)
    if compact:  # a chunk is copied in whole lanes
        *ks, v = (jnp.pad(x, ((0, 0),) * 3 + ((0, -x.shape[-1] % LANES),)) for x in (*ks, v))
    q_width, k_width = columns(widths, held, compact)[:2]
    computed = n * heads * tile_pairs(queries, keys, window)

    def stacked_heads(d):
        return pl.BlockSpec((None, None, stacked, block, d), lambda b, g, i: (b, g, 0, i, 0))

    def a_head(x):  # of the keys or values: the one that group g's heads read; compact, the array where it lies
        if compact:
            return pl.BlockSpec(memory_space=pl.ANY)
        rep = heads // x.shape[1] // stacked
        return pl.BlockSpec((None, None, k_len, x.shape[-1]), lambda b, g, i: (b, g // rep, 0, 0))

    def result(d):
        return jax.ShapeDtypeStruct((n, heads // stacked, stacked, q_len, d), jnp.float32)

    body = functools.partial(
        _kernel, widths=widths, reps=tuple(heads // k.shape[1] // stacked for k in ks),
        rep_v=heads // v.shape[1] // stacked, dv=dv, held=held, cd=cd, scale=scale, window=window,
        offset=keys - queries, block=block, keys=k_len, stacked=stacked, compact=compact, span=span)
    in_specs = [stacked_heads(d) for d in widths] + [a_head(k) for k in ks] + [a_head(v)]
    out_shape, out_specs, operands = result(dv), stacked_heads(dv), (*qs, *ks, v)
    if sink is not None:
        body = functools.partial(body, sunk=True)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        out_shape, out_specs = (out_shape, result(1)), (out_specs, stacked_heads(1))
        operands += (sink.astype(jnp.float32).reshape(heads),)
    scratch = [
        pltpu.VMEM((stacked * block, q_width), cd),
        pltpu.VMEM((k_len, k_width), cd),
        pltpu.VMEM((k_len, held * dv), cd),
        pltpu.VMEM((stacked * block, 1), jnp.float32),
        pltpu.VMEM((stacked * block, 1), jnp.float32),
        pltpu.VMEM((stacked * block, dv), jnp.float32),
    ]
    if compact:
        rows = math.gcd(block, PIECE_ROWS)
        scratch += [pltpu.VMEM((2, rows, x.shape[-1]), jnp.float32) for x in (*ks, v)]
        scratch.append(pltpu.SemaphoreType.DMA((len(widths) + 1, 2)))
    out = pl.pallas_call(
        body,
        out_shape=out_shape,
        grid=(n, heads // stacked, q_len // block),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
        # The pieces of a head's keys and values are made at its first step
        # and read by the steps after it: every axis in order.
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * computed * pairs * (sum(widths) + dv),
            transcendentals=computed,
            bytes_accessed=4 * (sum(q.size for q in qs) + sum(k.size for k in ks) + v.size + n * heads * q_len * dv)),
        interpret=interpret,
        name="attention",
    )(*operands)
    if sink is None:
        return out.reshape(n, heads, q_len, dv)[:, :, :queries]
    return tuple(x.reshape(n, heads, q_len, -1)[:, :, :queries] for x in out)
