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

Operands enter the MXU in the compute dtype, everything else is float32, and
the pieces and pairs are `models/sequence.py::product`'s: the result is the
XLA path's to float32 rounding (tests/test_attention_kernel.py, interpreted
on the CPU; tests/test_tpu_compile.py compiles it for a v5e).

A score may be a SUM of products over parts (`pangu_moe`: the heads' own
`nope` part and a rotary part whose keys all heads share): the parts' chunks
stand side by side in the same contracted axis. Each part of the keys, and
the values, may have fewer heads than the queries: query head h reads head
`h // (heads / theirs)` of each.

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


def vmem_bytes(keys: int, window: int | None, widths: tuple[int, ...], dv: int, shared: int, cd, count: int) -> int:
    """The VMEM bytes a call of `attention` asks for, from its shapes alone:
    the scratch (the queries' and the keys' pieces side by side a pair, the
    values' a piece, the running maximum and sum, a lane row each, and the
    float32 accumulator), the pipeline's double-buffered float32 blocks
    (the step's queries a part, a head's keys a part, its values, the result)
    and one float32 score tile.
    `keys` positions a row, the parts' `widths`, the values' `dv`, `shared`
    query heads a key-value head (the fewest over the parts and the values),
    `count` pieces of `cd`. Mosaic's own count is not this one (it keeps a
    stack of temporaries beside them and some blocks once: 12.5 MiB where this
    counts 15.1, 13.9 where this counts 12.0); this one is read against
    VMEM_LIMIT and agrees with the chip's verdict on every shape a cell or a
    float32 stand-in of one has run (PERF.md section 6, PR 58)."""
    held, block = pieces_held(cd, count), tile(keys, window)
    tall, k_len = heads_a_step(shared, block) * block, _round_up(keys, block)
    width = _round_up(held * (held + 1) // 2 * sum(widths), LANES)
    item = jnp.dtype(cd).itemsize
    scratch = (tall + k_len) * width * item + k_len * held * dv * item + tall * (2 * LANES + dv) * 4
    blocks = 2 * 4 * ((tall + k_len) * sum(widths) + k_len * dv + tall * dv)
    return scratch + blocks + tall * block * 4


# What a kernel has by default on a v5e, which this one never raises (ROWS has
# why), and what `sequence.attention_choice` reads `vmem_bytes` against before
# it says `pallas`. The widest head a cell runs, `mimo_v2`'s 192-wide keys over
# 128-wide values at three pieces, counts 15.1 MiB and is taken by the chip;
# 256 wide both ways counts 22.5 MiB at three pieces and 16.5 at one float32
# piece (the readings' stand-in), and the chip refuses both at run time (16.99
# and 16.20 MiB by its own count): PERF.md section 6, PR 50 (a) and PR 58.
VMEM_LIMIT = 16 << 20


def _kernel(*refs, widths, reps, rep_v, dv, held, cd, scale, window, offset, block, keys, stacked, sunk=False):
    parts = len(widths)
    tall = stacked * block
    pairs = [(i, j) for i in range(held) for j in range(held) if i + j < held]
    q_refs, k_refs, v_ref = refs[:parts], refs[parts:2 * parts], refs[2 * parts]
    # With a sink: its logits [heads] in SMEM after the values, and the
    # share's block after the result's.
    sink_ref, o_ref, share_ref = (refs[2 * parts + 1:2 * parts + 4] if sunk else (None, refs[2 * parts + 1], None))
    qcat, kcat, vcat, m_ref, l_ref, acc_ref = refs[-6:]
    head, qi = pl.program_id(1), pl.program_id(2)
    used = len(pairs) * sum(widths)

    def cut(ref, rows, store):
        # The whole key range of a head, PIECE_ROWS at a time.
        def chunk(c, carry):
            at = pl.ds(pl.multiple_of(c * rows, rows), rows)
            store(at, _pieces(ref[at, :].astype(jnp.float32), cd, held))
            return carry

        jax.lax.fori_loop(0, keys // rows, chunk, None)

    rows = math.gcd(block, PIECE_ROWS)
    column = 0
    for part, (width, rep) in enumerate(zip(widths, reps)):
        first_column = column

        @pl.when((qi == 0) & (head % rep == 0))
        def _keys(part=part, width=width, first_column=first_column):
            def store(at, ks):
                for c, (_, j) in enumerate(pairs):
                    kcat[at, first_column + c * width:first_column + (c + 1) * width] = ks[j]
                if part == 0 and used < kcat.shape[1]:
                    kcat[at, used:] = jnp.zeros((rows, kcat.shape[1] - used), cd)

            cut(k_refs[part], rows, store)

        qs = _pieces(q_refs[part][...].reshape(tall, width).astype(jnp.float32), cd, held)
        for c, (i, _) in enumerate(pairs):
            qcat[:, column + c * width:column + (c + 1) * width] = qs[i]
        column += len(pairs) * width
    if used < qcat.shape[1]:
        qcat[:, used:] = jnp.zeros((tall, qcat.shape[1] - used), cd)

    @pl.when((qi == 0) & (head % rep_v == 0))
    def _values():
        def store(at, vs):
            for j, piece in enumerate(vs):
                vcat[at, j * dv:(j + 1) * dv] = piece

        cut(v_ref, rows, store)

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

    def key_block(kb, carry):
        start = pl.multiple_of(kb * block, block)
        at = pl.ds(start, block)
        s = jax.lax.dot_general(
            qcat[...], kcat[at, :], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        k_pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
        seen = k_pos <= q_pos
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


@functools.partial(jax.jit, static_argnames=("scale", "window", "cd", "count", "interpret"))
def attention(qs, ks, v, *, scale: float, window: int | None, cd, count: int, interpret: bool = False,
              sink: jax.Array | None = None):
    """softmax(sum over the parts of `q k'` * scale | causal, window) v.

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
    without a window)."""
    n, heads, queries, _ = qs[0].shape
    keys, dv = v.shape[2], v.shape[3]
    widths = tuple(q.shape[-1] for q in qs)
    held = pieces_held(cd, count)
    pairs = held * (held + 1) // 2  # (i, j), i + j < held
    block = tile(keys, window)
    stacked = heads_a_step(min(heads // x.shape[1] for x in (*ks, v)), block)
    q_len, k_len = _round_up(queries, block), _round_up(keys, block)

    def padded(x, length):  # along the positions, with zeros
        return x if x.shape[2] == length else jnp.pad(x, ((0, 0), (0, 0), (0, length - x.shape[2]), (0, 0)))

    # Queries padded at their END keep their positions; so do the keys. The
    # queries' heads in the groups a step takes together.
    qs = tuple(padded(q, q_len).reshape(n, heads // stacked, stacked, q_len, q.shape[-1]) for q in qs)
    ks = tuple(padded(k, k_len) for k in ks)
    v = padded(v, k_len)
    width = _round_up(pairs * sum(widths), LANES)
    computed = n * heads * tile_pairs(queries, keys, window)

    def stacked_heads(d):
        return pl.BlockSpec((None, None, stacked, block, d), lambda b, g, i: (b, g, 0, i, 0))

    def a_head(x):  # of the keys or values: the one that group g's heads read
        rep = heads // x.shape[1] // stacked
        return pl.BlockSpec((None, None, k_len, x.shape[-1]), lambda b, g, i: (b, g // rep, 0, 0))

    def result(d):
        return jax.ShapeDtypeStruct((n, heads // stacked, stacked, q_len, d), jnp.float32)

    body = functools.partial(
        _kernel, widths=widths, reps=tuple(heads // k.shape[1] // stacked for k in ks),
        rep_v=heads // v.shape[1] // stacked, dv=dv, held=held, cd=cd, scale=scale, window=window,
        offset=keys - queries, block=block, keys=k_len, stacked=stacked)
    in_specs = [stacked_heads(d) for d in widths] + [a_head(k) for k in ks] + [a_head(v)]
    out_shape, out_specs, operands = result(dv), stacked_heads(dv), (*qs, *ks, v)
    if sink is not None:
        body = functools.partial(body, sunk=True)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        out_shape, out_specs = (out_shape, result(1)), (out_specs, stacked_heads(1))
        operands += (sink.astype(jnp.float32).reshape(heads),)
    out = pl.pallas_call(
        body,
        out_shape=out_shape,
        grid=(n, heads // stacked, q_len // block),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((stacked * block, width), cd),
            pltpu.VMEM((k_len, width), cd),
            pltpu.VMEM((k_len, held * dv), cd),
            pltpu.VMEM((stacked * block, 1), jnp.float32),
            pltpu.VMEM((stacked * block, 1), jnp.float32),
            pltpu.VMEM((stacked * block, dv), jnp.float32),
        ],
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
