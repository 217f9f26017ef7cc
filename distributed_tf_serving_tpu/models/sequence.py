"""What the sequence-ranker families (phi4flash, pangu_moe, exaone_moe) share: products
whose float32 activations enter as pieces of the compute dtype, the causal
softmax of a block of queries, the blocks themselves, and the cut to the last
position. One implementation, so that a change to any of them is measured on
every family's cell. (`models/routed.py` has what the two routed families
share beside these.)

A family keeps its own `OPERAND_PIECES` and a `_product` of four arguments
that hands it on (its tests and the benchmark's precision readings replace
either by name to plant the precision below the stated one).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Pieces of the compute dtype a wider activation enters a product as.
OPERAND_PIECES = 2
# Queries a block of the attention at all positions: a [block, keys] score
# tile per head instead of [L, L].
ATTN_BLOCK = 512


def pieces(x: jax.Array, cd, count: int = OPERAND_PIECES) -> list[jax.Array]:
    """x as arrays of the compute dtype that sum to it: its rounding, then
    the rounding of what that left, `count` in all; x alone where the
    compute dtype holds it whole. The rounding is `reduce_precision`, which
    the compiler has to keep: a cast to the compute dtype and back it may
    take for excess precision it is allowed to keep (the TPU's does), and
    every piece after the first is then zero."""
    info = jnp.finfo(cd)
    if info.bits >= jnp.finfo(x.dtype).bits:
        return [x.astype(cd)]
    out = []
    for _ in range(count):
        piece = jax.lax.reduce_precision(x, info.nexp, info.nmant)
        out.append(piece.astype(cd))
        x = x - piece
    return out


def product(spec: str, x: jax.Array, y: jax.Array, cd, count: int = OPERAND_PIECES) -> jax.Array:
    """einsum(spec, x, y) with operands in the compute dtype and a float32
    result: one pass a pair of pieces, but for the pairs whose product is
    below the last piece's size."""
    xs, ys = pieces(x, cd, count), pieces(y, cd, count)
    return sum(
        jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)
        for i, a in enumerate(xs) for j, b in enumerate(ys) if i + j < max(len(xs), len(ys))
    )


def causal_softmax(scores: jax.Array, q_start: int, window: int | None = None) -> jax.Array:
    """softmax over the keys of `scores [..., queries, keys]`, the queries at
    positions q_start .. against the keys at positions 0 ..: a query sees the
    keys up to its own position, and within `window` positions where one is
    given (position t sees t - window + 1 .. t). float32 in, float32 out."""
    q_pos = q_start + jnp.arange(scores.shape[-2])[:, None]
    k_pos = jnp.arange(scores.shape[-1])[None, :]
    seen = k_pos <= q_pos
    if window is not None:
        seen &= q_pos - k_pos < window
    return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)


def query_blocks(queries: int, keys: int, window: int | None = None, block: int = ATTN_BLOCK):
    """The blocks of an attention whose queries are the LAST `queries`
    positions of the keys' range (all of them, or the last one alone), as
    (start, stop, first, last): queries start .. stop - 1 read the keys
    first .. last - 1, which is all their causal reach (and their window's)
    holds. The block's first query stands at position
    `keys - queries + start - first` among those keys."""
    offset = keys - queries
    for start in range(0, queries, block):
        stop = min(start + block, queries)
        first = 0 if window is None else max(0, offset + start - window + 1)
        yield start, stop, first, offset + stop


def last_position(*arrays: jax.Array):
    """Each `[n, L, ...]` array cut to its last position, `[n, 1, ...]`: from
    where a stack mixes nothing more along the positions that the score's
    own position does not read, the rest is computed there alone."""
    cut = tuple(a[:, -1:] for a in arrays)
    return cut[0] if len(cut) == 1 else cut
