"""The gated delta rule's chunk pass as one Pallas TPU kernel whose state
never leaves VMEM between a row's first chunk and its last.

XLA's path (`models/olmo_hybrid.py::gated_delta_rule`) makes `W = T (K e^G)`,
`U = T V` and `within = lower((Q K') * D)` for every chunk of a layer at once,
writes them to HBM, and then walks the chunks in a `scan` whose every step
reads them back with the state and writes the state and `O`: five products 64
to 192 a side a step, two of them half-filled launches against the same
state, 192 hand-overs a row through HBM (PERF.md section 6, PR 47 and PR 52).
Here a grid step is one (row, group of heads, chunk), the chunk axis last and
in order, and everything after `T = (I + A)^-1 diag(b)` happens in it:

  D = exp(G_i - G_j) where i >= j, else 0           from the chunk's running sum G, [1, C] as it lies
  W = T (K e^G);  U = T V                           `T` pieces one under the other, a product a piece of the other side
  within = (Q K') * D
  [W; Q e^G] S                                      ONE product against the state: its upper half is `W S`, the lower `(Q e^G) S`
  V' = U - W S;  O = (Q e^G) S + within V'
  S <- e^(G_C) S + (K e^(G_C - G))' V'

the algebra of `models/olmo_hybrid.py`'s docstring to the letter; every
exponent is a difference under its mask. The state `S [dk, dv]` of the heads
in flight is float32 VMEM scratch: the start state is copied in at a row's
chunk 0, the state after the last chunk is written out once, and `W`, `U`,
`within` and `V'` never exist in HBM. What stays XLA's: the running sum of g,
`K K'` (once a KEY head), `A` and the block inverse that makes `T`
(lane-batched over every chunk of a layer side by side, which a kernel that
walks chunks in order has not).

The H value heads are whole groups of `r = H / Hk` a key head (one to one in
olmo_hybrid, two to one in qwen3_next). q and k cross as the `Hk` key heads
they are, never repeated: a step's q and k blocks are the `group / r` key
heads of its `group` value heads, `Q K'` is one product a key head that each
of its value heads masks with its own `D`, and everything under a value
head's decays (`K e^G`, `K e^(G_C - G)`, `W`, `U`, `V'`, the state) is a value
head's.

Operands enter the MXU as `count` pieces of the compute dtype in the pairs
`i + j < count`, accumulation and everything else is float32, and the state
is rounded to `state_dtype` after every chunk (float32: a no-op) as the XLA
path's carry is: the result is that path's to float32 rounding in another
order of additions (tests/test_delta_kernel.py, interpreted on the CPU;
tests/test_tpu_compile.py compiles it for a v5e).

q, k, v and o cross the kernel as they lie, `[n, L, heads x d]`, with no turn
on either side: a block is a chunk's rows and the columns of a GROUP of value
heads (of its key heads, for q and k), the fewest whole key heads' groups
whose keys and whose values are whole lanes side by side (4 at dk 96, dv 192;
a block of one head's 96 columns is no legal block of that array), twice that
here. A head's tile is a static slice of the block's lanes. Where
the heads are no whole number of groups (30 of 8) the last group's block hangs
over the array's edge: what it reads there no head's result reads, and what it
writes there is dropped. (Head-major `[n, H, L, d]` blocks of one head are
legal too and were tried first: the kernel read 3.07 ms a layer against 2.39
so, and XLA's turns around it cost 12 ms a step more: PERF.md section 6,
PR 52.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention_kernel import LANES, _pieces, pieces_held

# Heads a grid step takes at least, where their columns are whole lanes: a
# step's blocks, twice for the pipeline, and the states of the heads in flight
# are 0.8 MB a head at dk 96, dv 192, chunk 64 (6.7 MB at 8 heads), well inside
# the 16 MiB a kernel has by default (what a kernel claims beyond it is taken
# from XLA's prefetch of the step's weights: PERF.md section 6, PR 48). On the
# v5e 8 heads a step read 2.39 ms a layer and 4 read 2.58.
HEADS = 8

NN = (((1,), (0,)), ((), ()))  # x y
NT = (((1,), (1,)), ((), ()))  # x y'
TN = (((0,), (0,)), ((), ()))  # x' y


def _product(xs: list, ys: list, dims=NN) -> jax.Array:
    """`sum over i + j < pieces of xs[i] ys[j]` in float32: a product a piece
    of y, the pieces of x that pair with it one under the other (x's rows are
    the result's: not for `TN`, whose pairs are a product each)."""
    held, rows, out = len(xs), xs[0].shape[0], None
    dot = functools.partial(jax.lax.dot_general, dimension_numbers=dims, preferred_element_type=jnp.float32)
    for j, y in enumerate(ys):
        top = xs[:held - j]
        if dims == TN:
            parts = [dot(x, y) for x in top]
        else:
            wide = dot(top[0] if len(top) == 1 else jnp.concatenate(top, axis=0), y)
            parts = [wide[i * rows:(i + 1) * rows] for i in range(len(top))]
        for part in parts:
            out = part if out is None else out + part
    return out


def _chunk(across, q, k, v, t, s, scores, *, cut, diagonal, lower, state_dtype):
    """One value head's chunk: (`O [C, dv]`, the state after it, `Q K'`) from
    the chunk's running sum `across [1, C]`, its key head's `q`, `k [C, dk]`,
    `v [C, dv]`, `t [C, C]`, the state before it `s [dk, dv]` and `scores`,
    the key head's `Q K' [C, C]` where a value head before this one has made
    it, else None: made here then, and handed back for the next;
    `diagonal` and `lower` are the `[C, C]` masks i == j and i >= j."""
    chunk = q.shape[0]
    down = jnp.sum(jnp.where(diagonal, across, 0.0), axis=1, keepdims=True)  # G_i [C, 1] of G_j [1, C]
    left = down[chunk - 1:chunk, :]  # G_C, [1, 1]
    # D_ij = exp(G_i - G_j) where i >= j (there the difference is <= 0), else 0
    decay = jnp.where(lower, jnp.exp(jnp.minimum(down - across, 0.0)), 0.0)
    grown = jnp.exp(down)
    t = cut(t)
    w = _product(t, cut(k * grown))
    u = _product(t, cut(v))
    if scores is None:
        scores = _product(cut(q), cut(k), NT)
    within = scores * decay  # each value head masks its key head's Q K' with its own D
    both = _product(cut(jnp.concatenate([w, q * grown], axis=0)), cut(s))  # [W; Q e^G] S
    fresh = cut(u - both[:chunk])  # V'
    o = both[chunk:] + _product(cut(within), fresh)
    s = jnp.exp(left) * s + _product(cut(k * jnp.exp(left - down)), fresh, TN)
    return o, s.astype(state_dtype).astype(jnp.float32), scores


def _kernel(total_ref, q_ref, k_ref, v_ref, t_ref, start_ref, o_ref, end_ref, state, *,
            held, cd, state_dtype, chunk, heads, shared, dk, dv):
    z = pl.program_id(2)
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    chunk_of = functools.partial(
        _chunk, cut=functools.partial(_pieces, cd=cd, held=held), diagonal=i == j, lower=j <= i,
        state_dtype=state_dtype)

    @pl.when(z == 0)
    def _start():
        state[...] = start_ref[...]

    for h in range(heads):  # a value head's columns of the step's lanes, and its key head's
        key = h // shared
        keys, values = slice(key * dk, (key + 1) * dk), slice(h * dv, (h + 1) * dv)
        if h % shared == 0:  # Q K' is one product a key head: its first value head makes it
            scores = None
        o_ref[:, values], state[h], scores = chunk_of(
            total_ref[h, pl.ds(z, 1), :], q_ref[:, keys], k_ref[:, keys], v_ref[:, values], t_ref[h], state[h], scores)

    @pl.when(z == pl.num_programs(2) - 1)
    def _end():
        end_ref[...] = state[...]


def heads_a_step(heads: int, dk: int, dv: int, shared: int = 1) -> int:
    """Value heads a grid step takes, `shared` of them reading one key head: a
    multiple of the fewest whole key heads' groups whose keys and whose values
    are whole lanes side by side (4 at dk 96, dv 192 one to one; 2 over 1 at
    128 / 128 two to one), HEADS or the next one up; every head where that is
    no fewer than all of them (a test's narrow heads: the whole axis is a
    legal block whatever its width)."""
    aligned = next(g for g in range(shared, shared * LANES + 1, shared)
                   if g // shared * dk % LANES == 0 and g * dv % LANES == 0)
    group = aligned * -(-HEADS // aligned)
    return group if group < heads else heads


@functools.partial(jax.jit, static_argnames=("heads", "cd", "count", "state_dtype", "interpret"))
def chunk_pass(total, q, k, v, t, start, *, heads: int, cd, count: int, state_dtype=jnp.float32,
               interpret: bool = False):
    """The rule's chunks in order, from `T` on: `o [n, L, H x dv]` and the
    state after the last chunk `[n, H, dk, dv]`, float32.

    total  `[n, H, Z, C]` float32, g's running sum inside each of a row's Z chunks of C positions
    q, k   `[n, L, Hk x dk]` float32 as the projections lie, L = Z x C (the caller pads: k = v = 0, g = 0
           and a zero column of `t` leave the state as it is), a key head for each `H / Hk` value heads in
           a row; v `[n, L, H x dv]`
    t      `[n, Z, H, C, C]` float32, a chunk's `T = (I + A)^-1 diag(b)`
    start  `[n, H, dk, dv]` float32, the state before the first position

    Activations enter the products as `count` pieces of `cd` in the pairs
    `i + j < count`; the state is rounded to `state_dtype` after every chunk."""
    n, _, steps, chunk = total.shape
    dk, dv = start.shape[2], v.shape[-1] // heads
    shared = heads * dk // q.shape[-1]  # the value heads that read one key head
    held, group = pieces_held(cd, count), heads_a_step(heads, dk, dv, shared)
    pairs = held * (held + 1) // 2

    def lanes(columns):  # a chunk's rows, the columns of the group's heads side by side
        return pl.BlockSpec((None, chunk, columns), lambda b, g, z: (b, z, g))

    def a_row(*shape):  # fetched once a (row, group of heads): the index does not move with the chunk
        return pl.BlockSpec((None, group) + shape, lambda b, g, z: (b, g, 0, 0))

    # value heads [g group, (g + 1) group) read key heads [g group / r, (g + 1) group / r): the same index map
    keys, values = lanes(group // shared * dk), lanes(group * dv)
    products = heads * (chunk * chunk * (dk + 2 * dv) + 3 * chunk * dk * dv) + heads // shared * chunk * chunk * dk
    return pl.pallas_call(
        functools.partial(_kernel, held=held, cd=cd, state_dtype=state_dtype, chunk=chunk, heads=group, shared=shared,
                          dk=dk, dv=dv),
        out_shape=(jax.ShapeDtypeStruct(v.shape, jnp.float32), jax.ShapeDtypeStruct(start.shape, jnp.float32)),
        # The last group may hold heads past the last: what it reads for them is
        # no number anyone reads, and what it writes for them is dropped.
        grid=(n, -(-heads // group), steps),
        in_specs=[a_row(steps, chunk), keys, keys, values,
                  pl.BlockSpec((None, None, group, chunk, chunk), lambda b, g, z: (b, z, g, 0, 0)), a_row(dk, dv)],
        out_specs=(values, a_row(dk, dv)),
        scratch_shapes=[pltpu.VMEM((group, dk, dv), jnp.float32)],
        # A row's chunks in order: the state in scratch is the last chunk's.
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * pairs * n * steps * products,
            transcendentals=n * heads * steps * chunk * (chunk + 3),
            bytes_accessed=4 * (total.size + q.size + k.size + 2 * v.size + t.size + 2 * start.size)),
        interpret=interpret,
        name="delta_rule",
    )(total, q, k, v, t, start)
