"""What the sequence-ranker families (phi4flash, pangu_moe, exaone_moe,
olmo_hybrid, mimo_v2, falcon_h1, qwen3_next, nemotron_h, sdar_moe) share: products whose float32 activations enter as pieces of the
compute dtype (`product`: one product a call wherever a form exists that
copies no large array; against a weight always, two pieces along a second
contracted axis and three or more stacked, noted for the `startup.products`
stamp), the causal softmax of a block of queries, the blocks
themselves, causal attention in those blocks (`blocked_attention`:
exaone_moe's, olmo_hybrid's and qwen3_next's full layers, falcon_h1's,
nemotron_h's (no rotary turn before it), both
kinds of mimo_v2's, whose window layers' softmax holds a learned sink, and
sdar_moe's, whose mask is the one that looks AHEAD: to the end of the query's
block of `span` positions; `causal_softmax` names the three mask parameters,
`window`, `sink` and `span`, and who asks for each), the
attention at all positions as one Pallas kernel a layer where a one-chip
served entry runs on a TPU and the kernel's scratch fits its VMEM at the
layer's shapes (`attention`, `takes_kernel`, `attention_choice`: all nine
families), the causal depthwise convolution (`causal_conv`: phi4flash's Mamba
layers, olmo_hybrid's and qwen3_next's linear ones and falcon_h1's and
nemotron_h's Mamba-2 mixers), and
the cut to the last position. One implementation, so that a change to any of
them is measured on every family's cell. (`models/routed.py` has what the
routed families share beside these.)

A family keeps its own `OPERAND_PIECES` and a `_product` of four arguments
that hands it on (its tests and the benchmark's precision readings replace
either by name to plant the precision below the stated one).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import string
import threading
from typing import NamedTuple

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


def spec_terms(spec: str) -> tuple[str, str, str]:
    """(first operand's labels, second's, the result's) of a two-operand spec."""
    terms, _, out = spec.replace(" ", "").partition("->")
    first, _, second = terms.partition(",")
    return first, second, out


def contraction_axes(spec: str) -> tuple[int, int] | None:
    """The axis `spec` contracts, in the first operand and in the second:
    that of the one label both operands carry and the result does not. None
    where a spec has no such label, or more than one."""
    first, second, out = spec_terms(spec)
    found = [c for c in first if c.isalpha() and c in second and c not in out]
    if len(found) != 1:
        return None

    def axis(term: str) -> int:  # counted from the end where an ellipsis stands before the label
        head, dots, tail = term.partition("...")
        return tail.index(found[0]) - len(tail) if dots and found[0] in tail else head.index(found[0])

    return axis(first), axis(second)


# The forms a product of an activation in pieces against a weight takes, as
# `startup.products` names them; in both the pieces meet in ONE product's own
# accumulation.
FUSED_FORMS = ("stacked", "contracted")


def product_summary(notes: list) -> dict:
    """`{"ops", "fused_ops", "forms"}` of the products `product` noted as
    `(M, k, n, pieces, form)`: their operations (2 M k n a piece), those of
    the products whose pieces meet in one product (FUSED_FORMS), and the
    calls by form. A servable's `startup.products` stamp."""
    ops = [(2 * m * k * n * count, form) for m, k, n, count, form in notes]
    return {
        "ops": sum(o for o, _ in ops), "fused_ops": sum(o for o, form in ops if form in FUSED_FORMS),
        "forms": dict(sorted(collections.Counter(form for _, form in ops).items())),
    }


def product(spec: str, x: jax.Array, y: jax.Array, cd, count: int = OPERAND_PIECES) -> jax.Array:
    """einsum(spec, x, y) with operands in the compute dtype and a float32
    result: one pass of the MXU a pair of pieces, but for the pairs whose
    product is below the last piece's size. The pairs are added up in a
    product's own accumulation wherever a form does that without copying a
    large array (a product a pair writes its float32 result to memory and the
    next reads it back to add its own). The form follows the operands:

    - the second whole in the compute dtype (a weight): ONE `dot_general`
      whatever the shapes. Exactly two pieces meet along a second contracted
      axis, the weight repeated over it by a `broadcast_to` (`contracted`:
      what the next case does for `q k'`, with nothing concatenated): the
      float32 result is written once, where a product a piece wrote it, read
      it back and wrote it again. The v5e's compiler keeps that broadcast
      inside the product for an MLP's and a mixer's weights, and COPIES the
      repeated weight (`bf16[2, k, n]`, a temporary of the step) for the
      attention's q, k and v at all positions, whose products it turns round
      to write head-major for the attention kernel: ten, two and four such
      arrays in the three cells' steps, 2-3 ms of the 58.6 / 9.0 / 3.9 the
      form gains (PERF.md section 7, PR 57 (b);
      tests/test_tpu_compile.py::assert_no_large_weight_is_copied counts
      them). Three pieces or more are stacked on a new leading axis and
      summed over it, which the compiler fuses into one product that reads
      the weight once (`stacked`: at two pieces it loses to a product a piece
      in two cells of three). Read on the v5e at every weight product of the
      three two-piece cells' top-bucket steps, 2,560 to 21,504 deep, 4 rows x
      1 position to 8,192 positions (PERF.md section 6, PR 57): the
      contracted form is the fastest step of the three in each. Noted for the
      served entry being traced (`serving_attention(products=)`);
    - both in pieces and the result no smaller than either (`q k'`, whose
      score tile is what the memory carries): the pairs side by side along
      the contracted axis of both, ONE product. An operand is copied once a
      pair, which is why the result has to be the larger;
    - both in pieces and the first the largest array (`p v`, the
      probabilities): a product a PIECE of it, against the second's pieces
      that pair with that one side by side along an axis of the second that
      the result keeps; the large operand is read once a piece and never
      copied, and the parts of the small results are added up;
    - anything else (the second the largest or the only one in pieces, an
      ellipsis between pieces, a spec that does not contract exactly one
      label): a product a pair, added up.
    """
    xs, ys = pieces(x, cd, count), pieces(y, cd, count)
    kept = max(len(xs), len(ys))
    pairs = [(i, j) for i in range(len(xs)) for j in range(len(ys)) if i + j < kept]
    einsum = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    first, second, out = spec_terms(spec)
    if len(pairs) == 1:
        return einsum(spec, xs[0], ys[0])
    axes = contraction_axes(spec)
    if len(ys) == 1:
        form = "contracted" if len(xs) == 2 else "stacked"
        served = served_entry()
        if served is not None and served.products is not None and axes is not None:
            k = x.shape[axes[0]]
            served.products.append((x.size // k, k, y.size // k, len(xs), form))
        extra = next(c for c in string.ascii_uppercase if c not in spec)
        if form == "stacked":
            return jnp.sum(einsum(f"{extra}{first},{second}->{extra}{out}", jnp.stack(xs), ys[0]), axis=0)
        return einsum(f"{extra}{first},{extra}{second}->{out}", jnp.stack(xs),
                      jnp.broadcast_to(ys[0], (len(xs),) + ys[0].shape))
    if min(len(xs), len(ys)) > 1 and axes is not None:
        result = jax.eval_shape(functools.partial(jnp.einsum, spec), x, y)
        if result.size >= max(x.size, y.size):
            return einsum(
                spec, jnp.concatenate([xs[i] for i, _ in pairs], axis=axes[0]),
                jnp.concatenate([ys[j] for _, j in pairs], axis=axes[1]))
        beside = [c for c in second if c not in first and c in out]
        if beside and "." not in spec and x.size >= y.size:
            y_axis, out_axis = second.index(beside[0]), out.index(beside[0])
            return sum(
                part for i, piece in enumerate(xs)
                for part in jnp.split(
                    einsum(spec, piece, jnp.concatenate(ys[:kept - i], axis=y_axis)), kept - i, axis=out_axis))
    return sum(einsum(spec, xs[i], ys[j]) for i, j in pairs)


def causal_softmax(scores: jax.Array, q_start: int, window: int | None = None, sink: jax.Array | None = None,
                   span: int | None = None):
    """softmax over the keys of `scores [..., queries, keys]`, the queries at
    positions q_start .. against the keys at positions 0 ..: a query sees the
    keys up to its own position, and within `window` positions where one is
    given (position t sees t - window + 1 .. t); with `span`, up to the END of
    its block of `span` positions (`u // span <= t // span`: the one mask that
    looks ahead). float32 in, float32 out.

    The three mask parameters and who asks for each: `window` narrows the
    causal reach (phi4flash's, exaone_moe's and mimo_v2's window layers),
    `sink` widens the softmax by a term no key carries (mimo_v2's window
    layers), `span` widens the reach to the query's own block (sdar_moe, every
    layer). None of each is the plain causal softmax.

    `sink` (broadcast against `scores[..., :1]`) is a learned logit that no
    key carries: it joins the maximum and the denominator and gives no value,
    so a query's probabilities sum to less than one. With it the result is
    (the probabilities, the sink's own share `[..., queries, 1]`)."""
    q_pos = q_start + jnp.arange(scores.shape[-2])[:, None]
    k_pos = jnp.arange(scores.shape[-1])[None, :]
    seen = k_pos <= q_pos if span is None else k_pos < (q_pos // span + 1) * span
    if window is not None:
        seen &= q_pos - k_pos < window
    if sink is None:
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    scores = jnp.where(seen, scores, -jnp.inf)
    top = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), sink)
    kept, aside = jnp.exp(scores - top), jnp.exp(sink - top)
    total = jnp.sum(kept, axis=-1, keepdims=True) + aside
    return kept / total, aside / total


def query_blocks(queries: int, keys: int, window: int | None = None, block: int = ATTN_BLOCK):
    """The blocks of an attention whose queries are the LAST `queries`
    positions of the keys' range (all of them, or the last one alone), as
    (start, stop, first, last): queries start .. stop - 1 read the keys
    first .. last - 1, which is all their causal reach (and their window's)
    holds. The block's first query stands at position
    `keys - queries + start - first` among those keys. Under a block mask's
    `span` the blocks are the same ones: a block that ends on a span's edge
    reads nothing past its own last position, and `attention_choice`, which
    every caller with a span asks first, refuses a span that would not
    (`attention_kernel.check_span`)."""
    offset = keys - queries
    for start in range(0, queries, block):
        stop = min(start + block, queries)
        first = 0 if window is None else max(0, offset + start - window + 1)
        yield start, stop, first, offset + stop


class Served(NamedTuple):
    """What `serving_attention` was entered with: the notes lists of the
    attention, the routed layers, the delta rule, the SSD, the weight
    products and the Mamba-2 mixer's convolution, and whether the kernels run
    interpreted."""
    notes: list
    interpret: bool
    grouped: list | None
    delta: list | None
    ssd: list | None
    products: list | None
    conv: list | None


_served = threading.local()  # .entry: a Served while serving_attention is entered


@contextlib.contextmanager
def serving_attention(notes: list, interpret: bool = False, grouped: list | None = None,
                      delta: list | None = None, ssd: list | None = None, products: list | None = None,
                      conv: list | None = None):
    """While the batcher traces a one-chip served entry in this thread
    (serving/batcher.py _build_entry, and nowhere else): an attention at all
    positions may take the Pallas kernel (ops/attention_kernel.py), and
    `takes_kernel` appends to `notes` what it chose (attention_choice's dict,
    once each), the servable's `startup.attention` stamp; and a routed
    layer's held experts may take theirs (ops/grouped_kernel.py, chosen by
    `routed.takes_kernel`, which appends `routed.grouped_choice`'s dict to
    `grouped` where one is given: the `startup.grouped` stamp); and a gated
    delta rule's chunk pass may take its own (ops/delta_kernel.py, chosen by
    `olmo_hybrid.takes_kernel`, which appends `olmo_hybrid.delta_choice`'s
    dict to `delta`: the `startup.delta_rule` stamp); and a Mamba-2 mixer's
    SSD at all positions may take its own (ops/ssd_kernel.py, chosen by
    `falcon_h1.takes_kernel`, for falcon_h1's and nemotron_h's; `falcon_h1.note_ssd` appends
    `falcon_h1.ssd_choice`'s dict to `ssd`: the `startup.ssd` stamp), and the
    convolution before it its own (ops/conv_kernel.py, chosen by
    `falcon_h1.conv_choice`, whose dict `falcon_h1.note_conv` appends to
    `conv`: the `startup.conv` stamp); and
    `product` appends to `products`, where one is given, every product of an
    activation in pieces against a weight that it traces, as `(M, k, n,
    pieces, form)` (the `startup.products` stamp: the form is chosen the same
    inside and outside).
    `interpret` is for tests on the CPU: choose as on a TPU and run the
    kernels interpreted.

    Outside it every attention, routed layer, delta rule and SSD is the XLA path
    that stood before its kernel, as `embeddings.serving_gathers` keeps XLA's
    gather and for its reasons: the GSPMD executors, `shard_map` and the
    trainer trace `model.apply` themselves, and a `tpu_custom_call` neither
    partitions nor has a gradient rule."""
    before = getattr(_served, "entry", None)
    _served.entry = Served(notes, interpret, grouped, delta, ssd, products, conv)
    try:
        yield notes
    finally:
        _served.entry = before


def served_entry() -> Served | None:
    """The `Served` of the served entry this thread is tracing
    (serving_attention), else None."""
    return getattr(_served, "entry", None)


def kernels_run() -> bool:
    """Whether a served entry's kernels run, from what a trace can see:
    inside serving_attention, on a TPU (or interpreted)."""
    served = served_entry()
    return served is not None and (served.interpret or jax.default_backend() == "tpu")


def kernel_serves(queries: int) -> bool:
    """Whether an attention of `queries` queries a row may run the kernel:
    where a served entry's kernels run (`kernels_run`), and more than one
    query (the last layer's one query has a `[1, keys]` tile: nothing to keep
    out of memory). `attention_choice` asks besides whether its shapes fit."""
    return kernels_run() and queries > 1


class Heads(NamedTuple):
    """The shapes of an attention that decide what its kernel keeps in VMEM
    (ops/attention_kernel.py `vmem_bytes`): the widths of the parts of a
    query and key head, the width of a value head, the query heads that read
    one key-value head (the fewest over the parts and the values) and the
    compute dtype."""
    widths: tuple[int, ...]
    dv: int
    shared: int
    cd: object


def attention_choice(queries: int, keys: int, window: int | None, count: int, heads: Heads | None = None,
                     span: int | None = None) -> dict:
    """`{"kernel": "pallas" | "xla", "block", "pieces"}`: which path serves
    an attention, the side of the kernel's score tile (0 where XLA's blocks
    run: `Model.attention_plan` states those) and the pieces an activation
    enters its products as; and `"span"`, the block mask's, where the
    attention has one (`attention_kernel.check_span` holds it to the path's
    tiles, here where the path is chosen). A servable's `startup.attention` stamp. Where the
    kernel would serve but its scratch and blocks at the `heads`' shapes are
    past the VMEM a kernel has (`attention_kernel.fits`: a shape that does
    not fit is refused by the chip at warm-up, not by the compiler), XLA's blocks
    serve, and the stamp says `"why": "vmem"`."""
    choice = {"kernel": "xla", "block": 0, "pieces": count}
    if kernel_serves(queries):
        from ..ops.attention_kernel import fits, tile

        if heads is not None and not fits(keys, window, *heads, count):
            choice["why"] = "vmem"
        else:
            choice.update(kernel="pallas", block=tile(keys, window))
    if span is not None:
        from ..ops.attention_kernel import check_span

        check_span(queries, keys, span, choice["block"] or ATTN_BLOCK, window)
        choice["span"] = span
    return choice


def takes_kernel(queries: int, keys: int, window: int | None, count: int, heads: Heads | None = None,
                 span: int | None = None) -> bool:
    """Whether `attention` serves this one (attention_choice has the rule),
    noted for the served entry being traced."""
    choice = attention_choice(queries, keys, window, count, heads, span)
    served = served_entry()
    if served is not None and choice not in served.notes:
        served.notes.append(choice)
    return choice["kernel"] == "pallas"


def attention(qs, ks, v: jax.Array, window: int | None, cd, count: int, scale: float,
              sink: jax.Array | None = None, span: int | None = None):
    """Causal attention of the queries at the LAST positions of the keys'
    range as ONE Pallas kernel (ops/attention_kernel.py) that keeps the score
    tile in VMEM: for the callers `takes_kernel` said yes to. The score is
    the sum over the parts of `q k'`, times `scale`; position t sees
    `t - window + 1 .. t` (all up to t without a window; with `span`, all up
    to the end of t's block of `span` positions).

    qs    a tuple of `[n, Lq, H, d_p]` float32, a part each
    ks    a tuple of `[n, Lk, H_p, d_p]`: query head h reads head `h // (H / H_p)`
    v     `[n, Lk, H_v, d_v]`, read the same way
    sink  `[H]` float32 where every head's softmax holds one more term, a
          logit that no key carries (`causal_softmax` has the form)
    returns `[n, Lq, H, d_v]` float32; with a sink, that and the sink's share
    of every query's softmax, `[n, Lq, H]`

    The kernel's arrays are head-major; the transposes are XLA's, fused into
    what makes the operands and reads the result."""
    from ..ops.attention_kernel import attention as kernel

    heads_first = functools.partial(jnp.transpose, axes=(0, 2, 1, 3))
    out = kernel(
        tuple(map(heads_first, qs)), tuple(map(heads_first, ks)), heads_first(v),
        scale=float(scale), window=window, cd=jnp.dtype(cd), count=count, interpret=served_entry().interpret, sink=sink,
        span=span)
    if sink is None:
        return heads_first(out)
    return heads_first(out[0]), jnp.transpose(out[1][..., 0], (0, 2, 1))


def blocked_pairs(queries: int, keys: int, window: int | None = None, count: int = OPERAND_PIECES,
                  heads: Heads | None = None, span: int | None = None) -> tuple[int, int]:
    """((query, key) pairs the tiles of `blocked_attention` compute over a row,
    those its masks keep) for the last `queries` positions of `keys`: the
    kernel's tiles where it serves (`attention_choice`, at the `heads`' shapes
    and `count` pieces where given). A `span` moves no tile and keeps
    `ahead_pairs` more pairs."""
    if attention_choice(queries, keys, window, count, heads, span)["kernel"] == "pallas":
        from ..ops.attention_kernel import tile_pairs

        computed = tile_pairs(queries, keys, window)
    else:
        computed = sum(
            (stop - start) * (last - first) for start, stop, first, last in query_blocks(queries, keys, window))
    offset = keys - queries
    seen = sum(min(offset + t + 1, window or keys) for t in range(queries))
    return computed, seen + ahead_pairs(queries, keys, span)


def ahead_pairs(queries: int, keys: int, span: int | None) -> int:
    """The (query, key) pairs with the key AFTER the query that the mask keeps
    over a row, for the last `queries` positions of `keys`: what a block mask
    of `span` positions sees beyond the causal reach (`span - 1 - t % span` a
    query: `keys (span - 1) / 2` at all positions of a row of whole spans, 0
    for a lone last query) and 0 under every causal mask (`span` None)."""
    return 0 if span is None else sum(span - 1 - t % span for t in range(keys - queries, keys))


def blocked_attention(q: jax.Array, k: jax.Array, v: jax.Array, window: int | None, cd,
                      count: int = OPERAND_PIECES, sink: jax.Array | None = None, span: int | None = None):
    """Causal attention of the queries at the LAST `q.shape[1]` positions of
    the keys' range in blocks of ATTN_BLOCK queries, each against the keys its
    causal reach (and its window's, where one is given) holds: a layer at
    all positions, any layer at the last position alone. With `span`, a query
    sees up to the end of its block of `span` positions (`causal_softmax` has
    the three mask parameters), in the same blocks and tiles
    (`attention_kernel.check_span`). `q [n, Lq, G, J, d]`
    (J query heads a key-value head), `k [n, Lk, G, d]`, `v [n, Lk, G, d_v]`;
    returns `[n, Lq, G, J, d_v]` float32. Activations enter as `count` pieces.
    With `sink [G * J]` (a logit a query head beside its keys':
    `causal_softmax`), returns that and the sink's share of every query's
    softmax, `[n, Lq, G, J]`."""
    queries, keys, out, shares = q.shape[1], k.shape[1], [], []
    n, _, groups, per_group, head = q.shape
    if takes_kernel(queries, keys, window, count, Heads((head,), v.shape[-1], per_group, cd), span):
        flat = q.reshape(n, queries, groups * per_group, head)
        o = attention((flat,), (k,), v, window, cd, count, head ** -0.5, sink, span)
        if sink is None:
            return o.reshape(q.shape[:-1] + v.shape[-1:])
        return o[0].reshape(q.shape[:-1] + v.shape[-1:]), o[1].reshape(q.shape[:-1])
    aside = None if sink is None else sink.astype(jnp.float32).reshape(groups, per_group, 1, 1)
    for start, stop, first, last in query_blocks(queries, keys, window):
        scores = product("nqgjd,nkgd->ngjqk", q[:, start:stop], k[:, first:last], cd, count) * q.shape[-1] ** -0.5
        probs = causal_softmax(scores, keys - queries + start - first, window, aside, span)
        if sink is not None:
            probs, share = probs
            shares.append(jnp.transpose(share[..., 0], (0, 3, 1, 2)))
        out.append(product("ngjqk,nkgd->nqgjd", probs, v[:, first:last], cd, count))
    o = out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)
    if sink is None:
        return o
    return o, shares[0] if len(shares) == 1 else jnp.concatenate(shares, axis=1)


def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array | None = None) -> jax.Array:
    """silu of the causal depthwise convolution of `x [n, L, channels]` along
    its positions, float32: `w [channels, taps]`, tap k reads position
    t - (taps - 1) + k, so position t reads t - taps + 1 .. t and nothing
    ahead of it; `b [channels]` is added before the silu where given."""
    taps = w.shape[1]
    w = w.astype(jnp.float32)
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(padded[:, k:k + x.shape[1]] * w[:, k] for k in range(taps))
    return jax.nn.silu(y if b is None else y + b.astype(jnp.float32))


def last_position(*arrays: jax.Array):
    """Each `[n, L, ...]` array cut to its last position, `[n, 1, ...]`: from
    where a stack mixes nothing more along the positions that the score's
    own position does not read, the rest is computed there alone."""
    cut = tuple(a[:, -1:] for a in arrays)
    return cut[0] if len(cut) == 1 else cut
