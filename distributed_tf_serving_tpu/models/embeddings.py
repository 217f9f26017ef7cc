"""Embedding tables and weighted field lookups.

The reference's models (external SavedModels) consume hashed categorical ids
with per-feature weights (feat_ids/feat_wts, DCNClient.java:98-108). Here the
embedding bag is explicit: a single [vocab, dim] table, ids folded into the
vocab by modulo, gathered with jnp.take, and scaled by the feature weight.

TPU notes (v5e, PERF.md PR 25): the gather is bound by rows, not by HBM
bandwidth. One lookup of a whole 128-lane row costs 8-10 ns whatever its
bytes (512-byte rows at 52-63 GB/s of the chip's 819); a [V, 16] float32
table cannot be tiled row-major without padding 16 lanes to 128, so XLA
stores it dimension 0 minor and a lookup of 16 strided floats costs 20-25 ns
(2.5-3 GB/s). So a table narrower than a lane row is SERVED lane-packed,
[V/P, 128] with P = 128 // D logical rows side by side (pack_table; the same
bytes in the same order), and lookup_rows gathers the packed row and keeps
its lanes. Model.init, checkpoints and exports keep the logical [V, D]; the
loaders pack once, on the host or at init, never per request; field_embed
reads P off the table's shape, so model.apply serves both trees. ids arrive
[n, F] and the gather is batched over both axes at once (one gather of n*F
rows). The vocab axis is the sharding axis for the EP analog (SURVEY.md
§2.4): under shard_map each chip owns vocab/num_chips rows, packed or not,
and out-of-shard ids contribute zero, summed back with psum — see
parallel/embedding_sharding.py.

A multi-hot field (ModelConfig.multi_hot_sizes, the dlrm_dcnv2 family) is an
embedding bag: its ids take as many wire columns as the bag holds, the
lookup is the same one gather over all columns, and pool_bags sums each
bag's weighted rows to one vector.
"""

from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128  # one lane row of the TPU's (8, 128) tile


def pack_factor(vocab_size: int, embed_dim: int) -> int:
    """Logical rows per lane row: 128 // D when D is narrower than a lane
    row and divides it and the factor divides V, else 1 (left as it is)."""
    if embed_dim >= LANES or LANES % embed_dim:
        return 1
    p = LANES // embed_dim
    return p if vocab_size % p == 0 else 1


def embedding_init(
    rng: jax.Array, vocab_size: int, embed_dim: int, dtype, packed: bool = False
) -> jax.Array:
    """[V, D] normal table; packed=True draws it in its serving shape
    (pack_table's), the same values: the generator depends on the flat index
    alone, and a table of gigabytes must not exist twice to be reshaped."""
    p = pack_factor(vocab_size, embed_dim) if packed else 1
    # 1/sqrt(dim) scale keeps dot-product magnitudes O(1) for FM/two-tower.
    return jax.random.normal(rng, (vocab_size // p, p * embed_dim), dtype) / jnp.asarray(
        embed_dim**0.5, dtype
    )


def fold_ids(ids: jax.Array, vocab_size: int) -> jax.Array:
    """Fold arbitrary int64 feature ids into table rows (modulo hashing)."""
    return jnp.remainder(ids, vocab_size).astype(jnp.int32)


def sparse_linear(
    table: jax.Array,
    feat_ids: jax.Array,
    feat_wts: jax.Array,
) -> jax.Array:
    """Per-id scalar-weight sum in float32 — the Wide&Deep wide half and the
    DeepFM first-order term.

    table     [V]
    feat_ids  [n, F] int
    feat_wts  [n, F] float
    returns   [n] float32

    Runs in float32 regardless of the model's compute dtype (a scalar
    reduction, not an MXU op), which is why models using it must opt out of
    bf16 weight-transfer compression (Model.wts_in_compute_dtype=False).
    """
    rows = fold_ids(feat_ids, table.shape[0])
    return jnp.sum(
        jnp.take(table, rows, axis=0).astype(jnp.float32) * feat_wts.astype(jnp.float32),
        axis=-1,
    )


def pack_table(table, embed_dim: int):
    """Logical [V, D] -> serving shape [V/P, P*D] (P logical rows side by
    side in one lane row). Row-major, both are the same bytes in the same
    order, so a host array packs by reshape at no cost. A table that is
    packed already, or that pack_factor leaves alone, is returned as is."""
    vocab, width = table.shape
    p = pack_factor(vocab, embed_dim) if width == embed_dim else 1
    return table.reshape(vocab // p, p * width) if p > 1 else table


def unpack_table(table, embed_dim: int):
    """Serving shape -> logical [V, D], what checkpoints and exports hold. A
    packed table goes through the host, where the reshape is free (on the
    device it is a relayout: a second copy of the table)."""
    if table.shape[1] == embed_dim:
        return table
    return np.asarray(table).reshape(-1, embed_dim)


def _convert_table(params, convert, embed_dim: int):
    """`params` with convert(table, embed_dim) for its embedding table, every
    other leaf as it is; a tree without one (an imported graph) passes through."""
    if not isinstance(params, dict) or "embedding" not in params:
        return params
    return {**params, "embedding": convert(params["embedding"], embed_dim)}


def pack_params(params, embed_dim: int):
    """A zoo model's params as they are served: the table lane-packed."""
    return _convert_table(params, pack_table, embed_dim)


def unpack_params(params, embed_dim: int):
    """pack_params undone: the tree as checkpoints and exports hold it."""
    return _convert_table(params, unpack_table, embed_dim)


_served = threading.local()  # .entry: (notes, interpret) while serving_gathers is entered


@contextlib.contextmanager
def serving_gathers(notes: list, interpret: bool = False):
    """While the batcher traces a one-chip served entry in this thread
    (serving/batcher.py _build_entry, and nowhere else): lookup_rows may take
    the Pallas gather kernel, and appends to `notes` what it chose for each
    gather (gather_choice's dict, once each), the servable's `startup.gather`
    stamp. `interpret` is for tests on the CPU: choose as on a TPU and run
    the kernel in interpret mode.

    Outside it every gather is XLA's `jnp.take`, which GSPMD partitions, which
    runs under shard_map and which differentiates: the mesh executors
    (parallel/executor.py, parallel/multihost.py), the sharded lookup
    (parallel/embedding_sharding.py) and the trainer (train/trainer.py) trace
    `model.apply` themselves and keep it. A `tpu_custom_call` can do none of
    the three."""
    before = getattr(_served, "entry", None)
    _served.entry = (notes, interpret)
    try:
        yield notes
    finally:
        _served.entry = before


def gather_choice(table: jax.Array, rows: jax.Array) -> dict:
    """Which gather serves table[rows], from what a trace can see:
    `{"kernel": "pallas" | "xla", "row_bytes", "in_flight",
    "picked_in_kernel"}`, a servable's `startup.gather` stamp.

    The Pallas kernel (ops/gather_kernel.py) takes a table whose row is
    exactly one float32 lane row, [V, 128], on a TPU, inside serving_gathers
    (a one-chip served entry): the regime where XLA's gather costs 10-12 ns a
    looked-up row whatever the row holds and the kernel under 4 (PERF.md
    section 6, PR 39), at every lookup count of both benchmark ladders
    (512 x 26 to 8192 x 214 rows), so there is no threshold. Everything else
    keeps XLA's: a table of another width (the sequence families'
    [200064, 2560] and [19200, 7680]: 8192 lookups of 5-15 KB,
    bandwidth-bound; a logical [V, 16]), a bfloat16 [V, 128] table (two rows
    share a 32-bit sublane and Mosaic refuses a one-row copy), every CPU run,
    and every trace outside serving_gathers. Nothing picks a packed row's
    lanes inside the kernel yet: XLA's mask and fold follow it (PERF.md
    section 6, PR 39 has what was weighed)."""
    choice = {
        "kernel": "xla",
        "row_bytes": table.shape[1] * table.dtype.itemsize,
        "in_flight": 0,
        "picked_in_kernel": False,
    }
    served = getattr(_served, "entry", None)
    if (
        served is not None
        and (served[1] or jax.default_backend() == "tpu")
        and table.shape[1] == LANES
        and table.dtype == jnp.float32
        and rows.size
    ):
        from ..ops.gather_kernel import rows_in_flight

        choice.update(kernel="pallas", in_flight=rows_in_flight(rows.shape))
    return choice


def _take_rows(table: jax.Array, rows: jax.Array, dtype) -> jax.Array:
    """table[rows] in `dtype` by the gather gather_choice names, noted for
    the served entry being traced."""
    choice = gather_choice(table, rows)
    served = getattr(_served, "entry", None)
    if served is not None and choice not in served[0]:
        served[0].append(choice)
    if choice["kernel"] == "xla":
        return jnp.take(table, rows, axis=0).astype(dtype)
    from ..ops.gather_kernel import gather_rows

    return gather_rows(table, rows, dtype, interpret=served[1])


def lookup_rows(table: jax.Array, rows: jax.Array, embed_dim: int, dtype) -> jax.Array:
    """table[rows] in `dtype`, for a logical or a packed table, bit for bit.

    table  [V/P, P*D]; P is read off the shape (1: a logical table)
    rows   [...] int32 in [0, V)
    returns [..., D]

    The gather of the table's rows is XLA's `jnp.take` or, for a [V, 128]
    float32 table in a one-chip served entry on a TPU, the Pallas kernel
    that keeps row copies in flight (gather_choice has the rule and why;
    both give the same bits). The sharded caller
    (parallel/embedding_sharding.py, under shard_map) keeps XLA's gather, as
    every trace outside serving_gathers does.

    Packed, row r is lanes (r % P) * D ... + D of packed row r // P: one
    gather of whole lane rows (cast on the way out, so the [..., 128]
    intermediate is in `dtype`), a lane mask, and one [128, D] 0/1 matmul
    that folds the kept lanes onto D columns. Each output is one kept value
    plus zeros, so the sum is exact in any dtype (`highest`: float32 passes
    whole through the MXU). The variants measured against this one are in
    PERF.md (PR 25)."""
    p = table.shape[1] // embed_dim
    if p == 1:
        return _take_rows(table, rows, dtype)
    # P is a power of two (D divides 128): shift and mask, not divide.
    wide = _take_rows(table, rows >> (p.bit_length() - 1), dtype)
    lane = jnp.arange(LANES, dtype=rows.dtype)
    keep = lane // embed_dim == (rows & (p - 1))[..., None]
    fold = (lane[:, None] % embed_dim == jnp.arange(embed_dim, dtype=rows.dtype)).astype(dtype)
    return jnp.einsum(
        "...l,ld->...d",
        jnp.where(keep, wide, jnp.zeros((), dtype)),
        fold,
        preferred_element_type=dtype,
        precision="highest",
    )


def pool_bags(emb: jax.Array, wts: jax.Array, bag_sizes: tuple[int, ...]) -> jax.Array:
    """Weighted sum of each bag's rows.

    emb   [n, F, D], the rows as looked up, F = sum(bag_sizes)
    wts   [n, F] in emb's dtype
    returns [n, len(bag_sizes), D] in emb's dtype

    One [B, F] x [F, D] matmul a candidate row whose left operand holds bag
    b's weights in bag b's columns and zero elsewhere: the weighting rides the
    MXU with the sum, each product is exact in the float32 accumulator, and
    the result is rounded once (a bag of 100 rows summed in bfloat16 would
    lose a digit). Of the formulations timed on the chip THROUGH the upload's
    unpack (static slices, a [F, B] 0/1 matmul, a segment-sum, one gather a
    bag) this is the fastest at every bucket (PERF.md, PR 26)."""
    with jax.named_scope("pool"):
        bag_of = np.repeat(np.arange(len(bag_sizes)), bag_sizes)
        member = np.arange(len(bag_sizes))[:, None] == bag_of[None, :]  # [B, F]
        mix = wts[:, None, :] * jnp.asarray(member, emb.dtype)  # [n, B, F]
        return jnp.einsum(
            "nbf,nfd->nbd", mix, emb, preferred_element_type=jnp.float32
        ).astype(emb.dtype)


def field_embed(
    table: jax.Array,
    feat_ids: jax.Array,
    feat_wts: jax.Array,
    compute_dtype,
    embed_dim: int,
    bag_sizes: tuple[int, ...] = (),
) -> jax.Array:
    """Weighted per-field embedding lookup, pooled where a field is a bag.

    table     [V, D], or packed [V/P, P*D] (pack_table)
    feat_ids  [n, F] int
    feat_wts  [n, F] float
    bag_sizes ids per bag, the bags laid end to end over the F columns
              (ModelConfig.multi_hot_sizes); empty or all ones: one id a field
    returns   [n, F, D] in compute_dtype, or [n, len(bag_sizes), D] pooled
    """
    with jax.named_scope("embed"):
        vocab = table.shape[0] * (table.shape[1] // embed_dim)
        emb = lookup_rows(table, fold_ids(feat_ids, vocab), embed_dim, compute_dtype)
        if any(size != 1 for size in bag_sizes):
            return pool_bags(emb, feat_wts.astype(compute_dtype), bag_sizes)
        return emb * feat_wts[..., None].astype(compute_dtype)
