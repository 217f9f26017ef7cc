"""Embedding tables and weighted field lookups.

The reference's models (external SavedModels) consume hashed categorical ids
with per-feature weights (feat_ids/feat_wts, DCNClient.java:98-108). Here the
embedding bag is explicit: a single [vocab, dim] table, ids folded into the
vocab by modulo, gathered with jnp.take, and scaled by the feature weight.

TPU notes: the gather lowers to a dynamic-gather XLA op that is
HBM-bandwidth-bound; ids arrive [n, F] and the gather is batched over both
axes at once (one gather of n*F rows) so XLA can tile it. The vocab axis is
the sharding axis for the EP analog (SURVEY.md §2.4): under shard_map each
chip owns vocab/num_chips rows and out-of-shard ids contribute zero, summed
back with psum — see parallel/embedding_sharding.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def embedding_init(rng: jax.Array, vocab_size: int, embed_dim: int, dtype) -> jax.Array:
    # 1/sqrt(dim) scale keeps dot-product magnitudes O(1) for FM/two-tower.
    return jax.random.normal(rng, (vocab_size, embed_dim), dtype) / jnp.asarray(
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


def field_embed(
    table: jax.Array,
    feat_ids: jax.Array,
    feat_wts: jax.Array,
    compute_dtype,
) -> jax.Array:
    """Weighted per-field embedding lookup.

    table     [V, D]
    feat_ids  [n, F] int
    feat_wts  [n, F] float
    returns   [n, F, D] in compute_dtype
    """
    with jax.named_scope("embed"):
        rows = fold_ids(feat_ids, table.shape[0])
        emb = jnp.take(table, rows, axis=0)  # [n, F, D]
        return emb.astype(compute_dtype) * feat_wts[..., None].astype(compute_dtype)
