"""Vocab-sharded embedding lookups — the EP analog (SURVEY.md §2.4).

Two equivalent paths are provided:

1. The *annotation* path (executor.py): shard the table NamedSharding
   P("model", None), leave the model's jnp.take as-is, and let XLA's SPMD
   partitioner derive the masked-gather + psum. Idiomatic, zero model
   changes — this is what serving uses.

2. The *explicit* path here: shard_map over the mesh where each chip holds
   vocab/k contiguous rows, looks up only in-shard ids (clipped gather +
   mask), and psums partial embeddings over the model axis. This is the
   reference-visible semantics made manual — the scatter the Java client did
   per host (DCNClient.java:146-159) happens on-mesh — and it pins down the
   contract the annotation path must match (test_parallel.py asserts
   equality), while being the hook point for a Pallas lookup kernel.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..models.embeddings import lookup_rows
from .mesh import DATA_AXIS, MODEL_AXIS


# ---------------------------------------------------------------------------
# Named partition rules (the serving-mode layout contract, ISSUE 13).
#
# The generic layout walker (sharding.param_shardings) infers "vocab table"
# from path-name heuristics; the serving mode wants the layout to be an
# explicit, reviewable CONTRACT per model family — the match_partition_rules
# idiom (SNIPPETS.md): ordered (regex, PartitionSpec) pairs matched against
# the "/"-joined param path, first match wins. A rule that would place a
# mesh axis on a missing dim (spec rank > leaf rank) is a config error; an
# unmatched leaf returns None so the caller can fall back to the generic
# dense policy (replicated, or tensor-parallel splits) — the rules pin the
# memory-heavy EP decisions, the generic walker keeps handling the long
# tail of small dense params identically on both paths.


def tree_path_str(path) -> str:
    """jax key-path -> "/"-joined name ("cross/0/w") for rule matching."""
    parts = []
    for p in path:
        key = getattr(p, "key", None)
        if key is None:
            key = getattr(p, "idx", None)
        parts.append(str(key) if key is not None else str(p))
    return "/".join(parts)


# Per-family rules. Only the vocab-major tables are pinned here: they are
# the DLRM-scale memory (the 300M-qps paper's CTR models are embedding-
# dominated) and the one layout decision that MUST NOT silently change
# with a param rename. Dense MLP/cross weights fall through (None) to the
# generic policy so tensor_parallel keeps working identically.
MODEL_PARTITION_RULES: dict[str, tuple[tuple[str, P], ...]] = {
    "dcn": (("^embedding$", P(MODEL_AXIS, None)),),
    "dcn_v2": (("^embedding$", P(MODEL_AXIS, None)),),
    "dlrm": (("^embedding$", P(MODEL_AXIS, None)),),
    "dlrm_dcnv2": (("^embedding$", P(MODEL_AXIS, None)),),
    "two_tower": (
        ("^embedding$", P(MODEL_AXIS, None)),
        ("^temperature$", P()),  # scalar: explicit, never sharded
    ),
    "wide_deep": (
        ("^embedding$", P(MODEL_AXIS, None)),
        ("^wide$", P(MODEL_AXIS)),  # per-vocab-row scalar table (EP too)
        ("^wide_bias$", P()),
    ),
    "deepfm": (
        ("^embedding$", P(MODEL_AXIS, None)),
        ("^linear$", P(MODEL_AXIS)),
    ),
    "generic_mlp": (("^embedding$", P(MODEL_AXIS, None)),),
}


def partition_rules_for(model_kind: str) -> tuple[tuple[str, P], ...] | None:
    """The family's ordered (regex, PartitionSpec) rules, or None for an
    unknown/imported family (graph executors, custom servables) — callers
    then use the generic path-name layout unchanged."""
    return MODEL_PARTITION_RULES.get(model_kind)


def rule_matcher(rules, strict: bool = False):
    """(path, leaf) -> PartitionSpec-or-None resolver for an ordered rule
    list — the per-leaf core match_partition_rules and the generic layout
    walker (sharding.param_shardings) share.

    Scalars are never partitioned (the SNIPPETS idiom). A matched spec
    whose rank exceeds the leaf's is a layout bug — the table the rule
    was written for changed shape — and raises rather than silently
    serving a wrong layout. Unmatched leaves yield None (generic-policy
    fallback); strict=True turns them into errors for tests that want
    the rule set proven exhaustive."""
    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def resolve(path, leaf):
        shape = getattr(leaf, "shape", ())
        if len(shape) == 0 or all(d == 1 for d in shape):
            return P()  # scalars/degenerate leaves are never partitioned
        name = tree_path_str(path)
        for pat, spec in compiled:
            if pat.search(name) is not None:
                if len(spec) > len(shape):
                    raise ValueError(
                        f"partition rule {pat.pattern!r} places "
                        f"{len(spec)} dims but param {name!r} has shape "
                        f"{shape} — the rule no longer matches the model"
                    )
                return spec
        if strict:
            raise ValueError(f"no partition rule matched param {name!r}")
        return None

    return resolve


def match_partition_rules(rules, params, strict: bool = False):
    """PartitionSpec-or-None tree for `params` per the ordered rules (see
    rule_matcher for the matching semantics)."""
    return jax.tree_util.tree_map_with_path(rule_matcher(rules, strict), params)


def sharded_field_embed(
    table: jax.Array,
    feat_ids: jax.Array,
    feat_wts: jax.Array,
    mesh: Mesh,
    compute_dtype,
    embed_dim: int,
) -> jax.Array:
    """Weighted field lookup with the table sharded over the model axis and
    candidates sharded over the data axis.

    table     [V, D], or lane-packed [V/P, P*D] (models/embeddings.py
              pack_table: still vocab-major, a shard still owns a contiguous
              range of logical rows); rows divisible by the model-axis size
    feat_ids  [n, F] int32, already folded into [0, V)
    feat_wts  [n, F] float
    returns   [n, F, D] in compute_dtype, candidate-sharded
    """
    k = mesh.shape[MODEL_AXIS]
    if table.shape[0] % k != 0:
        raise ValueError(
            f"table rows {table.shape[0]} not divisible by model-axis size {k}"
        )
    pack = table.shape[1] // embed_dim

    def local(table_shard, ids_blk, wts_blk):
        # table_shard: this chip's contiguous vocab rows, [V/k, D] logical.
        vshard = table_shard.shape[0] * pack
        lo = jax.lax.axis_index(MODEL_AXIS) * vshard
        local_ids = ids_blk - lo
        in_shard = (local_ids >= 0) & (local_ids < vshard)
        # Clipped gather stays in-bounds; the mask zeroes out-of-shard rows,
        # so the psum over the model axis reassembles exact lookups.
        emb = lookup_rows(
            table_shard, jnp.clip(local_ids, 0, vshard - 1), embed_dim, compute_dtype
        )
        emb = jnp.where(in_shard[..., None], emb, jnp.zeros((), emb.dtype))
        emb = jax.lax.psum(emb, MODEL_AXIS)
        return emb * wts_blk[..., None].astype(compute_dtype)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(MODEL_AXIS, None), P(DATA_AXIS, None), P(DATA_AXIS, None)),
        out_specs=P(DATA_AXIS, None, None),
    )(table, feat_ids, feat_wts)
