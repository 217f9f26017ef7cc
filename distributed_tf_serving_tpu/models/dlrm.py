"""DLRM and DLRM-DCNv2 (BASELINE.json config: "DLRM (embedding-bag heavy),
v5e-8 ICI shard, 4k batch").

`dlrm`: bottom MLP over dense features, ONE weighted embedding row a field
(one id a field: no bag; the bag family is `dlrm_dcnv2`), pairwise
dot-product feature interactions (the DLRM signature op), top MLP over
[bottom output ++ upper-triangle interactions]. The interaction matmul Z Z^T
is the MXU op; it runs in compute_dtype with f32 accumulation.

`dlrm_dcnv2` (MLPerf dlrm-v2, torchrec DLRM_DCN): the same bottom MLP, each
sparse field an embedding BAG of multi_hot_sizes[f] ids pooled by a weighted
sum (embeddings.pool_bags), a low-rank cross network (dcn.cross_apply) over
concat(bottom output, pooled bags) in place of the dot interaction, top MLP
over the crossed vector. On the wire the bags lie end to end in field order:
feat_ids / feat_wts are [n, sum(multi_hot_sizes)], columns 0..h_0-1 bag 0,
the next h_1 bag 1, ... and num_fields is that column count.

Serving contract: both accept the standard feat_ids/feat_wts [n, F] pair plus
an optional `dense_features` float [n, num_dense] input; when absent, dense
features default to zeros so the reference's two-input request shape still
serves.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import Model, ModelConfig, dense_apply, dense_init, mlp_apply, mlp_init, register_model
from .dcn import _cross_init, cross_apply
from .embeddings import embedding_init, field_embed


def _check_bottom_width(config: ModelConfig) -> None:
    # The bottom MLP output joins the sparse vectors as one more "field";
    # force its width to the embedding dim like upstream DLRM.
    if config.bottom_mlp_dims[-1] != config.embed_dim:
        raise ValueError(
            f"bottom_mlp_dims[-1] ({config.bottom_mlp_dims[-1]}) must equal "
            f"embed_dim ({config.embed_dim})"
        )


def _bottom(params, batch, config: ModelConfig) -> jax.Array:
    """The bottom MLP over the dense features (zeros when absent): [n, D]."""
    dense = batch.get("dense_features")
    if dense is None:
        dense = jnp.zeros((batch["feat_ids"].shape[0], config.num_dense_features), jnp.float32)
    return mlp_apply(params["bottom_mlp"], dense, config.cdtype)


@register_model("dlrm")
def build_dlrm(config: ModelConfig) -> Model:
    D = config.embed_dim
    F = config.num_fields
    _check_bottom_width(config)
    num_feat = F + 1  # sparse fields + bottom-MLP dense "field"
    num_pairs = num_feat * (num_feat - 1) // 2
    top_in = D + num_pairs

    def init(rng, packed: bool = False):
        k_emb, k_bot, k_top, k_out = jax.random.split(rng, 4)
        return {
            "embedding": embedding_init(k_emb, config.vocab_size, D, config.pdtype, packed),
            "bottom_mlp": mlp_init(k_bot, config.num_dense_features, config.bottom_mlp_dims, config.pdtype),
            "top_mlp": mlp_init(k_top, top_in, config.mlp_dims, config.pdtype),
            "out": dense_init(k_out, config.mlp_dims[-1], 1, config.pdtype),
        }

    def apply(params, batch):
        cd = config.cdtype
        bot = _bottom(params, batch, config)  # [n, D]
        emb = field_embed(
            params["embedding"], batch["feat_ids"], batch["feat_wts"], cd, config.embed_dim
        )
        with jax.named_scope("interact"):
            z = jnp.concatenate([bot[:, None, :].astype(cd), emb], axis=1)  # [n, F+1, D]
            # Pairwise dot interactions: upper triangle of Z Z^T (excl. diagonal).
            zzt = jax.lax.dot_general(
                z, z, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
            )  # [n, F+1, F+1]
            iu, ju = jnp.triu_indices(num_feat, k=1)
            inter = zzt[:, iu, ju]  # [n, num_pairs]
        top = jnp.concatenate([bot.astype(jnp.float32), inter], axis=-1)
        logit = dense_apply(params["out"], mlp_apply(params["top_mlp"], top, cd), cd)[:, 0]
        return {"prediction_node": jax.nn.sigmoid(logit), "logits": logit}

    return Model(config=config, init=init, apply=apply, takes_dense=True)


@register_model("dlrm_dcnv2")
def build_dlrm_dcnv2(config: ModelConfig) -> Model:
    D = config.embed_dim
    bags = config.multi_hot_sizes or (1,) * config.num_fields
    if sum(bags) != config.num_fields or min(bags) < 1:
        raise ValueError(
            f"multi_hot_sizes {bags} must be positive and sum to num_fields "
            f"({config.num_fields}), the wire's column count"
        )
    _check_bottom_width(config)
    d = (len(bags) + 1) * D  # bottom output ++ one pooled vector a bag

    def init(rng, packed: bool = False):
        k_emb, k_bot, k_cross, k_top, k_out = jax.random.split(rng, 5)
        return {
            "embedding": embedding_init(k_emb, config.vocab_size, D, config.pdtype, packed),
            "bottom_mlp": mlp_init(k_bot, config.num_dense_features, config.bottom_mlp_dims, config.pdtype),
            "cross": _cross_init(
                k_cross, config.num_cross_layers, d, True, config.pdtype, config.cross_low_rank
            ),
            "top_mlp": mlp_init(k_top, d, config.mlp_dims, config.pdtype),
            "out": dense_init(k_out, config.mlp_dims[-1], 1, config.pdtype),
        }

    def apply(params, batch):
        cd = config.cdtype
        bot = _bottom(params, batch, config)  # [n, D]
        pooled = field_embed(
            params["embedding"], batch["feat_ids"], batch["feat_wts"], cd, D, bags
        )  # [n, len(bags), D]
        x0 = jnp.concatenate([bot.astype(cd), pooled.reshape(pooled.shape[0], -1)], axis=-1)
        with jax.named_scope("cross"):
            xc = cross_apply(params["cross"], x0, cd)
        logit = dense_apply(params["out"], mlp_apply(params["top_mlp"], xc, cd), cd)[:, 0]
        return {"prediction_node": jax.nn.sigmoid(logit), "logits": logit}

    return Model(config=config, init=init, apply=apply, takes_dense=True)
