"""DLRM (BASELINE.json config: "DLRM (embedding-bag heavy), v5e-8 ICI shard,
4k batch").

Bottom MLP over dense features, per-field sparse embedding bag, pairwise
dot-product feature interactions (the DLRM signature op), top MLP over
[bottom output ++ upper-triangle interactions].

Serving contract: accepts the standard feat_ids/feat_wts [n, F] pair plus an
optional `dense_features` float [n, num_dense] input; when absent, dense
features default to zeros so the reference's two-input request shape still
serves. The interaction matmul Z Z^T is the MXU op; it runs in compute_dtype
with f32 accumulation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import Model, ModelConfig, dense_apply, dense_init, mlp_apply, mlp_init, register_model
from .embeddings import embedding_init, field_embed


@register_model("dlrm")
def build_dlrm(config: ModelConfig) -> Model:
    D = config.embed_dim
    F = config.num_fields
    if config.bottom_mlp_dims[-1] != D:
        # The bottom MLP output joins the interaction as one more "field";
        # force its width to the embedding dim like upstream DLRM.
        raise ValueError(
            f"bottom_mlp_dims[-1] ({config.bottom_mlp_dims[-1]}) must equal embed_dim ({D})"
        )
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
        n = batch["feat_ids"].shape[0]
        dense = batch.get("dense_features")
        if dense is None:
            dense = jnp.zeros((n, config.num_dense_features), jnp.float32)
        bot = mlp_apply(params["bottom_mlp"], dense, cd)  # [n, D]
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

    return Model(config=config, init=init, apply=apply)
