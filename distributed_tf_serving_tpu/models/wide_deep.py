"""Wide&Deep CTR model (BASELINE.json config: "Wide&Deep CTR SavedModel").

Wide half: a per-id scalar weight table (a [V,1] embedding) summed over
fields with feature weights — the classic sparse-linear memorization path.
Deep half: MLP over the shared embedding bag. Serving contract identical to
DCN (feat_ids/feat_wts -> prediction_node).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import Model, ModelConfig, dense_apply, dense_init, mlp_apply, mlp_init, register_model
from .embeddings import embedding_init, field_embed, sparse_linear


@register_model("wide_deep")
def build_wide_deep(config: ModelConfig) -> Model:
    d = config.num_fields * config.embed_dim

    def init(rng, packed: bool = False):
        k_wide, k_emb, k_mlp, k_out = jax.random.split(rng, 4)
        return {
            "wide": jax.random.normal(k_wide, (config.vocab_size,), config.pdtype) * 0.01,
            "wide_bias": jnp.zeros((), config.pdtype),
            "embedding": embedding_init(
                k_emb, config.vocab_size, config.embed_dim, config.pdtype, packed
            ),
            "mlp": mlp_init(k_mlp, d, config.mlp_dims, config.pdtype),
            "out": dense_init(k_out, config.mlp_dims[-1], 1, config.pdtype),
        }

    def apply(params, batch):
        cd = config.cdtype
        ids, wts = batch["feat_ids"], batch["feat_wts"]
        # Wide: sum of per-id scalar weights, feature-weighted (f32).
        wide = sparse_linear(params["wide"], ids, wts) + params["wide_bias"].astype(jnp.float32)
        # Deep: MLP over flattened weighted embeddings.
        emb = field_embed(params["embedding"], ids, wts, cd, config.embed_dim)
        xd = mlp_apply(params["mlp"], emb.reshape(emb.shape[0], d), cd)
        logit = dense_apply(params["out"], xd, cd)[:, 0] + wide
        return {"prediction_node": jax.nn.sigmoid(logit), "logits": logit}

    # The wide half consumes raw f32 weights -> bf16 weight-transfer
    # compression would change scores; opt out.
    return Model(config=config, init=init, apply=apply, wts_in_compute_dtype=False)
