"""DeepFM CTR model (BASELINE.json config: "DeepFM CTR (Criteo-1TB features)").

First-order term: per-id scalar weights (shared with the Wide&Deep wide
half). Second-order FM term over the embedding bag uses the
O(n·F·D) identity  0.5 * ((sum_f e_f)^2 - sum_f e_f^2), which avoids the
O(F^2) pairwise products — on TPU this is two reductions over the [n,F,D]
bag, fused by XLA into the lookup. Deep half: MLP over the same bag.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import Model, ModelConfig, dense_apply, dense_init, mlp_apply, mlp_init, register_model
from .embeddings import embedding_init, field_embed, sparse_linear


def fm_second_order(emb: jax.Array) -> jax.Array:
    """emb [n, F, D] -> scalar FM interaction [n] (f32)."""
    e = emb.astype(jnp.float32)
    sum_sq = jnp.square(jnp.sum(e, axis=1))  # [n, D]
    sq_sum = jnp.sum(jnp.square(e), axis=1)  # [n, D]
    return 0.5 * jnp.sum(sum_sq - sq_sum, axis=-1)


@register_model("deepfm")
def build_deepfm(config: ModelConfig) -> Model:
    d = config.num_fields * config.embed_dim

    def init(rng, packed: bool = False):
        k_lin, k_emb, k_mlp, k_out = jax.random.split(rng, 4)
        return {
            "linear": jax.random.normal(k_lin, (config.vocab_size,), config.pdtype) * 0.01,
            "bias": jnp.zeros((), config.pdtype),
            "embedding": embedding_init(
                k_emb, config.vocab_size, config.embed_dim, config.pdtype, packed
            ),
            "mlp": mlp_init(k_mlp, d, config.mlp_dims, config.pdtype),
            "out": dense_init(k_out, config.mlp_dims[-1], 1, config.pdtype),
        }

    def apply(params, batch):
        cd = config.cdtype
        ids, wts = batch["feat_ids"], batch["feat_wts"]
        first = sparse_linear(params["linear"], ids, wts)
        emb = field_embed(params["embedding"], ids, wts, cd, config.embed_dim)
        second = fm_second_order(emb)
        deep = dense_apply(params["out"], mlp_apply(params["mlp"], emb.reshape(emb.shape[0], d), cd), cd)[:, 0]
        logit = first + second + deep + params["bias"].astype(jnp.float32)
        return {"prediction_node": jax.nn.sigmoid(logit), "logits": logit}

    # First-order term consumes raw f32 weights -> opt out of bf16
    # weight-transfer compression.
    return Model(config=config, init=init, apply=apply, wts_in_compute_dtype=False)
