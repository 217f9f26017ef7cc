"""Two-tower retrieval model (BASELINE.json config: "Two-tower retrieval
(user/item embed), 10k candidate scoring").

Fields split positionally: the first `num_user_fields` are the user/context
tower's, the rest are the item tower's. Each tower is an MLP over its
weighted embedding bag producing an L2-normalized embedding; the score is the
scaled dot product. The serving contract stays feat_ids/feat_wts [n, F] →
prediction_node [n]: for candidate scoring the caller replicates the user
fields into each candidate row, which keeps the request shape identical to
the reference's DCN workload and lets candidate sharding apply unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import Model, ModelConfig, mlp_apply, mlp_init, register_model
from .embeddings import embedding_init, field_embed


@register_model("two_tower")
def build_two_tower(config: ModelConfig) -> Model:
    nu = config.num_user_fields
    ni = config.num_fields - nu
    if ni <= 0:
        raise ValueError(f"num_user_fields={nu} must be < num_fields={config.num_fields}")
    du, di = nu * config.embed_dim, ni * config.embed_dim

    def init(rng, packed: bool = False):
        k_emb, k_user, k_item = jax.random.split(rng, 3)
        return {
            "embedding": embedding_init(
                k_emb, config.vocab_size, config.embed_dim, config.pdtype, packed
            ),
            "user_mlp": mlp_init(k_user, du, config.mlp_dims, config.pdtype),
            "item_mlp": mlp_init(k_item, di, config.mlp_dims, config.pdtype),
            "temperature": jnp.asarray(10.0, config.pdtype),
        }

    def _tower(layers, emb, cd):
        x = mlp_apply(layers, emb.reshape(emb.shape[0], -1), cd, final_relu=False)
        x = x.astype(jnp.float32)
        return x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-12)

    def apply(params, batch):
        cd = config.cdtype
        emb = field_embed(
            params["embedding"], batch["feat_ids"], batch["feat_wts"], cd, config.embed_dim
        )
        u = _tower(params["user_mlp"], emb[:, :nu], cd)
        v = _tower(params["item_mlp"], emb[:, nu:], cd)
        score = jnp.sum(u * v, axis=-1) * params["temperature"].astype(jnp.float32)
        return {"prediction_node": jax.nn.sigmoid(score), "logits": score}

    return Model(config=config, init=init, apply=apply)
