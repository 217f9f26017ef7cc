"""Generic embed+MLP family — the import-boundary fallback.

The zoo covers the six CTR families the reference ecosystem actually ships
(SURVEY.md §7 endorses zoo-forward serving; the reference itself executes
arbitrary GraphDefs inside tensorflow_model_server, meta_graph.proto:31-87 /
graph.proto:14 upstream — a capability this framework deliberately scopes
to weight import onto native forwards). This family is the documented
best-effort boundary for exports whose architecture is NOT in the zoo
(VERDICT r2 item 7): any model that is structurally "embedding bag ->
dense chain -> logit" — the dominant shape of real-world CTR DNN exports —
serves through this forward, with the architecture dims inferred from the
export's own variable shapes (interop/savedmodel.py
infer_generic_architecture). Anything else gets an actionable rejection
naming the supported families.

Forward (the plain DNN classifier):
  x0    = flatten(field_embed(ids, wts))      [n, F*D]
  h     = relu MLP over mlp_dims              [n, mlp_dims[-1]]
  logit = dense(h)                            [n]
  prediction_node = sigmoid(logit)

Same serving contract as every zoo family (feat_ids/feat_wts ->
prediction_node, DCNClient.java:33-35,98-108,162); same TPU numerics
(bf16 MXU compute, f32 accumulation via mlp_apply/dense_apply).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import Model, ModelConfig, dense_apply, dense_init, mlp_apply, mlp_init, register_model
from .embeddings import embedding_init, field_embed


@register_model("generic")
def build_generic(config: ModelConfig) -> Model:
    d = config.num_fields * config.embed_dim

    def init(rng, packed: bool = False):
        k_emb, k_mlp, k_out = jax.random.split(rng, 3)
        return {
            "embedding": embedding_init(
                k_emb, config.vocab_size, config.embed_dim, config.pdtype, packed
            ),
            "mlp": mlp_init(k_mlp, d, config.mlp_dims, config.pdtype),
            "out": dense_init(
                k_out, config.mlp_dims[-1] if config.mlp_dims else d, 1, config.pdtype
            ),
        }

    def apply(params, batch):
        cd = config.cdtype
        emb = field_embed(
            params["embedding"], batch["feat_ids"], batch["feat_wts"], cd, config.embed_dim
        )
        x0 = emb.reshape(emb.shape[0], d)
        h = mlp_apply(params["mlp"], x0, cd) if config.mlp_dims else x0
        logit = dense_apply(params["out"], h, cd)[:, 0]
        return {"prediction_node": jax.nn.sigmoid(logit), "logits": logit}

    return Model(config=config, init=init, apply=apply)
