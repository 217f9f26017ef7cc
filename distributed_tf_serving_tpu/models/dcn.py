"""DCN / DCN-v2 — the reference's flagship model family.

The reference serves an externally-exported "DCN" SavedModel with signature
"serving_default" over inputs feat_ids/feat_wts [n,43] and output
prediction_node [n] (DCNClient.java:33-35,98-108,162). This is the in-tree
TPU-native equivalent: explicit cross network + deep MLP over a shared
embedding bag.

Cross layers (per Wang et al.):
  v1 (rank-1):     x_{l+1} = x0 * (x_l . w_l) + b_l + x_l       w_l: [d]
  v2 (full-rank):  x_{l+1} = x0 * (x_l @ W_l + b_l) + x_l       W_l: [d, d]
  v2 (low-rank):   x_{l+1} = x0 * ((x_l @ V_l) @ W_l + b_l) + x_l  V_l: [d, r], W_l: [r, d]
                   (torchrec LowRankCrossNet; the dlrm_dcnv2 family, models/dlrm.py)

The v2 matmul is the MXU hot op; it runs in compute_dtype (bf16 default) with
f32 accumulation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import Model, ModelConfig, dense_apply, dense_init, mlp_apply, mlp_init, register_model
from .embeddings import embedding_init, field_embed


def _cross_init(rng, num_layers: int, d: int, full_matrix: bool, dtype, low_rank: int = 0):
    layers = []
    for _ in range(num_layers):
        rng, sub = jax.random.split(rng)
        if low_rank:
            kv, kw = jax.random.split(sub)
            layers.append({
                "v": jax.random.normal(kv, (d, low_rank), dtype) / jnp.asarray(d**0.5, dtype),
                "w": jax.random.normal(kw, (low_rank, d), dtype) / jnp.asarray(low_rank**0.5, dtype),
                "b": jnp.zeros((d,), dtype),
            })
            continue
        if full_matrix:
            w = jax.random.normal(sub, (d, d), dtype) / jnp.asarray(d**0.5, dtype)
        else:
            w = jax.random.normal(sub, (d,), dtype) / jnp.asarray(d**0.5, dtype)
        layers.append({"w": w, "b": jnp.zeros((d,), dtype)})
    return layers


def cross_apply(layers, x0: jax.Array, compute_dtype) -> jax.Array:
    """Apply the stack of cross layers; x0 is [n, d] in compute_dtype.
    Accepts the {"w","b"} layers and the low-rank {"v","w","b"} form
    (xw = (x @ v) @ w, the [n, r] intermediate rounded to compute_dtype for
    the second matmul)."""
    x = x0
    for p in layers:
        b = p["b"].astype(jnp.float32)
        h = x
        if "v" in p:  # low-rank: down to [n, r] first
            h = jax.lax.dot_general(
                x, p["v"].astype(compute_dtype),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            ).astype(compute_dtype)
        w = p["w"].astype(compute_dtype)
        if w.ndim == 2:  # DCN-v2
            xw = jax.lax.dot_general(
                h, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            x = (x0.astype(jnp.float32) * (xw + b) + x.astype(jnp.float32)).astype(compute_dtype)
        else:  # DCN-v1
            xw = jnp.sum(x.astype(jnp.float32) * w.astype(jnp.float32), axis=-1, keepdims=True)
            x = (x0.astype(jnp.float32) * xw + b + x.astype(jnp.float32)).astype(compute_dtype)
    return x


def _build(config: ModelConfig) -> Model:
    d = config.num_fields * config.embed_dim

    def init(rng, packed: bool = False):
        k_emb, k_cross, k_mlp, k_out = jax.random.split(rng, 4)
        mlp = mlp_init(k_mlp, d, config.mlp_dims, config.pdtype)
        out_in = d + (config.mlp_dims[-1] if config.mlp_dims else 0)
        return {
            "embedding": embedding_init(
                k_emb, config.vocab_size, config.embed_dim, config.pdtype, packed
            ),
            "cross": _cross_init(
                k_cross, config.num_cross_layers, d, config.cross_full_matrix, config.pdtype
            ),
            "mlp": mlp,
            "out": dense_init(k_out, out_in, 1, config.pdtype),
        }

    def apply(params, batch):
        cd = config.cdtype
        emb = field_embed(
            params["embedding"], batch["feat_ids"], batch["feat_wts"], cd, config.embed_dim
        )
        x0 = emb.reshape(emb.shape[0], d)  # [n, F*D]
        with jax.named_scope("cross"):
            xc = cross_apply(params["cross"], x0, cd)
        xd = mlp_apply(params["mlp"], x0, cd)
        h = jnp.concatenate([xc.astype(jnp.float32), xd.astype(jnp.float32)], axis=-1)
        logit = dense_apply(params["out"], h, cd)[:, 0]
        return {"prediction_node": jax.nn.sigmoid(logit), "logits": logit}

    return Model(config=config, init=init, apply=apply)


@register_model("dcn")
def build_dcn(config: ModelConfig) -> Model:
    import dataclasses

    return _build(dataclasses.replace(config, cross_full_matrix=False))


@register_model("dcn_v2")
def build_dcn_v2(config: ModelConfig) -> Model:
    import dataclasses

    return _build(dataclasses.replace(config, cross_full_matrix=True))
