"""DLRM (Naumov et al. 2019) as MLPerf Inference runs it, the plain reference:
float32 `jax.numpy`, no kernels, no batching, nothing imported from the
program.

  b      = relu MLP over the 13 dense features             [n, D]
  e      = table[ids mod V] * wts[..., None]               [n, F, D]
  z      = [b, e_1 .. e_F]                                 F + 1 vectors
  inter  = z_i . z_j for i < j                             (F + 1) F / 2 pairs
  top    = relu MLP over [b, inter]
  score  = sigmoid(top w_out + b_out)

Departures from the source, all the program's and listed in config.json: one
hashed table for the 26 fields, a weight on every lookup (the source's bags
have weight 1), ReLU after the last top layer before the head. `params` is the
pytree the program's own `init` makes. Call under
`jax.default_matmul_precision("highest")`.
"""

import jax
import jax.numpy as jnp
import numpy as np


def forward(params, batch):
    bot = batch["dense_features"].astype(jnp.float32)
    for layer in params["bottom_mlp"]:
        bot = jax.nn.relu(bot @ layer["w"] + layer["b"])
    table = params["embedding"].astype(jnp.float32)
    rows = jnp.remainder(batch["feat_ids"], table.shape[0])
    emb = table[rows] * batch["feat_wts"].astype(jnp.float32)[..., None]
    z = jnp.concatenate([bot[:, None, :], emb], axis=1)
    zzt = jnp.einsum("nid,njd->nij", z, z)
    i, j = np.triu_indices(z.shape[1], k=1)
    top = jnp.concatenate([bot, zzt[:, i, j]], axis=-1)
    for layer in params["top_mlp"]:
        top = jax.nn.relu(top @ layer["w"] + layer["b"])
    logit = top @ params["out"]["w"] + params["out"]["b"]
    return jax.nn.sigmoid(logit[:, 0])
