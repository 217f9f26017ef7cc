"""MLPerf DLRM-DCNv2 (dlrm-v2: torchrec DLRM_DCN over Criteo 1TB multi-hot),
the plain reference: float32 `jax.numpy`, no kernels, no batching, nothing
imported from the program.

  b      = relu MLP 13 -> 512 -> 256 -> 128 over the dense features    [n, D]
  p_f    = sum over the h_f ids of bag f of table[id mod V] * wt       [n, 26, D]
  x0     = concat(b, p_1 .. p_26)                                      [n, d], d = 27 D
  x_l+1  = x0 * ((x_l V_l) W_l + b_l) + x_l      V_l [d, r], W_l [r, d] (LowRankCrossNet)
  top    = relu MLP d -> 1024 -> 1024 -> 512 -> 256 over x_3
  score  = sigmoid(top w_out + b_out)

The bags lie end to end over the columns of `feat_ids` / `feat_wts` in field
order: columns 0-2 are bag 0, 3-4 bag 1, ... (`MULTI_HOT_SIZES`, 214 columns).

Departures from the source, all the program's and listed in config.json: one
hashed table for the 26 fields, a weight on every lookup (the source's bags
are unweighted sums), ReLU after the last top layer before the head. `params`
is the pytree the program's own `init` makes. Call under
`jax.default_matmul_precision("highest")`.
"""

import jax
import jax.numpy as jnp
import numpy as np

MULTI_HOT_SIZES = (3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100, 27, 10, 3, 1, 1)


def logits(params, batch, multi_hot_sizes=MULTI_HOT_SIZES):
    bot = batch["dense_features"].astype(jnp.float32)
    for layer in params["bottom_mlp"]:
        bot = jax.nn.relu(bot @ layer["w"] + layer["b"])
    table = params["embedding"].astype(jnp.float32)
    rows = jnp.remainder(batch["feat_ids"], table.shape[0])
    emb = table[rows] * batch["feat_wts"].astype(jnp.float32)[..., None]
    # Column f belongs to bag bag_of[f]; a 0/1 matrix sums each bag.
    bag_of = np.repeat(np.arange(len(multi_hot_sizes)), multi_hot_sizes)
    member = (bag_of[:, None] == np.arange(len(multi_hot_sizes))[None, :]).astype(np.float32)
    pooled = jnp.einsum("nfd,fb->nbd", emb, member)
    x0 = jnp.concatenate([bot, pooled.reshape(pooled.shape[0], -1)], axis=-1)
    x = x0
    for layer in params["cross"]:
        x = x0 * ((x @ layer["v"]) @ layer["w"] + layer["b"]) + x
    for layer in params["top_mlp"]:
        x = jax.nn.relu(x @ layer["w"] + layer["b"])
    return (x @ params["out"]["w"] + params["out"]["b"])[:, 0]


def forward(params, batch, multi_hot_sizes=MULTI_HOT_SIZES):
    return jax.nn.sigmoid(logits(params, batch, multi_hot_sizes))
