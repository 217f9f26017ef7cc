"""DCN-v2 (Wang et al. 2021), the plain reference: float32 `jax.numpy`, no
kernels, no batching, nothing imported from the program.

  e      = table[ids mod V] * wts[..., None]              [n, F, D]
  x0     = reshape(e, [n, F * D])
  x_l+1  = x0 * (x_l W_l + b_l) + x_l                     L full-matrix cross layers
  h      = relu(... relu(x0 W1 + b1) ...)                 the deep tower
  score  = sigmoid([x_L, h] w_out + b_out)

`params` is the pytree the program's own `init` makes (the parameters are the
program's; the arithmetic is not). Call under
`jax.default_matmul_precision("highest")`: on a TPU a float32 matmul
otherwise runs in bfloat16 passes.
"""

import jax
import jax.numpy as jnp


def forward(params, batch):
    table = params["embedding"].astype(jnp.float32)
    rows = jnp.remainder(batch["feat_ids"], table.shape[0])
    emb = table[rows] * batch["feat_wts"].astype(jnp.float32)[..., None]
    x0 = emb.reshape(emb.shape[0], -1)
    x = x0
    for layer in params["cross"]:
        x = x0 * (x @ layer["w"] + layer["b"]) + x
    h = x0
    for layer in params["mlp"]:
        h = jax.nn.relu(h @ layer["w"] + layer["b"])
    logit = jnp.concatenate([x, h], axis=-1) @ params["out"]["w"] + params["out"]["b"]
    return jax.nn.sigmoid(logit[:, 0])
