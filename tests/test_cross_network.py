"""DCN's cross network (models/dcn.py cross_apply, the XLA path every cell
serves) against a reference written here in numpy, at the shapes the fused
cross kernel's tests held that kernel to before ISSUE 62 took it out:

  v2 full:      x' = x0 * (x W + b) + x
  v1 rank-1:    x' = x0 * (x . w) + b + x
  v2 low-rank:  x' = x0 * ((x V) W + b) + x      (dlrm_dcnv2)

In bfloat16 the reference rounds where the path rounds: the operands of a
product, the [n, r] intermediate and each layer's output; sums are float32
there and float64 here."""

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from distributed_tf_serving_tpu.models.dcn import _cross_init, cross_apply

TOLERANCE = {"float32": 1e-4, "bfloat16": 2e-2}


def _rounded(a, dtype):
    """`a` as the path's operand holds it, widened to float64."""
    a = np.asarray(a, np.float32)
    return (a.astype(ml_dtypes.bfloat16) if dtype == "bfloat16" else a).astype(np.float64)


def _reference(layers, x0, dtype):
    x0 = x = _rounded(x0, dtype)
    for p in layers:
        b = np.asarray(p["b"], np.float64)
        w = _rounded(p["w"], dtype)
        if w.ndim == 1:  # the sum runs over float32 values, never rounded
            x = _rounded(x0 * (x @ np.asarray(p["w"], np.float64))[:, None] + b + x, dtype)
            continue
        h = _rounded(x @ _rounded(p["v"], dtype), dtype) if "v" in p else x
        x = _rounded(x0 * (h @ w + b) + x, dtype)
    return x


def _check(layers, n, d, dtype, seed):
    x0 = np.random.RandomState(seed).randn(n, d).astype(np.float32)
    # A bias that is not zero, so that its place in each form is held too.
    layers = [{**p, "b": p["b"] + 0.1 * (i + 1)} for i, p in enumerate(layers)]
    got = cross_apply(layers, jnp.asarray(x0, dtype), jnp.dtype(dtype))
    assert got.shape == (n, d) and got.dtype == jnp.dtype(dtype)
    np.testing.assert_allclose(
        np.asarray(got, np.float64), _reference(layers, x0, dtype), rtol=TOLERANCE[dtype], atol=TOLERANCE[dtype])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n, d, layers", [(32, 128, 3), (100, 688, 2), (7, 96, 1)])
def test_the_full_matrix_layers_match_numpy(n, d, layers, dtype):
    _check(_cross_init(jax.random.PRNGKey(n), layers, d, True, jnp.float32), n, d, dtype, seed=n)


def test_the_rank_one_layers_match_numpy():
    _check(_cross_init(jax.random.PRNGKey(1), 3, 96, False, jnp.float32), 13, 96, "float32", seed=1)


def test_the_low_rank_layers_match_numpy():
    _check(_cross_init(jax.random.PRNGKey(2), 2, 128, True, jnp.float32, low_rank=16), 13, 128, "bfloat16", seed=2)
