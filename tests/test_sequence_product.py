"""`models/sequence.py::product`: a product of pieces is ONE product wherever a
form exists that copies no large array and the chip runs it no slower, the
same pairs of pieces as a product a pair, and the three families' steps hold
the fewer products for it."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import build_model, sequence
from distributed_tf_serving_tpu.utils.config import load_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CD = jnp.bfloat16

# Every spec the three families pass, at shapes of the same order as theirs:
# (spec, x's shape, y's shape, what y is, the form the product takes).
# A `weight` is whole in the compute dtype; `scores` have the result the
# largest array (q k'); `values` have the first operand the largest (p v).
CALLS = [
    ("...k,kn->...n", (2, 5, 16), (16, 24), "weight"),
    ("nqgjcd,nkgcd->ngjcqk", (2, 8, 2, 2, 2, 8), (2, 12, 2, 2, 8), "scores"),  # phi4flash
    ("ngjcqk,nkge->nqgjce", (2, 2, 2, 2, 8, 32), (2, 32, 2, 16), "values"),
    ("nqhd,nkhd->nhqk", (2, 8, 3, 8), (2, 12, 3, 8), "scores"),  # pangu_moe
    ("nqhd,nkd->nhqk", (2, 8, 3, 4), (2, 12, 4), "scores"),
    ("nhqk,nkhd->nqhd", (2, 3, 8, 32), (2, 32, 3, 8), "values"),
    ("nbqgjd,nbkgd->nbgjqk", (2, 3, 4, 2, 2, 8), (2, 3, 8, 2, 8), "scores"),  # exaone_moe
    ("nbgjqk,nbkgd->nbqgjd", (2, 3, 2, 2, 4, 32), (2, 3, 32, 2, 4), "values"),
    ("nqgjd,nkgd->ngjqk", (2, 8, 2, 2, 8), (2, 12, 2, 8), "scores"),
    ("ngjqk,nkgd->nqgjd", (2, 2, 2, 8, 32), (2, 32, 2, 8), "values"),
]
SPECS = sorted({spec for spec, *_ in CALLS})
COUNTS = (1, 2, 3)
# dot_general in the lowered 4-row step at the small TOMLs' sizes: PR 43's
# tree (one product a pair of pieces, but for the routed families' weights),
# and this one.
PARENTS_PRODUCTS = {"phi4flash_small": 118, "pangu_moe_small": 104, "exaone_moe_small": 147}
PRODUCTS = {"phi4flash_small": 112, "pangu_moe_small": 78, "exaone_moe_small": 109}


def operands(x_shape, y_shape, kind, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(x_shape), jnp.float32)
    y = jnp.asarray(rng.standard_normal(y_shape), CD if kind == "weight" else jnp.float32)
    return x, y


def kept_pairs(xs, ys):
    kept = max(len(xs), len(ys))
    return [(a, b) for i, a in enumerate(xs) for j, b in enumerate(ys) if i + j < kept]


def in_float64(spec, xs, ys):
    """einsum over the kept pairs of pieces, each pair exact, summed in float64."""
    return sum(np.einsum(spec, np.asarray(a, np.float64), np.asarray(b, np.float64)) for a, b in kept_pairs(xs, ys))


def equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub)


def lowered_step(name):
    cfgs = load_config(os.path.join(ROOT, "configs", name + ".toml"))
    config = cfgs["model"]
    model = build_model(cfgs["server"].model_kind, config)
    batch = {"feat_ids": jax.ShapeDtypeStruct((4, config.num_fields), jnp.int32),
             "feat_wts": jax.ShapeDtypeStruct((4, config.num_fields), jnp.float32)}
    return jax.jit(model.apply).lower(jax.eval_shape(model.init, jax.random.PRNGKey(0)), batch)


@pytest.mark.parametrize("count", COUNTS)
@pytest.mark.parametrize("spec,x_shape,y_shape,kind", CALLS, ids=[c[0] for c in CALLS])
def test_product_is_the_sum_over_the_kept_pairs_of_pieces(spec, x_shape, y_shape, kind, count):
    x, y = operands(x_shape, y_shape, kind)
    got = np.asarray(sequence.product(spec, x, y, CD, count))
    assert got.dtype == np.float32
    want = in_float64(spec, sequence.pieces(x, CD, count), sequence.pieces(y, CD, count))
    rounding = 1e-5 * np.abs(want).max()  # float32 sums of a few hundred terms
    np.testing.assert_allclose(got, want, rtol=0, atol=rounding)
    one_piece = in_float64(spec, sequence.pieces(x, CD, 1), sequence.pieces(y, CD, 1))
    if count > 1:  # and the pieces after the first are in it
        assert np.abs(got - one_piece).max() > 30 * rounding
    else:
        np.testing.assert_allclose(got, one_piece, rtol=0, atol=rounding)


@pytest.mark.parametrize("count", COUNTS)
@pytest.mark.parametrize("spec,x_shape,y_shape,kind", CALLS, ids=[c[0] for c in CALLS])
def test_a_call_is_one_product_where_a_form_copies_no_large_array(spec, x_shape, y_shape, kind, count):
    x, y = operands(x_shape, y_shape, kind)
    traced = jax.make_jaxpr(lambda a, b: sequence.product(spec, a, b, CD, count))(x, y)
    eqns = list(equations(traced.jaxpr))
    products = [e for e in eqns if e.primitive.name == "dot_general"]
    pairs = len(kept_pairs(sequence.pieces(x, CD, count), sequence.pieces(y, CD, count)))
    if kind == "scores":
        assert len(products) == 1
        return
    if kind == "weight":  # two pieces: the compiler folds the second product's add already
        assert len(products) == (2 if count == 2 else 1)
        return
    # p v: a product a piece of the probabilities (one a pair before PR 44),
    # and nothing larger than their pieces (or the result): the large operand
    # is never copied.
    assert len(products) == count <= pairs
    result = traced.out_avals[0].size
    largest = max(count * max(x.size, y.size), result)
    assert max(v.aval.size for e in eqns for v in e.outvars) <= largest


@pytest.mark.parametrize("count", COUNTS)
def test_a_spec_that_cannot_be_placed_is_a_product_a_pair(count):
    spec = "nqhd,nkhd->nqk"  # two labels contracted: no one axis to lay the pairs along
    assert sequence.contraction_axes(spec) is None
    x, y = operands((2, 8, 3, 8), (2, 12, 3, 8), "scores")
    xs, ys = sequence.pieces(x, CD, count), sequence.pieces(y, CD, count)
    want = in_float64(spec, xs, ys)
    got = np.asarray(sequence.product(spec, x, y, CD, count))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * np.abs(want).max())
    traced = jax.make_jaxpr(lambda a, b: sequence.product(spec, a, b, CD, count))(x, y)
    assert sum(e.primitive.name == "dot_general" for e in equations(traced.jaxpr)) == len(kept_pairs(xs, ys))


@pytest.mark.parametrize("spec,axes", [
    ("...k,kn->...n", (-1, 0)), ("nqhd,nkd->nhqk", (3, 2)), ("nhqk,nkhd->nqhd", (3, 1)),
    ("nbgjqk,nbkgd->nbqgjd", (5, 2)), ("k...,...kn->...n", (0, -2)), ("ab,cd->abcd", None), ("ab,ab->", None),
])
def test_the_contracted_axis_is_read_from_the_spec(spec, axes):
    assert sequence.contraction_axes(spec) == axes


def test_a_product_in_the_compute_dtype_is_one_plain_einsum():
    x, y = operands((2, 5, 16), (16, 24), "weight")
    traced = jax.make_jaxpr(lambda a, b: sequence.product("...k,kn->...n", a, b, jnp.float32, 3))(x, y)
    names = [e.primitive.name for e in equations(traced.jaxpr)]
    assert names.count("dot_general") == 1 and "reduce_precision" not in names


@pytest.mark.parametrize("name", sorted(PRODUCTS))
def test_the_small_steps_hold_fewer_products_than_the_parents(name):
    found = lowered_step(name).as_text().count("stablehlo.dot_general")
    assert found <= PRODUCTS[name] < PARENTS_PRODUCTS[name]


@pytest.mark.parametrize("name", sorted(PRODUCTS))
def test_no_spec_a_family_passes_takes_the_fallback(name, monkeypatch):
    """Every call of `sequence.product` in the family's step names a spec
    with one contracted axis, and one this file holds to the float64 sum."""
    seen, product = set(), sequence.product

    def recording(spec, *args, **kwargs):
        seen.add(spec)
        return product(spec, *args, **kwargs)

    monkeypatch.setattr(sequence, "product", recording)
    lowered_step(name)
    assert seen and seen <= set(SPECS)
    assert all(sequence.contraction_axes(spec) is not None for spec in seen)
