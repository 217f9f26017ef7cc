"""`models/sequence.py::product`: a product of pieces is ONE product wherever a
form exists that copies no large array and the chip runs it no slower, the
same pairs of pieces as a product a pair, the three families' steps hold
the fewer products for it, and the servables' `startup.products` stamp says
how many of a step's operations against a weight meet in one product."""

import dataclasses

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
# and this one (`phi4flash_small` held 112 while its two pieces met a weight
# in a product a piece: PR 44 to PR 56).
PARENTS_PRODUCTS = {"phi4flash_small": 118, "pangu_moe_small": 104, "exaone_moe_small": 147}
PRODUCTS = {"phi4flash_small": 65, "pangu_moe_small": 78, "exaone_moe_small": 109}


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
    if kind == "weight":
        # ONE product at any number of pieces, and nothing larger than the
        # stacked pieces or the result (the stacked form's, a result a piece
        # until the sum the compiler fuses in) but, at two pieces, the
        # weight's own broadcast, which reads the weight as it was handed in:
        # the weight is never copied.
        assert len(products) == 1
        repeated = [e for e in eqns if e.primitive.name == "broadcast_in_dim" and e.outvars[0].aval.size > y.size]
        assert len(repeated) == (count == 2) and all(e.invars[0] is traced.jaxpr.invars[1] for e in repeated)
        others = [v.aval.size for e in eqns if e not in repeated for v in e.outvars]
        result = traced.out_avals[0].size
        assert max(others) <= max(count * x.size, result if count == 2 else count * result, y.size)
        return
    # p v: a product a piece of the probabilities (one a pair before PR 44),
    # and nothing larger than their pieces (or the result): the large operand
    # is never copied.
    assert len(products) == count <= pairs
    result = traced.out_avals[0].size
    largest = max(count * max(x.size, y.size), result)
    assert max(v.aval.size for e in eqns for v in e.outvars) <= largest


# The rule against a weight, walked along each thing it reads: the pieces'
# count (1: one plain product; 2: the pieces meet along a second contracted
# axis; 3 and more: stacked and summed), whether the second operand is whole
# in the compute dtype (a float32 one is cut into pieces itself and is no
# weight: a product a pair), and, on both sides of every shape the cells have,
# the rows (one position to many), a product deeper than wide and wider than
# deep: the shapes do not move it.
# (x's shape, y's shape, y's dtype, count, the form noted, dot_generals, their contracted axes)
BOUNDARY = {
    "one piece": ((3, 16), (16, 24), CD, 1, None, 1, 1),
    "two pieces": ((3, 16), (16, 24), CD, 2, "contracted", 1, 2),
    "three pieces": ((3, 16), (16, 24), CD, 3, "stacked", 1, 1),
    "four pieces": ((3, 16), (16, 24), CD, 4, "stacked", 1, 1),
    "two pieces, the second in float32": ((3, 16), (16, 24), jnp.float32, 2, None, 3, 1),
    "two pieces, one position": ((1, 16), (16, 24), CD, 2, "contracted", 1, 2),
    "two pieces, many positions": ((4, 64, 16), (16, 24), CD, 2, "contracted", 1, 2),
    "two pieces, deeper than wide": ((3, 48), (48, 8), CD, 2, "contracted", 1, 2),
    "two pieces, wider than deep": ((3, 8), (8, 48), CD, 2, "contracted", 1, 2),
    "three pieces, deeper than wide": ((3, 48), (48, 8), CD, 3, "stacked", 1, 1),
}


@pytest.mark.parametrize("case", sorted(BOUNDARY))
def test_the_form_against_a_weight_follows_the_pieces_and_not_the_shapes(case):
    x_shape, y_shape, y_dtype, count, form, products, contracted = BOUNDARY[case]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal(x_shape), jnp.float32)
    y = jnp.asarray(rng.standard_normal(y_shape), y_dtype)
    spec = "...k,kn->...n"
    with sequence.serving_attention([], products=(notes := [])):
        traced = jax.make_jaxpr(lambda a, b: sequence.product(spec, a, b, CD, count))(x, y)
    dots = [e for e in equations(traced.jaxpr) if e.primitive.name == "dot_general"]
    assert len(dots) == products
    assert all(len(e.params["dimension_numbers"][0][0]) == contracted for e in dots)
    rows, (k, n) = int(np.prod(x_shape[:-1])), y_shape
    assert notes == ([(rows, k, n, count, form)] if form else [])
    assert sequence.product_summary(notes) == {
        "ops": 2 * rows * k * n * count if form else 0, "fused_ops": 2 * rows * k * n * count if form else 0,
        "forms": {form: 1} if form else {}}
    want = in_float64(spec, sequence.pieces(x, CD, count), sequence.pieces(y, CD, count))
    got = np.asarray(sequence.product(spec, x, y, CD, count))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * np.abs(want).max())


def test_nothing_is_noted_outside_a_served_entry_or_without_a_list():
    x, y = operands((2, 5, 16), (16, 24), "weight")
    sequence.product("...k,kn->...n", x, y, CD, 2)  # no entry: nothing to note into
    with sequence.serving_attention([]):  # an entry that keeps no products
        sequence.product("...k,kn->...n", x, y, CD, 2)
    with sequence.serving_attention([], products=(notes := [])):
        sequence.product("...k,kn->...n", x, y, CD, 2)
        sequence.product("nqhd,nkhd->nhqk", *operands((2, 8, 3, 8), (2, 12, 3, 8), "scores"), CD, 2)  # no weight
    assert notes == [(10, 16, 24, 2, "contracted")]
    both = 2 * 10 * 16 * 24 * 2 + 2 * 4 * 8 * 8 * 3
    assert sequence.product_summary([(10, 16, 24, 2, "contracted"), (4, 8, 8, 3, "stacked")]) == {
        "ops": both, "fused_ops": both, "forms": {"contracted": 1, "stacked": 1}}


def test_the_gradient_through_two_pieces_against_a_weight_holds_no_more_than_a_product_a_piece_did():
    """The trainer's path: `jax.grad` through the contracted form, traced.
    Three products (the forward's, the activation's cotangent, the weight's),
    each with both contracted axes or the pieces' axis kept; the only values
    of the repeated weight's size are the forward's broadcast of the weight
    as handed in and the weight's cotangent on its way to the sum over the
    pieces (a product a piece held the same bytes as two `[k, n]` cotangents
    to add), and nothing traced is larger than the stacked pieces; the
    weight's gradient is the float64 one to the compute dtype's rounding."""
    x, w = operands((4, 64, 16), (16, 24), "weight")
    spec, count = "...k,kn->...n", 2

    def loss(a, b):
        return jnp.sum(sequence.product(spec, a, b, CD, count) ** 2)

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w)
    eqns = list(equations(traced.jaxpr))
    assert sum(e.primitive.name == "dot_general" for e in eqns) == 3
    repeated = [e for e in eqns for v in e.outvars if v.aval.size == count * w.size]
    # the cotangent: its product, laid `[pieces, k, n]` and rounded to the weight's dtype, then summed over the pieces
    assert sorted(e.primitive.name for e in repeated) == [
        "broadcast_in_dim", "convert_element_type", "dot_general", "transpose"]
    assert next(e for e in repeated if e.primitive.name == "broadcast_in_dim").invars[0] is traced.jaxpr.invars[1]
    assert max(v.aval.size for e in eqns for v in e.outvars) <= count * max(x.size, w.size)
    grads = jax.grad(loss, argnums=(0, 1))(x, w)
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in grads)
    whole = sum(np.asarray(piece, np.float64) for piece in sequence.pieces(x, CD, count)).reshape(-1, 16)
    want = whole.T @ (2 * whole @ np.asarray(w, np.float64))
    np.testing.assert_allclose(np.asarray(grads[1], np.float64), want, rtol=0, atol=2 ** -7 * np.abs(want).max())


@pytest.mark.parametrize("axis, all_reduces", [("n", 0), ("k", 1)])
def test_two_pieces_against_a_sharded_weight_partition_without_gathering_it(axis, all_reduces):
    """The GSPMD executors' path: the contracted form compiled over a 2 x 2
    mesh, the rows over `data` and the weight over `model` along its width or
    its depth. ONE product a chip on the weight's own shard: never an
    `all-gather` (the broadcast repeats the shard, not the weight), and the
    one `all-reduce` a weight sharded along the contracted axis always took."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    x = jax.ShapeDtypeStruct((4, 64, 16), jnp.float32, sharding=NamedSharding(mesh, PartitionSpec("data")))
    spec = PartitionSpec(None, "model") if axis == "n" else PartitionSpec("model", None)
    w = jax.ShapeDtypeStruct((16, 24), CD, sharding=NamedSharding(mesh, spec))
    text = jax.jit(lambda a, b: sequence.product("...k,kn->...n", a, b, CD, 2)).lower(x, w).compile().as_text()
    assert text.count(" dot(") + text.count(" convolution(") == 1
    assert "all-gather" not in text and "collective-permute" not in text
    assert text.count(" all-reduce(") == all_reduces


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


# ------------------------------------------------- the `startup.products` stamp

# The small TOML of each sequence family, the pieces its activations enter a
# product as, and the form every product against a weight takes for it.
STAMPED = {
    "phi4flash_small": (2, "contracted"), "olmo_hybrid_small": (2, "contracted"), "falcon_h1_small": (2, "contracted"),
    "pangu_moe_small": (3, "stacked"), "exaone_moe_small": (3, "stacked"), "mimo_v2_small": (3, "stacked"),
}


def runtime_startup(config_name: str) -> dict:
    """`/monitoring?section=runtime`'s `startup` block of the CLI server's
    stack for `configs/<config_name>.toml`, after one request."""
    from distributed_tf_serving_tpu.serving.server import build_stack

    cfgs = load_config(os.path.join(ROOT, "configs", config_name + ".toml"))
    config = dataclasses.replace(cfgs["model"], name="M")
    cfg = dataclasses.replace(cfgs["server"], model_name="M", warmup=False)
    _registry, batcher, impl, servable, _mesh, _watcher = build_stack(cfg, model_config=config)
    try:
        rng = np.random.default_rng(1)
        arrays = {"feat_ids": rng.integers(0, 1 << 40, size=(2, config.num_fields), dtype=np.int64),
                  "feat_wts": rng.random((2, config.num_fields), dtype=np.float32)}
        if servable.model.takes_dense:
            arrays["dense_features"] = rng.random((2, config.num_dense_features), dtype=np.float32)
        batcher.submit(servable, arrays).result(timeout=600)
        return impl.runtime_stats()["startup"]
    finally:
        batcher.stop()


@pytest.mark.parametrize("name", sorted(STAMPED))
def test_a_sequence_servables_products_are_stamped_and_all_meet_in_one_product(name):
    count, form = STAMPED[name]
    stamp = runtime_startup(name)["products"]
    assert sorted(stamp) == ["M:1"]
    assert stamp["M:1"]["ops"] == stamp["M:1"]["fused_ops"] > 0
    assert list(stamp["M:1"]["forms"]) == [form] and stamp["M:1"]["forms"][form] > 4


def test_a_ctr_servable_has_no_products_stamp():
    startup = runtime_startup("latency")
    assert startup["products"] == {} and startup["gather"]  # its entry was traced: the gather is stamped
