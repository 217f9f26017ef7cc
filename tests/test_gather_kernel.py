"""The embedding gather as a Pallas kernel (ops/gather_kernel.py), run here in
interpret mode: bit for bit XLA's `jnp.take(...).astype(dtype)`, through
`lookup_rows` and `field_embed` for the three CTR layouts, which tables take
it, and what the batcher stamps and counts. Times come from the chip
(PERF.md section 6, PR 39); its compile for a v5e is in test_tpu_compile.py."""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import ModelConfig, build_model, embeddings
from distributed_tf_serving_tpu.models.embeddings import (
    field_embed,
    gather_choice,
    lookup_rows,
    pack_table,
    serving_gathers,
)
from distributed_tf_serving_tpu.models.registry import Servable, ctr_signatures
from distributed_tf_serving_tpu.ops import gather_kernel
from distributed_tf_serving_tpu.ops.gather_kernel import gather_rows
from distributed_tf_serving_tpu.serving import batcher as batcher_mod
from distributed_tf_serving_tpu.serving.batcher import DynamicBatcher

ROWS = 4096  # table rows in these tests


def _table(dtype, rows=ROWS, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((rows, 128)), dtype)


def _bits(x):
    return np.asarray(x).view(np.uint16 if x.dtype == jnp.bfloat16 else np.uint32)


def _same_bits(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(_bits(got), _bits(want))


# Shapes that are no multiple of a block, a unit or a tile; a last axis too
# short and too long to be a unit; the three CTR layouts' units. Interpreted,
# a row copy is a host callback, so each case runs the output dtypes it needs.
CASES = [
    (jnp.float32, (1,), (jnp.bfloat16, jnp.float32)),
    (jnp.float32, (43,), (jnp.bfloat16, jnp.float32)),
    (jnp.float32, (1024 * 43 + 1,), (jnp.bfloat16,)),
    (jnp.float32, (7, 43), (jnp.bfloat16, jnp.float32)),
    (jnp.float32, (64, 26), (jnp.bfloat16, jnp.float32)),
    (jnp.float32, (3, 214), (jnp.bfloat16,)),
    (jnp.float32, (5, 3), (jnp.bfloat16, jnp.float32)),
    (jnp.float32, (2, 3, 43), (jnp.bfloat16,)),
    (jnp.float32, (2, 300), (jnp.bfloat16,)),
    (jnp.bfloat16, (43,), (jnp.bfloat16, jnp.float32)),
    (jnp.bfloat16, (7, 43), (jnp.bfloat16, jnp.float32)),
    (jnp.bfloat16, (5, 3), (jnp.bfloat16, jnp.float32)),
]


@pytest.mark.parametrize(
    "table_dtype, shape, dtypes", CASES,
    ids=[f"{jnp.dtype(t).name}-{'x'.join(map(str, s))}" for t, s, _ in CASES])
def test_kernel_is_xlas_gather_bit_for_bit(table_dtype, shape, dtypes):
    table = _table(table_dtype)
    rows = np.random.default_rng(len(shape)).integers(0, ROWS, shape)
    flat = rows.reshape(-1)
    flat[0], flat[-1] = 0, ROWS - 1  # the table's first and last row
    if flat.size > 8:
        flat[3:6] = flat[1]  # repeated, and out of order beside their neighbours
    rows = jnp.asarray(flat.reshape(shape), jnp.int32)
    for dtype in dtypes:
        got = gather_rows(table, rows, dtype, interpret=True)
        _same_bits(got, jnp.take(table, rows, axis=0).astype(dtype))


def test_kernel_clips_a_row_past_the_table():
    """Mosaic's bounds checks are off in the kernel (they are most of a
    row's cost), so it clips: never a read outside the table."""
    table = _table(jnp.float32)
    rows = jnp.asarray([[-5, ROWS + 7] + [1] * 14], jnp.int32)
    got = gather_rows(table, rows, jnp.float32, interpret=True)
    _same_bits(got, jnp.take(table, jnp.clip(rows, 0, ROWS - 1), axis=0))


def test_blocks_hold_whole_units_and_fit_the_ring():
    for units, unit_rows in [(32768, 43), (8192, 214), (16384, 26), (2752, 16), (1, 43), (3, 16)]:
        per_block = gather_kernel.block_units(units, unit_rows)
        assert 1 <= per_block <= units and per_block * unit_rows <= max(
            gather_kernel.BLOCK_ROWS, unit_rows)
    assert gather_kernel.rows_in_flight((32768, 43)) == 32 * 43
    assert gather_kernel.rows_in_flight((8192, 214)) == 8 * 214
    assert gather_kernel.rows_in_flight((5, 3)) == 16  # a flat list: one unit of 16
    assert gather_kernel.rows_in_flight((2, 300)) == 38 * 16  # too long for a unit: flat


@pytest.mark.parametrize("pack, embed_dim", [(1, 128), (8, 16)], ids=["P1", "P8"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_lookup_rows_through_the_kernel_is_the_xla_path(pack, embed_dim, dtype):
    logical = jnp.asarray(
        np.random.default_rng(1).standard_normal((ROWS * pack, embed_dim)), jnp.float32)
    table = pack_table(logical, embed_dim)
    assert table.shape == (ROWS, 128)
    rows = jnp.asarray(np.random.default_rng(2).integers(0, ROWS * pack, (9, 43)), jnp.int32)
    want = lookup_rows(table, rows, embed_dim, dtype)
    with serving_gathers([], interpret=True) as notes:
        got = lookup_rows(table, rows, embed_dim, dtype)
    assert [n["kernel"] for n in notes] == ["pallas"]
    _same_bits(got, want)
    _same_bits(got, logical[rows].astype(dtype))


CTR_LAYOUTS = {
    # fields, embed_dim, logical rows, bag sizes
    "dcn_43x16_packed": (43, 16, ROWS * 8, ()),
    "dlrm_26x128": (26, 128, ROWS, ()),
    "dlrm_dcnv2_214x128_bags": (214, 128, ROWS, (
        3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100, 27, 10, 3, 1, 1)),
}


@pytest.mark.parametrize("name", sorted(CTR_LAYOUTS))
def test_field_embed_through_the_kernel_is_the_xla_path(name):
    fields, embed_dim, vocab, bags = CTR_LAYOUTS[name]
    rng = np.random.default_rng(3)
    table = pack_table(jnp.asarray(rng.standard_normal((vocab, embed_dim)), jnp.float32), embed_dim)
    ids = jnp.asarray(rng.integers(0, 1 << 30, (3, fields)), jnp.int32)
    wts = jnp.asarray(rng.random((3, fields)), jnp.float32)
    want = field_embed(table, ids, wts, jnp.bfloat16, embed_dim, bags)
    with serving_gathers([], interpret=True) as notes:
        got = field_embed(table, ids, wts, jnp.bfloat16, embed_dim, bags)
        looked_up = lookup_rows(
            table, embeddings.fold_ids(ids, vocab), embed_dim, jnp.bfloat16)
    assert notes and all(n["kernel"] == "pallas" and n["row_bytes"] == 512 for n in notes)
    # 0 ulp before the pooling, and after it (the same operations on the same bits).
    _same_bits(looked_up, lookup_rows(table, embeddings.fold_ids(ids, vocab), embed_dim, jnp.bfloat16))
    _same_bits(got, want)
    assert got.shape == (3, len(bags) or fields, embed_dim)


@pytest.mark.parametrize(
    "shape, dtype, served, interpret, kernel",
    [
        ((ROWS, 128), jnp.float32, True, True, "pallas"),  # one float32 lane row
        ((ROWS, 128), jnp.float32, True, False, "xla"),  # a CPU backend, no interpret
        ((ROWS, 128), jnp.float32, False, False, "xla"),  # no served entry is being traced
        ((ROWS, 128), jnp.bfloat16, True, True, "xla"),  # two rows a 32-bit sublane
        ((ROWS, 2560), jnp.bfloat16, True, True, "xla"),  # phi4flash's table
        ((ROWS, 7680), jnp.bfloat16, True, True, "xla"),  # pangu_moe's
        ((ROWS, 16), jnp.float32, True, True, "xla"),  # a logical, unpacked [V, 16]
        ((ROWS, 256), jnp.float32, True, True, "xla"),
    ],
)
def test_which_tables_take_the_kernel(shape, dtype, served, interpret, kernel):
    table = jax.ShapeDtypeStruct(shape, dtype)
    rows = jax.ShapeDtypeStruct((8, 43), jnp.int32)

    with serving_gathers([], interpret) if served else contextlib.nullcontext():
        choice = gather_choice(table, rows)
    assert choice["kernel"] == kernel
    assert choice["row_bytes"] == shape[1] * jnp.dtype(dtype).itemsize
    assert choice["in_flight"] == (8 * 43 if kernel == "pallas" else 0)


# Who else traces `model.apply` or `lookup_rows` over a [V, 128] float32
# table: GSPMD cannot partition a `tpu_custom_call`, a DMA kernel has no
# differentiation rule and none has run under shard_map, so on a TPU each of
# them has to keep XLA's gather. The backend is the CPU here, so the test says
# `tpu` where gather_choice asks and makes the kernel's entry raise.
DLRM_128 = ModelConfig(
    num_fields=8, vocab_size=1024, embed_dim=128, mlp_dims=(16,), bottom_mlp_dims=(16, 128),
    compute_dtype="float32",
)


def _gspmd_executor(sv, batch, mesh):
    from distributed_tf_serving_tpu.parallel import ShardedExecutor

    return ShardedExecutor(mesh)(sv, batch)["prediction_node"]


def _shard_map_score(sv, batch, mesh):
    from distributed_tf_serving_tpu.parallel import shard_map_score

    return shard_map_score(sv, mesh)(sv.params, jax.tree.map(jnp.asarray, batch))


def _sharded_field_embed(sv, batch, mesh):
    from distributed_tf_serving_tpu.parallel import MODEL_AXIS, sharded_field_embed

    table = jax.device_put(
        sv.params["embedding"],
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(MODEL_AXIS, None)))
    return jax.jit(lambda t, i, w: sharded_field_embed(t, i, w, mesh, jnp.float32, 128))(
        table, jnp.asarray(batch["feat_ids"]), jnp.asarray(batch["feat_wts"]))


def _train_step(sv, batch, mesh):
    import optax

    from distributed_tf_serving_tpu.train.trainer import TrainState, make_train_step

    optimizer = optax.sgd(1e-2)
    state = TrainState(
        params=sv.params, opt_state=optimizer.init(sv.params), step=jnp.zeros((), jnp.int32))
    labels = jnp.asarray(np.arange(len(batch["feat_ids"])) % 2, jnp.float32)
    state, metrics = make_train_step(sv.model, optimizer)(state, {**batch, "labels": labels})
    assert np.isfinite(float(metrics["loss"]))
    return state.params["embedding"]


def _plain_apply(sv, batch, mesh):
    return jax.jit(sv.model.apply)(sv.params, batch)["prediction_node"]


@pytest.mark.parametrize(
    "caller",
    [_gspmd_executor, _shard_map_score, _sharded_field_embed, _train_step, _plain_apply],
    ids=lambda f: f.__name__.strip("_"),
)
def test_only_the_served_entry_takes_the_kernel(caller, monkeypatch):
    from distributed_tf_serving_tpu.parallel import make_mesh

    def refused(*args, **kwargs):
        raise AssertionError("the Pallas gather outside a one-chip served entry")

    monkeypatch.setattr(embeddings.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(gather_kernel, "gather_rows", refused)
    model = build_model("dlrm", DLRM_128)
    sv = Servable(
        name="dlrm", version=1, model=model, params=model.init(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(DLRM_128.num_fields, with_dense=DLRM_128.num_dense_features),
    )
    assert sv.params["embedding"].shape == (1024, 128)
    rng = np.random.RandomState(5)
    batch = {
        "feat_ids": rng.randint(0, 1024, size=(16, 8)).astype(np.int32),
        "feat_wts": rng.rand(16, 8).astype(np.float32),
        "dense_features": rng.randn(16, DLRM_128.num_dense_features).astype(np.float32),
    }
    out = caller(sv, batch, make_mesh(4, model_parallel=2))
    assert np.isfinite(np.asarray(out)).all()
    # And the rule does reach for the kernel here once an entry is served.
    with serving_gathers([]) as notes, pytest.raises(AssertionError, match="outside a one-chip"):
        lookup_rows(sv.params["embedding"], jnp.asarray(batch["feat_ids"]), 128, jnp.float32)
    assert [n["kernel"] for n in notes] == ["pallas"]


def _servable(kind, fields, embed_dim, **extra):
    cfg = ModelConfig(
        num_fields=fields, vocab_size=1024, embed_dim=embed_dim, mlp_dims=(16,),
        num_cross_layers=1, compute_dtype="bfloat16", **extra,
    )
    model = build_model(kind, cfg)
    return Servable(
        name=kind, version=1, model=model,
        params=jax.jit(functools.partial(model.init, packed=True))(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(
            fields, with_dense=cfg.num_dense_features if model.takes_dense else None),
    )


def _payload(sv, n, seed):
    rng = np.random.RandomState(seed)
    cfg = sv.model.config
    out = {
        "feat_ids": rng.randint(0, 1 << 40, size=(n, cfg.num_fields)).astype(np.int64),
        "feat_wts": rng.rand(n, cfg.num_fields).astype(np.float32),
    }
    if sv.model.takes_dense:
        out["dense_features"] = rng.randn(n, cfg.num_dense_features).astype(np.float32)
    return out


def _serve(sv, payloads):
    from distributed_tf_serving_tpu.utils.tracing import request_trace

    batcher = DynamicBatcher(buckets=(16,), max_wait_us=0).start()
    try:
        before = request_trace.snapshot().get("batch.gather_kernel", {}).get("count", 0)
        scores = [batcher.submit(sv, p).result(timeout=300)["prediction_node"] for p in payloads]
        counted = request_trace.snapshot().get("batch.gather_kernel", {}).get("count", 0) - before
        return np.concatenate(scores), batcher.stats, counted, batcher.gathers()
    finally:
        batcher.stop()


@pytest.mark.parametrize(
    "kind, fields, embed_dim, extra",
    [("dcn_v2", 43, 16, {}), ("dlrm", 26, 128, {"bottom_mlp_dims": (16, 128)})],
    ids=["dcn_packed", "dlrm"],
)
def test_batcher_stamps_the_gather_and_counts_its_batches(kind, fields, embed_dim, extra, monkeypatch):
    """`startup.gather` per servable and the batches that ran the kernel,
    beside `batches`; the scores are the XLA entry's to the last bit."""
    sv = _servable(kind, fields, embed_dim, **extra)
    payloads = [_payload(sv, n, seed=n) for n in (5, 13)]
    want, stats, counted, stamp = _serve(sv, payloads)
    assert stats.batches == 2 and stats.gather_kernel_batches == 0 and counted == 0
    assert stamp == {f"{kind}:1": {
        "kernel": "xla", "row_bytes": 512, "in_flight": 0, "picked_in_kernel": False}}
    monkeypatch.setattr(
        batcher_mod, "serving_gathers", functools.partial(serving_gathers, interpret=True))
    got, stats, counted, stamp = _serve(sv, payloads)
    assert stats.batches == 2 and stats.gather_kernel_batches == 2 and counted == 2
    assert stamp == {f"{kind}:1": {
        "kernel": "pallas", "row_bytes": 512, "in_flight": 16 * fields, "picked_in_kernel": False}}
    np.testing.assert_array_equal(got, want)
