"""Mesh serving mode (ISSUE 13): the [mesh] section end to end on the
virtual 8-device CPU mesh — named partition rules, the data-axis
divisibility fix, build_stack wiring + explicit mode refusals, the `mesh`
monitoring/Prometheus surfaces, per-device utilization attribution, and
the key-affinity client placement satellite."""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_tf_serving_tpu.models import (
    ModelConfig,
    Servable,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.parallel import (
    DATA_AXIS,
    MODEL_AXIS,
    ShardedExecutor,
    make_mesh,
    match_partition_rules,
    param_shardings,
    partition_rules_for,
    tree_path_str,
)
from distributed_tf_serving_tpu.serving import DynamicBatcher
from distributed_tf_serving_tpu.serving.batcher import fold_ids_host
from distributed_tf_serving_tpu.serving.server import build_stack
from distributed_tf_serving_tpu.utils.config import (
    MeshConfig,
    RecoveryConfig,
    ServerConfig,
    load_config,
)

CFG = ModelConfig(
    num_fields=8, vocab_size=1024, embed_dim=4, mlp_dims=(16,),
    num_cross_layers=1, compute_dtype="float32",
)


def _servable(seed=0, kind="dcn_v2", cfg=CFG):
    model = build_model(kind, cfg)
    return Servable(
        name="DCN", version=1, model=model,
        params=model.init(jax.random.PRNGKey(seed)),
        signatures=ctr_signatures(cfg.num_fields),
    )


def _arrays(n, seed=0, cfg=CFG):
    rng = np.random.RandomState(seed)
    return {
        "feat_ids": rng.randint(0, 1 << 40, size=(n, cfg.num_fields)).astype(np.int64),
        "feat_wts": rng.rand(n, cfg.num_fields).astype(np.float32),
    }


def _golden(sv, arrays, cfg=CFG):
    batch = {
        "feat_ids": fold_ids_host(arrays["feat_ids"], cfg.vocab_size),
        "feat_wts": arrays["feat_wts"],
    }
    return np.asarray(jax.jit(sv.model.apply)(sv.params, batch)["prediction_node"])


def _prepared(arrays, cfg=CFG):
    return {
        "feat_ids": fold_ids_host(arrays["feat_ids"], cfg.vocab_size),
        "feat_wts": arrays["feat_wts"],
    }


# ------------------------------------------------------- partition rules


def test_build_model_stamps_kind():
    assert build_model("dcn_v2", CFG).kind == "dcn_v2"
    assert build_model("dlrm", dataclasses.replace(CFG, bottom_mlp_dims=(8, 4))).kind == "dlrm"


def test_tree_path_str_handles_dicts_and_lists():
    params = {"cross": [{"w": np.zeros((4, 4))}]}
    paths = []
    jax.tree_util.tree_map_with_path(
        lambda p, _l: paths.append(tree_path_str(p)), params
    )
    assert paths == ["cross/0/w"]


@pytest.mark.parametrize("kind", ["dcn_v2", "dlrm", "two_tower"])
def test_named_rules_pin_embedding_tables(kind):
    cfg = {
        "dlrm": dataclasses.replace(CFG, bottom_mlp_dims=(8, 4)),
        "two_tower": dataclasses.replace(CFG, num_user_fields=4),
    }.get(kind, CFG)
    sv = _servable(kind=kind, cfg=cfg)
    rules = partition_rules_for(kind)
    assert rules is not None
    specs = match_partition_rules(rules, sv.params)
    assert specs["embedding"] == P(MODEL_AXIS, None)


def test_two_tower_temperature_is_explicitly_replicated():
    sv = _servable(
        kind="two_tower", cfg=dataclasses.replace(CFG, num_user_fields=4)
    )
    specs = match_partition_rules(partition_rules_for("two_tower"), sv.params)
    assert specs["temperature"] == P()


def test_rule_rank_mismatch_raises():
    with pytest.raises(ValueError, match="no longer matches"):
        match_partition_rules(
            (("^embedding$", P(MODEL_AXIS, None)),),
            {"embedding": np.zeros((16,))},  # 1-D table vs 2-dim rule
        )


def test_unmatched_leaf_none_or_strict_raises():
    rules = (("^embedding$", P(MODEL_AXIS, None)),)
    params = {"embedding": np.zeros((16, 4)), "mlp": np.zeros((4, 4))}
    specs = match_partition_rules(rules, params)
    assert specs["mlp"] is None
    with pytest.raises(ValueError, match="no partition rule matched"):
        match_partition_rules(rules, params, strict=True)


def test_param_shardings_with_rules_match_generic_layout():
    """The named-rule path must land the same layout the generic
    path-name walker produces for the zoo (the rules are a contract, not
    a behavior change)."""
    mesh = make_mesh(8, model_parallel=2)
    sv = _servable()
    generic = param_shardings(sv.params, mesh, tensor_parallel=True)
    ruled = param_shardings(
        sv.params, mesh, tensor_parallel=True, model_kind="dcn_v2"
    )
    flat_g = jax.tree_util.tree_leaves(generic)
    flat_r = jax.tree_util.tree_leaves(ruled)
    assert [s.spec for s in flat_g] == [s.spec for s in flat_r]


# --------------------------------------------------- divisibility fix


@pytest.mark.parametrize("rows", [5, 10, 50, 63])
def test_executor_pads_non_divisible_batches(rows):
    """The ISSUE 13 satellite: bucket sizes the ladder legitimately
    produces (any size) are padded to the data axis inside the executor
    and sliced back — never raised on."""
    mesh = make_mesh(8, model_parallel=2)  # data axis = 4
    sv = _servable()
    ex = ShardedExecutor(mesh)
    arrays = _arrays(rows, seed=11)
    out = np.asarray(ex(sv, _prepared(arrays))["prediction_node"])
    assert out.shape == (rows,)
    np.testing.assert_allclose(out, _golden(sv, arrays), rtol=1e-6)
    snap = ex.snapshot()
    if rows % 4:
        assert snap["executor"]["pad_batches"] >= 1
        assert snap["executor"]["data_pad_rows"] >= 1
    else:
        assert snap["executor"]["pad_batches"] == 0


@pytest.mark.parametrize(
    "vocab,dim,placed_rows",
    # 1024 x 4: 32 packed rows, split in two. 1000 x 16: the 125 packed
    # rows do not split in two, so the executor places the logical table.
    [(1024, 4, 32), (1000, 16, 1000)],
    ids=["packed", "packed_rows_do_not_divide"],
)
def test_executor_serves_lane_packed_table_bit_identical(vocab, dim, placed_rows):
    from distributed_tf_serving_tpu.models.embeddings import pack_params

    cfg = dataclasses.replace(CFG, vocab_size=vocab, embed_dim=dim)
    logical = _servable(cfg=cfg)
    served = dataclasses.replace(logical, params=pack_params(logical.params, dim))
    assert logical.embedding_pack == 1 and served.embedding_pack == 128 // dim
    mesh = make_mesh(8, model_parallel=2)
    ex = ShardedExecutor(mesh)
    arrays = _arrays(24, seed=12, cfg=cfg)
    want = np.asarray(ex(logical, _prepared(arrays, cfg))["prediction_node"])
    got = np.asarray(ex(served, _prepared(arrays, cfg))["prediction_node"])
    np.testing.assert_array_equal(got, want)
    table = ex._prepare(served)[1]["embedding"]
    assert table.shape[0] == placed_rows and table.sharding.spec == P(MODEL_AXIS, None)


def test_batcher_arbitrary_buckets_over_mesh_bit_identical():
    """A bucket ladder with NON-mesh-shaped rungs serves over the mesh
    with scores identical to the single-device execution."""
    mesh = make_mesh(8, model_parallel=2)
    sv = _servable()
    ex = ShardedExecutor(mesh)
    batcher = DynamicBatcher(buckets=(10, 50), max_wait_us=0, run_fn=ex).start()
    try:
        for n, seed in [(7, 1), (33, 2), (50, 3)]:
            arrays = _arrays(n, seed)
            # The serving contract: output-filtered requests (what every
            # production client sends) are BIT-identical at padded
            # shapes; unfiltered all-outputs is float-exact (~1 ULP).
            got = batcher.submit(
                sv, arrays, output_keys=("prediction_node",)
            ).result(timeout=60)["prediction_node"]
            np.testing.assert_array_equal(got, _golden(sv, arrays))
            unfiltered = batcher.submit(sv, arrays).result(timeout=60)
            np.testing.assert_allclose(
                unfiltered["prediction_node"], _golden(sv, arrays), rtol=1e-6
            )
    finally:
        batcher.stop()
    assert ex.snapshot()["executor"]["pad_batches"] >= 1


def test_executor_out_keys_filter_and_sidecar_passthrough():
    """Output selection rides through the mesh executor (PR-1 compaction
    over the mesh): a score-only union fetches only the score tensor."""
    mesh = make_mesh(8)
    sv = _servable()
    ex = ShardedExecutor(mesh)
    arrays = _prepared(_arrays(16, seed=4))
    full = ex(sv, arrays)
    assert set(full) >= {"prediction_node", "logits"}
    only = ex(sv, arrays, out_keys=("prediction_node",))
    assert set(only) == {"prediction_node"}
    np.testing.assert_array_equal(
        np.asarray(only["prediction_node"]),
        np.asarray(full["prediction_node"]),
    )


def test_batcher_passes_out_keys_union_to_mesh_executor():
    mesh = make_mesh(8)
    sv = _servable()
    seen = []

    class Spy(ShardedExecutor):
        def __call__(self, servable, arrays, out_keys=None):
            seen.append(out_keys)
            return super().__call__(servable, arrays, out_keys=out_keys)

    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0, run_fn=Spy(mesh)).start()
    try:
        arrays = _arrays(8, seed=5)
        got = batcher.submit(
            sv, arrays, output_keys=("prediction_node",)
        ).result(timeout=60)
        assert set(got) == {"prediction_node"}
        np.testing.assert_array_equal(
            got["prediction_node"], _golden(sv, arrays)
        )
    finally:
        batcher.stop()
    assert ("prediction_node",) in seen


def test_padded_precision_contract():
    """The documented precision contract at padded shapes: the
    output-FILTERED path (what production clients send) is BIT-identical
    to single-chip; the unfiltered all-outputs variant is a different
    executable and is float-exact within ~1 ULP (XLA may fuse the
    multi-output graph differently at the padded shape)."""
    mesh = make_mesh(8, model_parallel=2)  # data axis 4; 7 rows -> pad 1
    sv = _servable(seed=19)
    ex = ShardedExecutor(mesh)
    arrays = _arrays(7, seed=20)
    golden = _golden(sv, arrays)
    filtered = np.asarray(
        ex(sv, _prepared(arrays), out_keys=("prediction_node",))["prediction_node"]
    )
    np.testing.assert_array_equal(filtered, golden)
    unfiltered = np.asarray(ex(sv, _prepared(arrays))["prediction_node"])
    np.testing.assert_allclose(unfiltered, golden, rtol=1e-6)


def test_int8_wire_quantization_excludes_pad_rows():
    """The divisibility pad must be sliced off BEFORE the int8 wire's
    per-tensor quantization: pad-row scores inside the min/max would
    stretch the scale and perturb every real row (review finding). The
    restored output must equal the numpy-twin round-trip of the UNPADDED
    scores exactly."""
    from distributed_tf_serving_tpu import codec
    from distributed_tf_serving_tpu.ops.transfer import restore_outputs_host

    mesh = make_mesh(8, model_parallel=2)  # data axis 4
    sv = _servable(seed=21)
    ex = ShardedExecutor(mesh, output_wire_dtype="int8")
    arrays = _arrays(10, seed=22)  # 10 % 4 != 0 -> 2 pad rows
    out = ex(sv, _prepared(arrays))
    host = restore_outputs_host({k: np.asarray(v) for k, v in out.items()})
    got = host["prediction_node"]
    assert got.shape == (10,)
    golden = _golden(sv, arrays)
    q, scale, mn = codec.quantize_scores(golden)
    np.testing.assert_array_equal(got, codec.dequantize_scores(q, scale, mn))


# ------------------------------------------------- build_stack wiring


def _mesh_cfg(**kw):
    return MeshConfig(enabled=True, devices=8, model_parallel=2, **kw)


def _server_cfg(**kw):
    base = dict(
        model_kind="dcn_v2", model_name="DCN", num_fields=CFG.num_fields,
        buckets=(10, 50), max_wait_us=0, warmup=False,
    )
    base.update(kw)
    return ServerConfig(**base)


def _model_cfg():
    return CFG


def test_build_stack_mesh_mode_serves_bit_identical(tmp_path):
    """The tentpole end to end: build_stack with [mesh] constructs the
    mesh, installs the ShardedExecutor, and serves scores identical to a
    single-chip build of the same params."""
    from distributed_tf_serving_tpu.train.checkpoint import save_servable

    sv = _servable(seed=7)
    ckpt = tmp_path / "ckpt"
    save_servable(str(ckpt), sv, kind="dcn_v2")

    registry1, batcher1, impl1, sv1, mesh1, _w = build_stack(
        _server_cfg(), checkpoint=str(ckpt), model_config=_model_cfg(),
    )
    registry2, batcher2, impl2, sv2, mesh2, _w = build_stack(
        _server_cfg(), checkpoint=str(ckpt), model_config=_model_cfg(),
        mesh_config=_mesh_cfg(),
    )
    try:
        assert mesh1 is None and mesh2 is not None
        assert dict(mesh2.shape) == {"data": 4, "model": 2}
        assert impl2.mesh_executor is not None
        for n, seed in [(9, 1), (41, 2)]:
            arrays = _arrays(n, seed)
            # Output-filtered (the production request shape): bitwise.
            keys = ("prediction_node",)
            a = batcher1.submit(sv1, arrays, output_keys=keys).result(
                timeout=120)["prediction_node"]
            b = batcher2.submit(sv2, arrays, output_keys=keys).result(
                timeout=120)["prediction_node"]
            np.testing.assert_array_equal(a, b)
        snap = impl2.mesh_stats()
        assert snap["shape"] == {"data": 4, "model": 2}
        assert len(snap["devices"]) == 8
        assert snap["executor"]["batches"] >= 2
        assert snap["executor"]["layout"]["DCN"] == "rules:dcn_v2"
        assert impl1.mesh_stats() is None
    finally:
        batcher1.stop()
        batcher2.stop()


def test_build_stack_refusals():
    # [mesh] x [recovery]: the blanket refusal is LIFTED (ISSUE 15 — the
    # mesh executor recovers as one unit, default scope="executor");
    # only per-chip scope stays refused.
    _r, b, impl, _sv, _m, _w = build_stack(
        _server_cfg(), model_config=_model_cfg(),
        mesh_config=_mesh_cfg(),
        recovery_config=RecoveryConfig(enabled=True),
    )
    try:
        assert impl.recovery is not None
    finally:
        b.stop()
    with pytest.raises(ValueError, match="per_chip"):
        build_stack(
            _server_cfg(), model_config=_model_cfg(),
            mesh_config=_mesh_cfg(),
            recovery_config=RecoveryConfig(enabled=True, scope="per_chip"),
        )
    # [mesh] x legacy [server] mesh knobs (all three)
    for legacy in (
        {"mesh_devices": 8}, {"model_parallel": 2}, {"tensor_parallel": True}
    ):
        with pytest.raises(ValueError, match="legacy \\[server\\]"):
            build_stack(
                _server_cfg(**legacy), model_config=_model_cfg(),
                mesh_config=_mesh_cfg(),
            )
    # [mesh] x output_top_k
    with pytest.raises(ValueError, match="output_top_k"):
        build_stack(
            _server_cfg(output_top_k=4), model_config=_model_cfg(),
            mesh_config=_mesh_cfg(),
        )


def test_mesh_tensor_parallel_preplaces_loaded_params(tmp_path):
    """[mesh] tensor_parallel must reach the LOADER paths, not just the
    executor: a checkpoint restore pre-places dense weights in the
    model-axis-split layout the executor serves (review finding — the
    effective knob, not cfg.tensor_parallel, threads through)."""
    from distributed_tf_serving_tpu.train.checkpoint import save_servable

    sv = _servable(seed=8)
    ckpt = tmp_path / "ckpt"
    save_servable(str(ckpt), sv, kind="dcn_v2")
    _r, batcher, impl, loaded, mesh, _w = build_stack(
        _server_cfg(), checkpoint=str(ckpt), model_config=_model_cfg(),
        mesh_config=_mesh_cfg(tensor_parallel=True),
    )
    try:
        assert impl.mesh_executor.tensor_parallel is True
        # mlp[0].w is (32, 16): output dim divides mp=2 -> column split.
        spec = loaded.params["mlp"][0]["w"].sharding.spec
        assert spec == P(None, MODEL_AXIS)
        arrays = _arrays(20, seed=6)
        got = batcher.submit(loaded, arrays).result(timeout=120)
        np.testing.assert_allclose(
            got["prediction_node"], _golden(sv, arrays), rtol=1e-5
        )
    finally:
        batcher.stop()


def test_mesh_config_validation_and_parse(tmp_path):
    with pytest.raises(ValueError, match="not divisible"):
        MeshConfig(enabled=True, devices=6, model_parallel=4)
    with pytest.raises(ValueError, match="non-negative"):
        MeshConfig(devices=-1)
    toml = tmp_path / "cfg.toml"
    toml.write_text(
        "[mesh]\nenabled = true\ndevices = 8\nmodel_parallel = 2\n"
        "tensor_parallel = false\n"
    )
    cfgs = load_config(str(toml))
    mc = cfgs["mesh"]
    assert mc.enabled and mc.devices == 8 and mc.model_parallel == 2
    # Absent section parses to the disabled default (behavior unchanged).
    toml2 = tmp_path / "plain.toml"
    toml2.write_text("[server]\nport = 9999\n")
    assert load_config(str(toml2))["mesh"].enabled is False


def test_mesh_prometheus_series():
    from distributed_tf_serving_tpu.utils.metrics import ServerMetrics

    mesh = make_mesh(8, model_parallel=2)
    sv = _servable()
    ex = ShardedExecutor(mesh)
    ex(sv, _prepared(_arrays(10, seed=3)))  # one padded batch
    snap = ex.snapshot()
    snap["per_device"] = {d: {"busy_fraction": 0.5} for d in snap["devices"]}
    text = ServerMetrics().prometheus_text(mesh=snap)
    assert "dts_tpu_mesh_devices 8" in text
    assert "dts_tpu_mesh_data_parallel 4" in text
    assert "dts_tpu_mesh_model_parallel 2" in text
    assert "dts_tpu_mesh_pad_batches_total 1" in text
    assert text.count("dts_tpu_mesh_device_busy_fraction{") == 8


def test_utilization_per_device_attribution():
    from distributed_tf_serving_tpu.serving.utilization import OccupancyLedger

    t = [0.0]
    ledger = OccupancyLedger(clock=lambda: t[0])
    ledger.devices = ["dev:0", "dev:1"]
    t[0] = 1.0
    ledger.note_batch(0.2, 0.8, 1.0, bucket=32, candidates=20, d2h_wait_s=0.1)
    snap = ledger.snapshot(window_s=2.0)
    assert snap["devices"] == ["dev:0", "dev:1"]
    assert set(snap["per_device"]) == {"dev:0", "dev:1"}
    assert snap["per_device"]["dev:0"]["busy_fraction"] > 0
    assert snap["occupancy_attribution"] == "spmd_uniform"
    events = ledger.chrome_counter_events(0.0, pid=1)
    names = {
        e["args"]["name"] for e in events if e["name"] == "thread_name"
    }
    assert names == {"dev:0", "dev:1"}
    # Counter events ride both tracks with non-decreasing ts per track.
    for tid in (0, 1):
        ts = [e["ts"] for e in events if e.get("ph") == "C" and e["tid"] == tid]
        assert ts and ts == sorted(ts)


# ------------------------------------------------- affinity placement


def test_jump_hash_consistency():
    from distributed_tf_serving_tpu.client import jump_hash

    # Deterministic, in range, and consistent: growing n -> n+1 remaps
    # only a minority of keys (the property the policy exists for).
    keys = [int.from_bytes(np.random.RandomState(0).bytes(8), "big")
            for _ in range(500)]
    a3 = [jump_hash(k, 3) for k in keys]
    assert a3 == [jump_hash(k, 3) for k in keys]
    assert set(a3) <= {0, 1, 2}
    a4 = [jump_hash(k, 4) for k in keys]
    moved = sum(1 for x, y in zip(a3, a4) if x != y)
    assert moved < len(keys) * 0.5  # ~1/4 expected; never a full reshuffle


def test_affinity_groups_partition_rows_exactly_once():
    from distributed_tf_serving_tpu.client import affinity_groups

    arrays = _arrays(64, seed=9)
    groups = affinity_groups(arrays, 3)
    all_idx = np.sort(np.concatenate([idx for _h, idx, _s in groups]))
    np.testing.assert_array_equal(all_idx, np.arange(64))
    for host, idx, sub in groups:
        assert 0 <= host < 3
        np.testing.assert_array_equal(sub["feat_ids"], arrays["feat_ids"][idx])
    # Identical rows hash identically -> identical home backend.
    dup = {k: np.concatenate([v[:1]] * 8) for k, v in arrays.items()}
    dup_groups = affinity_groups(dup, 3)
    assert len(dup_groups) == 1 and dup_groups[0][1].size == 8


def test_index_runs():
    from distributed_tf_serving_tpu.client import index_runs

    assert index_runs(np.asarray([], np.int64)) == ()
    assert index_runs(np.asarray([3])) == ((3, 4),)
    assert index_runs(np.asarray([0, 1, 2, 7, 9, 10])) == ((0, 3), (7, 8), (9, 11))


def test_affinity_predict_scatters_back_in_order():
    """Stubbed-shard affinity predict: groups go to their affine home
    host and the merged vector comes back in ORIGINAL candidate order —
    identical to what the contiguous split would score."""
    import asyncio

    from distributed_tf_serving_tpu.client import (
        affinity_groups,
        client_from_config,
    )
    from distributed_tf_serving_tpu.utils import ClientConfig

    arrays = _arrays(24, seed=13)
    groups = affinity_groups(arrays, 2)
    homes = {}

    async def go():
        cfg = ClientConfig(hosts=("h1", "h2"), placement="affinity")
        client = client_from_config(cfg)
        assert client.placement == "affinity"

        async def fake_shard(i, shard, rr, budget=None):
            # Score = the row's first feature weight: position-independent,
            # so scatter correctness is directly observable.
            homes.setdefault(i, 0)
            homes[i] += 1
            return shard["feat_wts"][:, 0].astype(np.float32)

        client._predict_shard = fake_shard
        merged = await client.predict(arrays)
        await client.close()
        return merged

    merged = asyncio.run(go())
    np.testing.assert_array_equal(
        merged, arrays["feat_wts"][:, 0].astype(np.float32)
    )
    # Every non-empty group was sent once, addressed to its affine home.
    assert sorted(homes) == sorted({h for h, _i, _s in groups})


def test_affinity_partial_results_degrade_with_scattered_ranges():
    import asyncio

    from distributed_tf_serving_tpu.client import (
        PredictClientError,
        affinity_groups,
        client_from_config,
        index_runs,
    )
    from distributed_tf_serving_tpu.utils import ClientConfig

    arrays = _arrays(24, seed=17)
    groups = affinity_groups(arrays, 2)
    assert len(groups) == 2
    dead_host = groups[0][0]

    async def go():
        cfg = ClientConfig(
            hosts=("h1", "h2"), placement="affinity", partial_results=True,
        )
        client = client_from_config(cfg)

        async def fake_shard(i, shard, rr, budget=None):
            if i == dead_host:
                raise PredictClientError("h-dead", None, "down")
            return shard["feat_wts"][:, 0].astype(np.float32)

        client._predict_shard = fake_shard
        result = await client.predict(arrays)
        await client.close()
        return result

    result = asyncio.run(go())
    assert result.degraded
    assert result.missing_ranges == index_runs(groups[0][1])
    surviving = np.sort(np.concatenate(
        [idx for h, idx, _s in groups if h != dead_host]
    ))
    np.testing.assert_array_equal(
        result.scores, arrays["feat_wts"][surviving, 0].astype(np.float32)
    )


def test_affinity_placement_config_validation():
    from distributed_tf_serving_tpu.client import ShardedPredictClient

    with pytest.raises(ValueError, match="placement"):
        ShardedPredictClient(["h1"], placement="nearest")
