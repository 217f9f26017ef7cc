"""Serving-stack integration tests (SURVEY.md §4 integration strategy):
in-process gRPC servers + stub models, golden-score checks vs eager JAX,
model/version/signature resolution, error codes, the Example RPC path, and
the fan-out client against a 3-backend set — the role the reference validated
only manually against lab hosts (DCNClient.java:38)."""

import asyncio

import grpc
import jax
import numpy as np
import pytest

from distributed_tf_serving_tpu import codec
from distributed_tf_serving_tpu.client import (
    ShardedPredictClient,
    build_predict_request,
    make_payload,
    predict_sync,
    run_closed_loop,
)
from distributed_tf_serving_tpu.models import (
    ModelConfig,
    Servable,
    ServableRegistry,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.proto import PredictionServiceStub
from distributed_tf_serving_tpu.proto import serving_apis_pb2 as apis
from distributed_tf_serving_tpu.serving import (
    DynamicBatcher,
    PredictionServiceImpl,
    ServiceError,
    create_server,
    make_example,
)
from distributed_tf_serving_tpu.serving.batcher import fold_ids_host

CFG = ModelConfig(
    num_fields=8, vocab_size=1009, embed_dim=4, mlp_dims=(16,), num_cross_layers=1,
    compute_dtype="float32",
)


def _servable(version=1, seed=0):
    model = build_model("dcn_v2", CFG)
    return Servable(
        name="DCN", version=version, model=model,
        params=model.init(jax.random.PRNGKey(seed)),
        signatures=ctr_signatures(CFG.num_fields),
    )


@pytest.fixture(scope="module")
def stack():
    registry = ServableRegistry()
    registry.load(_servable(version=1, seed=0))
    registry.load(_servable(version=3, seed=1))
    batcher = DynamicBatcher(buckets=(32, 128), max_wait_us=0).start()
    impl = PredictionServiceImpl(registry, batcher)
    server, port = create_server(impl, "127.0.0.1:0")
    server.start()
    yield registry, impl, port
    server.stop(0)
    batcher.stop()


def _arrays(n=10, seed=3):
    rng = np.random.RandomState(seed)
    return {
        "feat_ids": rng.randint(0, 1 << 40, size=(n, CFG.num_fields)).astype(np.int64),
        "feat_wts": rng.rand(n, CFG.num_fields).astype(np.float32),
    }


def _golden(servable, arrays):
    batch = {
        "feat_ids": fold_ids_host(arrays["feat_ids"], CFG.vocab_size),
        "feat_wts": arrays["feat_wts"],
    }
    return np.asarray(servable.model.apply(servable.params, batch)["prediction_node"])


# ------------------------------------------------------------------ Predict


def test_predict_golden_scores(stack):
    registry, impl, port = stack
    arrays = _arrays()
    resp = impl.predict(build_predict_request(arrays, "DCN"))
    got = codec.to_ndarray(resp.outputs["prediction_node"])
    np.testing.assert_allclose(got, _golden(registry.resolve("DCN"), arrays), rtol=1e-6)
    assert resp.model_spec.name == "DCN"
    assert resp.model_spec.version.value == 3  # latest


def test_predict_version_pinning(stack):
    registry, impl, _ = stack
    arrays = _arrays()
    r1 = impl.predict(build_predict_request(arrays, "DCN", version=1))
    r3 = impl.predict(build_predict_request(arrays, "DCN", version=3))
    assert r1.model_spec.version.value == 1
    a1 = codec.to_ndarray(r1.outputs["prediction_node"])
    a3 = codec.to_ndarray(r3.outputs["prediction_node"])
    assert not np.allclose(a1, a3)  # different param seeds
    np.testing.assert_allclose(a1, _golden(registry.resolve("DCN", 1), arrays), rtol=1e-6)


def test_predict_version_label_routing(stack):
    """ModelSpec.version_label (upstream model.proto field 4) resolves to
    the labeled version; retargeting the label is the blue-green flip."""
    registry, impl, _ = stack
    registry.set_label("DCN", "stable", 1)
    registry.set_label("DCN", "canary", 3)
    arrays = _arrays()
    req = build_predict_request(arrays, "DCN")
    req.model_spec.version_label = "stable"
    r = impl.predict(req)
    assert r.model_spec.version.value == 1  # echoes the RESOLVED version
    np.testing.assert_allclose(
        codec.to_ndarray(r.outputs["prediction_node"]),
        _golden(registry.resolve("DCN", 1), arrays), rtol=1e-6,
    )
    registry.set_label("DCN", "stable", 3)  # the flip: no client change
    assert impl.predict(req).model_spec.version.value == 3
    registry.set_label("DCN", "stable", 1)  # restore for other tests


def test_client_routes_by_version_label(stack):
    """ShardedPredictClient(version_label=...) resolves the labeled version
    over the wire, on both the per-call and prepared-bytes paths."""
    import asyncio

    from distributed_tf_serving_tpu.client import ShardedPredictClient

    registry, _impl, port = stack
    registry.set_label("DCN", "client_label", 1)
    arrays = _arrays(seed=21)
    want = np.sort(_golden(registry.resolve("DCN", 1), arrays))

    async def go():
        async with ShardedPredictClient(
            [f"127.0.0.1:{port}"], "DCN", version_label="client_label"
        ) as c:
            live = await c.predict(arrays, sort_scores=True)
            prepared = await c.predict_prepared(c.prepare(arrays), sort_scores=True)
            return live, prepared

    live, prepared = asyncio.run(go())
    np.testing.assert_allclose(live, want, rtol=1e-6)
    np.testing.assert_allclose(prepared, want, rtol=1e-6)

    with pytest.raises(ValueError, match="oneof"):
        build_predict_request(arrays, "DCN", version=1, version_label="x")


def test_version_label_errors(stack):
    registry, impl, _ = stack
    req = build_predict_request(_arrays(), "DCN")
    req.model_spec.version_label = "nope"
    with pytest.raises(ServiceError) as e:
        impl.predict(req)
    assert e.value.code == "NOT_FOUND"

    # version AND label together violate the upstream oneof.
    both = build_predict_request(_arrays(), "DCN", version=1)
    both.model_spec.version_label = "stable"
    with pytest.raises(ServiceError) as e2:
        impl.predict(both)
    assert e2.value.code == "INVALID_ARGUMENT"

    # Labels may only name LOADED versions (config typos fail at
    # assignment time, not at request time).
    from distributed_tf_serving_tpu.models.registry import VersionNotFoundError

    with pytest.raises(VersionNotFoundError):
        registry.set_label("DCN", "broken", 99)


def _example_request(cls, seed):
    rng = np.random.RandomState(seed)
    req = cls()
    req.model_spec.name = "DCN"
    for _ in range(3):
        req.input.example_list.examples.append(make_example(
            rng.randint(0, 1 << 40, size=CFG.num_fields).astype(np.int64),
            rng.rand(CFG.num_fields).astype(np.float32),
        ))
    return req


_COROUTINE_VARIANTS = {
    "predict": (
        lambda seed: build_predict_request(_arrays(seed=seed), "DCN"),
        lambda resp: codec.to_ndarray(resp.outputs["prediction_node"]),
    ),
    "classify": (
        lambda seed: _example_request(apis.ClassificationRequest, seed),
        lambda resp: [c.classes[1].score for c in resp.result.classifications],
    ),
    "regress": (
        lambda seed: _example_request(apis.RegressionRequest, seed),
        lambda resp: [r.value for r in resp.result.regressions],
    ),
}


@pytest.mark.parametrize("method", sorted(_COROUTINE_VARIANTS))
def test_coroutine_variant_matches_sync_method(stack, method):
    """The `_async` variant of each method (what the REST gateway's event
    loop rides): several calls awaiting the batcher on ONE loop thread give
    the sync method's scores."""
    _registry, impl, _port = stack
    make, scores = _COROUTINE_VARIANTS[method]
    requests = [make(seed) for seed in (31, 32, 33)]
    want = [scores(getattr(impl, method)(req)) for req in requests]

    async def go():
        variant = getattr(impl, f"{method}_async")
        return await asyncio.gather(*(variant(req) for req in requests))

    for resp, expected in zip(asyncio.run(go()), want):
        np.testing.assert_allclose(scores(resp), expected, rtol=1e-6)


def test_model_service_get_model_status(stack):
    """tensorflow.serving.ModelService/GetModelStatus over the wire: all
    loaded versions AVAILABLE, version/label pinning, NOT_FOUND classification."""
    registry, _impl, port = stack
    from distributed_tf_serving_tpu.proto import ModelServiceStub

    registry.set_label("DCN", "status_label", 1)
    with grpc.insecure_channel(f"127.0.0.1:{port}") as ch:
        stub = ModelServiceStub(ch)
        req = apis.GetModelStatusRequest()
        req.model_spec.name = "DCN"
        resp = stub.GetModelStatus(req, timeout=30)
        assert [s.version for s in resp.model_version_status] == [1, 3]
        assert all(
            s.state == apis.ModelVersionStatus.AVAILABLE
            and s.status.error_code == 0
            for s in resp.model_version_status
        )

        req.model_spec.version.value = 3
        resp = stub.GetModelStatus(req, timeout=30)
        assert [s.version for s in resp.model_version_status] == [3]

        req.model_spec.ClearField("version")
        req.model_spec.version_label = "status_label"
        resp = stub.GetModelStatus(req, timeout=30)
        assert [s.version for s in resp.model_version_status] == [1]

        req.model_spec.name = "NOPE"
        req.model_spec.ClearField("version_label")
        with pytest.raises(grpc.RpcError) as e:
            stub.GetModelStatus(req, timeout=30)
        assert e.value.code() == grpc.StatusCode.NOT_FOUND


def test_model_service_reload_config_label_flip(stack):
    """HandleReloadConfigRequest retargets version labels over the wire —
    the blue-green flip — atomically: a request with any invalid label
    applies nothing."""
    registry, impl, port = stack
    from distributed_tf_serving_tpu.proto import ModelServiceStub

    registry.set_label("DCN", "reload_label", 1)
    with grpc.insecure_channel(f"127.0.0.1:{port}") as ch:
        stub = ModelServiceStub(ch)
        req = apis.ReloadConfigRequest()
        mc = req.config.model_config_list.config.add()
        mc.name = "DCN"
        mc.version_labels["reload_label"] = 3
        resp = stub.HandleReloadConfigRequest(req, timeout=30)
        assert resp.status.error_code == 0
        # DECLARATIVE: the supplied map IS the label state — labels from
        # earlier tests/assignments absent from it are unassigned (upstream
        # reload semantics; dropping a finished canary is one request).
        assert registry.labels("DCN") == {"reload_label": 3}

        # Routed traffic follows the flip.
        preq = build_predict_request(_arrays(), "DCN")
        preq.model_spec.version_label = "reload_label"
        assert impl.predict(preq).model_spec.version.value == 3

        # Atomicity: one good + one bad label -> FAILED_PRECONDITION and
        # NOTHING applied (the good label must not move).
        bad = apis.ReloadConfigRequest()
        mc = bad.config.model_config_list.config.add()
        mc.name = "DCN"
        mc.version_labels["reload_label"] = 1
        mc.version_labels["zz_broken"] = 99
        with pytest.raises(grpc.RpcError) as e:
            stub.HandleReloadConfigRequest(bad, timeout=30)
        assert e.value.code() == grpc.StatusCode.FAILED_PRECONDITION
        assert registry.labels("DCN")["reload_label"] == 3  # unchanged
        assert "zz_broken" not in registry.labels("DCN")

        # Unknown model -> NOT_FOUND; custom config -> INVALID_ARGUMENT.
        unknown = apis.ReloadConfigRequest()
        unknown.config.model_config_list.config.add().name = "NOPE"
        with pytest.raises(grpc.RpcError) as e:
            stub.HandleReloadConfigRequest(unknown, timeout=30)
        assert e.value.code() == grpc.StatusCode.NOT_FOUND

        custom = apis.ReloadConfigRequest()
        custom.config.custom_model_config.type_url = "type.googleapis.com/x"
        with pytest.raises(grpc.RpcError) as e:
            stub.HandleReloadConfigRequest(custom, timeout=30)
        assert e.value.code() == grpc.StatusCode.INVALID_ARGUMENT

        # base_path in single-model mode: a config RE-STATING the served
        # source is a legal label flip; an actual MOVE is an explicit
        # FAILED_PRECONDITION, never a silent OK.
        impl.served_sources["DCN"] = ("/models/dcn", "dcn_v2")
        try:
            restate = apis.ReloadConfigRequest()
            mc = restate.config.model_config_list.config.add()
            mc.name = "DCN"
            mc.base_path = "/models/dcn"
            mc.version_labels["reload_label"] = 3
            assert stub.HandleReloadConfigRequest(
                restate, timeout=30
            ).status.error_code == 0
            assert registry.labels("DCN") == {"reload_label": 3}

            moved = apis.ReloadConfigRequest()
            mc = moved.config.model_config_list.config.add()
            mc.name = "DCN"
            mc.base_path = "/models/somewhere-else"
            with pytest.raises(grpc.RpcError) as e:
                stub.HandleReloadConfigRequest(moved, timeout=30)
            assert e.value.code() == grpc.StatusCode.FAILED_PRECONDITION
            assert "model-config-file" in e.value.details()
        finally:
            impl.served_sources.clear()

        # Empty-string label key (legal proto3 map key, malformed request):
        # INVALID_ARGUMENT, not INTERNAL.
        empty = apis.ReloadConfigRequest()
        mc = empty.config.model_config_list.config.add()
        mc.name = "DCN"
        mc.version_labels[""] = 1
        with pytest.raises(grpc.RpcError) as e:
            stub.HandleReloadConfigRequest(empty, timeout=30)
        assert e.value.code() == grpc.StatusCode.INVALID_ARGUMENT


def test_unload_drops_labels():
    registry = ServableRegistry()
    registry.load(_servable(version=1, seed=0))
    registry.load(_servable(version=2, seed=1))
    registry.set_label("DCN", "stable", 1)
    registry.unload("DCN", 1)
    assert registry.labels("DCN") == {}  # no dangling label
    registry.set_label("DCN", "stable", 2)
    registry.unload("DCN")
    from distributed_tf_serving_tpu.models.registry import ModelNotFoundError

    with pytest.raises(ModelNotFoundError):
        registry.resolve("DCN", label="stable")


def test_predict_output_filter(stack):
    _, impl, _ = stack
    resp = impl.predict(build_predict_request(_arrays(), "DCN", output_filter=("logits",)))
    assert set(resp.outputs) == {"logits"}


def test_predict_repeated_field_encoding(stack):
    """The grpc-java encoding path (int64_val/float_val, DCNClient.java:98-108)
    must produce identical scores to tensor_content."""
    _, impl, _ = stack
    arrays = _arrays()
    a = impl.predict(build_predict_request(arrays, "DCN", use_tensor_content=True))
    b = impl.predict(build_predict_request(arrays, "DCN", use_tensor_content=False))
    np.testing.assert_array_equal(
        codec.to_ndarray(a.outputs["prediction_node"]),
        codec.to_ndarray(b.outputs["prediction_node"]),
    )


@pytest.mark.parametrize(
    "mutate,code",
    [
        (lambda r: r.model_spec.ClearField("name"), "INVALID_ARGUMENT"),
        (lambda r: setattr(r.model_spec, "name", "nope"), "NOT_FOUND"),
        (lambda r: setattr(r.model_spec.version, "value", 99), "NOT_FOUND"),
        (lambda r: setattr(r.model_spec, "signature_name", "nope"), "NOT_FOUND"),
        (lambda r: r.inputs["feat_ids"].int64_val.append(0), "INVALID_ARGUMENT"),
        (lambda r: r.inputs.pop("feat_wts"), "INVALID_ARGUMENT"),
        (lambda r: r.output_filter.append("nope"), "INVALID_ARGUMENT"),
    ],
    ids=["no-name", "unknown-model", "unknown-version", "unknown-signature",
         "corrupt-tensor", "missing-input", "bad-filter"],
)
def test_predict_errors(stack, mutate, code):
    _, impl, _ = stack
    req = build_predict_request(_arrays(), "DCN", use_tensor_content=False)
    mutate(req)
    with pytest.raises(ServiceError) as ei:
        impl.predict(req)
    assert ei.value.code == code


def test_predict_on_classify_signature_rejected(stack):
    """The classify/regress signatures declare outputs the raw model doesn't
    produce; Predict against them must be a clean client error, not an empty
    response."""
    _, impl, _ = stack
    req = build_predict_request(_arrays(), "DCN", signature_name="classify")
    with pytest.raises(ServiceError) as ei:
        impl.predict(req)
    assert ei.value.code == "INVALID_ARGUMENT"
    assert "Predict" in str(ei.value)


def test_wrong_dtype_rejected(stack):
    _, impl, _ = stack
    arrays = _arrays()
    arrays["feat_wts"] = arrays["feat_wts"].astype(np.float64)
    req = build_predict_request(arrays, "DCN")
    with pytest.raises(ServiceError, match="dtype"):
        impl.predict(req)


def test_wrong_field_count_rejected(stack):
    _, impl, _ = stack
    rng = np.random.RandomState(0)
    arrays = {
        "feat_ids": rng.randint(0, 100, size=(4, 5)).astype(np.int64),
        "feat_wts": rng.rand(4, 5).astype(np.float32),
    }
    with pytest.raises(ServiceError, match="shape"):
        impl.predict(build_predict_request(arrays, "DCN"))


# ----------------------------------------------------- Example path RPCs


def _example_input(n=6, seed=5):
    arrays = _arrays(n, seed)
    inp = apis.Input()
    for i in range(n):
        inp.example_list.examples.append(
            make_example(arrays["feat_ids"][i], arrays["feat_wts"][i])
        )
    return arrays, inp


def test_classify(stack):
    registry, impl, _ = stack
    arrays, inp = _example_input()
    req = apis.ClassificationRequest(input=inp)
    req.model_spec.name = "DCN"
    resp = impl.classify(req)
    want = _golden(registry.resolve("DCN"), arrays)
    assert len(resp.result.classifications) == 6
    for cls, p in zip(resp.result.classifications, want):
        assert cls.classes[1].label == "1"
        assert cls.classes[1].score == pytest.approx(p, rel=1e-5)
        assert cls.classes[0].score + cls.classes[1].score == pytest.approx(1.0, abs=1e-5)


def test_regress(stack):
    registry, impl, _ = stack
    arrays, inp = _example_input()
    req = apis.RegressionRequest(input=inp)
    req.model_spec.name = "DCN"
    resp = impl.regress(req)
    want = _golden(registry.resolve("DCN"), arrays)
    got = np.array([r.value for r in resp.result.regressions])
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_multi_inference(stack):
    _, impl, _ = stack
    _, inp = _example_input()
    req = apis.MultiInferenceRequest(input=inp)
    t1 = req.tasks.add(method_name="tensorflow/serving/classify")
    t1.model_spec.name = "DCN"
    t2 = req.tasks.add(method_name="tensorflow/serving/regress")
    t2.model_spec.name = "DCN"
    resp = impl.multi_inference(req)
    assert len(resp.results) == 2
    assert resp.results[0].WhichOneof("result") == "classification_result"
    assert resp.results[1].WhichOneof("result") == "regression_result"


def test_example_with_context(stack):
    """Context features fill gaps (two-tower pattern): examples carry only
    ids, context carries the weights."""
    registry, impl, _ = stack
    arrays = _arrays(3, seed=9)
    shared_wts = arrays["feat_wts"][0]
    inp = apis.Input()
    for i in range(3):
        inp.example_list_with_context.examples.append(make_example(arrays["feat_ids"][i]))
    inp.example_list_with_context.context.CopyFrom(make_example([], shared_wts))
    inp.example_list_with_context.context.features.feature["feat_ids"].Clear()
    req = apis.RegressionRequest(input=inp)
    req.model_spec.name = "DCN"
    resp = impl.regress(req)
    want_arrays = {
        "feat_ids": arrays["feat_ids"],
        "feat_wts": np.broadcast_to(shared_wts, arrays["feat_ids"].shape).copy(),
    }
    want = _golden(registry.resolve("DCN"), want_arrays)
    got = np.array([r.value for r in resp.result.regressions])
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_bad_example_rejected(stack):
    _, impl, _ = stack
    inp = apis.Input()
    inp.example_list.examples.append(make_example([1, 2]))  # wrong field count
    req = apis.ClassificationRequest(input=inp)
    req.model_spec.name = "DCN"
    with pytest.raises(ServiceError) as ei:
        impl.classify(req)
    assert ei.value.code == "INVALID_ARGUMENT"


# ------------------------------------------------------- GetModelMetadata


def test_get_model_metadata(stack):
    _, impl, _ = stack
    req = apis.GetModelMetadataRequest()
    req.model_spec.name = "DCN"
    req.metadata_field.append("signature_def")
    resp = impl.get_model_metadata(req)
    assert resp.model_spec.version.value == 3
    sig_map = apis.SignatureDefMap()
    assert resp.metadata["signature_def"].Unpack(sig_map)
    sd = sig_map.signature_def["serving_default"]
    assert sd.method_name == "tensorflow/serving/predict"
    assert sd.inputs["feat_ids"].dtype == 9  # DT_INT64
    assert [d.size for d in sd.inputs["feat_ids"].tensor_shape.dim] == [-1, 8]
    assert "prediction_node" in sd.outputs


# ------------------------------------------------------------ gRPC socket


def test_grpc_socket_roundtrip_and_status_codes(stack):
    _, _, port = stack
    out = predict_sync(f"127.0.0.1:{port}", _arrays(), "DCN")
    assert out["prediction_node"].shape == (10,)

    with grpc.insecure_channel(f"127.0.0.1:{port}") as ch:
        stub = PredictionServiceStub(ch)
        req = build_predict_request(_arrays(), "unknown-model")
        with pytest.raises(grpc.RpcError) as ei:
            stub.Predict(req, timeout=10)
        assert ei.value.code() == grpc.StatusCode.NOT_FOUND

        bad = build_predict_request(_arrays(), "DCN", use_tensor_content=False)
        bad.inputs["feat_ids"].int64_val.append(0)
        with pytest.raises(grpc.RpcError) as ei:
            stub.Predict(bad, timeout=10)
        assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT


# ------------------------------------------------- fan-out client (3 hosts)


@pytest.fixture(scope="module")
def three_backends():
    """Three independent in-process servers sharing one param seed — the
    fake-backend stand-in for the reference's three lab hosts."""
    servers, hosts = [], []
    batchers = []
    for _ in range(3):
        registry = ServableRegistry()
        registry.load(_servable(version=1, seed=0))
        batcher = DynamicBatcher(buckets=(32, 128), max_wait_us=0).start()
        impl = PredictionServiceImpl(registry, batcher)
        server, port = create_server(impl, "127.0.0.1:0")
        server.start()
        servers.append(server)
        batchers.append(batcher)
        hosts.append(f"127.0.0.1:{port}")
    yield hosts
    for s in servers:
        s.stop(0)
    for b in batchers:
        b.stop()


def test_fanout_merge_order_and_sort(three_backends):
    """Host-order merge must equal the unsharded scores (DCNClient.java:161-164
    semantics); sort_scores reproduces the ranking step (DCNClient.java:195)."""
    servable = _servable(version=1, seed=0)
    arrays = _arrays(n=10, seed=11)
    want = _golden(servable, arrays)

    async def go():
        async with ShardedPredictClient(three_backends, "DCN") as client:
            merged = await client.predict(arrays)
            ranked = await client.predict(arrays, sort_scores=True)
            return merged, ranked

    merged, ranked = asyncio.run(go())
    np.testing.assert_allclose(merged, want, rtol=1e-6)
    # rtol (not bitwise): row position inside the padded bucket shifts SIMD
    # lane grouping on CPU, perturbing the last ulp.
    np.testing.assert_allclose(ranked, np.sort(want), rtol=1e-6)


def test_closed_loop_bench_smoke(three_backends):
    payload = make_payload(candidates=30, num_fields=CFG.num_fields)

    async def go():
        async with ShardedPredictClient(three_backends, "DCN") as client:
            return await run_closed_loop(
                client, payload, concurrency=2, requests_per_worker=5, warmup_requests=1
            )

    report = asyncio.run(go())
    s = report.summary()
    assert s["requests"] == 10
    assert s["candidates_per_request"] == 30
    assert s["p99_ms"] >= s["p50_ms"] > 0
    assert s["qps"] > 0


def test_fanout_failure_is_typed(three_backends):
    from distributed_tf_serving_tpu.client import PredictClientError

    hosts = list(three_backends[:2]) + ["127.0.0.1:1"]  # dead backend

    async def go():
        async with ShardedPredictClient(hosts, "DCN", timeout_s=2.0) as client:
            await client.predict(_arrays(n=9))

    with pytest.raises(PredictClientError) as ei:
        asyncio.run(go())
    assert ei.value.host == "127.0.0.1:1"


def test_channels_per_host_stripes_and_scores(three_backends):
    """channels_per_host multiplies HTTP/2 connections, not semantics:
    scores must equal the single-channel client's."""
    servable = _servable(version=1, seed=0)
    arrays = _arrays(n=10, seed=13)
    want = _golden(servable, arrays)

    async def go():
        async with ShardedPredictClient(
            three_backends, "DCN", channels_per_host=3
        ) as client:
            return [await client.predict(arrays) for _ in range(4)]

    for merged in asyncio.run(go()):
        np.testing.assert_allclose(merged, want, rtol=1e-6)


def test_closed_loop_mp_smoke(three_backends):
    """Spawn-context load generators: end-to-end report over a real socket.
    Single process x small load — the multi-core fan-out is exercised on
    real hosts."""
    from distributed_tf_serving_tpu.client import run_closed_loop_mp

    payload = make_payload(candidates=12, num_fields=CFG.num_fields)
    report = run_closed_loop_mp(
        list(three_backends), payload, model_name="DCN",
        processes=1, concurrency=2, requests_per_worker=2, warmup_requests=1,
    )
    s = report.summary()
    assert s["requests"] == 4
    assert s["qps"] > 0 and s["p99_ms"] >= s["p50_ms"] > 0


def test_fanout_failover_reroutes_dead_shard(three_backends):
    """Beyond the reference (whose async mode let a dead host kill the load
    thread, DCNClient.java:158-159): with failover_attempts, the shard whose
    home backend is dead reroutes to the next host — scores AND merge order
    must equal the all-healthy fan-out."""
    servable = _servable(version=1, seed=0)
    arrays = _arrays(n=9, seed=21)
    want = _golden(servable, arrays)

    hosts = ["127.0.0.1:1"] + list(three_backends[:2])  # shard 0's home is dead

    async def go():
        async with ShardedPredictClient(
            hosts, "DCN", timeout_s=2.0, failover_attempts=1
        ) as client:
            return await client.predict(arrays)

    merged = asyncio.run(go())
    np.testing.assert_allclose(merged, want, rtol=1e-6)


def test_fanout_failover_does_not_retry_deterministic_errors():
    """INVALID_ARGUMENT/NOT_FOUND would fail identically on every backend:
    failover must raise immediately, not burn attempts — pinned by the
    server's own RPC counter (exactly ONE Predict arrives despite
    failover_attempts=2)."""
    from distributed_tf_serving_tpu.client import PredictClientError
    from distributed_tf_serving_tpu.utils.metrics import ServerMetrics

    registry = ServableRegistry()
    registry.load(_servable(version=1, seed=0))
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0).start()
    metrics = ServerMetrics()
    server, port = create_server(
        PredictionServiceImpl(registry, batcher), "127.0.0.1:0", metrics=metrics
    )
    server.start()
    try:
        host = f"127.0.0.1:{port}"

        async def go():
            async with ShardedPredictClient(
                [host], "NOSUCH", timeout_s=2.0, failover_attempts=2
            ) as client:
                await client.predict(_arrays(n=9))

        with pytest.raises(PredictClientError) as ei:
            asyncio.run(go())
        assert getattr(ei.value.code, "name", "") == "NOT_FOUND"
        assert ei.value.host == host
        snap = metrics.snapshot()["rpcs"]["Predict"]
        assert snap["errors"] + snap["ok"] == 1  # no attempts were burned
    finally:
        server.stop(0)
        batcher.stop()


def test_fanout_failover_exhaustion_raises_last_host():
    """All candidate hosts dead: the raised error stays typed and names the
    LAST host tried. full_async=False makes shard 0's error surface
    deterministically (no gather race): home dead[0], reroutes to dead[1]
    then dead[2] with failover_attempts=2."""
    from distributed_tf_serving_tpu.client import PredictClientError

    dead = ["127.0.0.1:1", "127.0.0.1:2", "127.0.0.1:3"]

    async def go():
        async with ShardedPredictClient(
            dead, "DCN", timeout_s=2.0, failover_attempts=2, full_async=False
        ) as client:
            await client.predict(_arrays(n=9))

    with pytest.raises(PredictClientError) as ei:
        asyncio.run(go())
    assert ei.value.host == dead[2]
    assert getattr(ei.value.code, "name", "") == "UNAVAILABLE"


# ----------------------------------------- who crosses the batcher direct


@pytest.mark.parametrize("caller", ["sync", "coroutine"])
def test_only_a_handler_thread_crosses_the_batcher_direct(caller):
    """Below the load at which batches share anything, a request over the
    gRPC transport is staged by its own handler thread, which would sleep on
    the Future anyway; a coroutine caller (the REST gateway's event loop,
    here `impl.predict_async` on a loop of the test's) never is
    (service._run_async does not say it may block)."""
    registry = ServableRegistry()
    servable = _servable(version=1, seed=0)
    registry.load(servable)
    batcher = DynamicBatcher(buckets=(32, 128), max_wait_us=0).start()
    impl = PredictionServiceImpl(registry, batcher)
    arrays = _arrays(n=10, seed=22)
    want = _golden(servable, arrays)

    def trickle():
        with batcher._cv:  # arrivals far slower than crossings
            batcher._arrival_gap_s, batcher._traversal_s = 1.0, 0.001
            batcher._last_arrival_t = None

    async def go():
        out = []
        for _ in range(3):
            trickle()
            resp = await impl.predict_async(build_predict_request(arrays, "DCN"))
            out.append(codec.to_ndarray(resp.outputs["prediction_node"]))
        return out

    try:
        if caller == "coroutine":
            got = asyncio.run(go())
        else:
            server, port = create_server(impl, "127.0.0.1:0")
            server.start()
            try:
                got = []
                for _ in range(3):
                    trickle()
                    got.append(predict_sync(f"127.0.0.1:{port}", arrays, "DCN")["prediction_node"])
            finally:
                server.stop(0)
        for scores in got:
            np.testing.assert_allclose(scores, want, rtol=1e-6)
        assert batcher.stats.batches == 3
        # The first sync request may find the collector not parked yet.
        assert batcher.stats.direct_batches == 0 if caller == "coroutine" else batcher.stats.direct_batches >= 2
    finally:
        batcher.stop()


def test_prepared_request_against_threaded_server(three_backends):
    """predict_prepared shards/merges exactly like predict() on a 3-host
    fan-out (host-order merge parity), against the classic threaded server."""
    servable = _servable(version=1, seed=0)
    arrays = _arrays(n=10, seed=31)
    want = _golden(servable, arrays)

    async def go():
        async with ShardedPredictClient(three_backends, "DCN") as client:
            prep = client.prepare(arrays)
            assert len(prep.shard_blobs) == 3 and prep.candidates == 10
            return await client.predict_prepared(prep)

    np.testing.assert_allclose(asyncio.run(go()), want, rtol=1e-6)


def test_closed_loop_prepared_mode(three_backends):
    payload = make_payload(candidates=30, num_fields=CFG.num_fields)

    async def go():
        async with ShardedPredictClient(three_backends, "DCN") as client:
            return await run_closed_loop(
                client, payload, concurrency=2, requests_per_worker=3,
                warmup_requests=1, prepared=True,
            )

    report = asyncio.run(go())
    assert report.requests == 6

    async def prepared_pool_rejected():
        async with ShardedPredictClient(three_backends, "DCN") as client:
            await run_closed_loop(
                client, payload, concurrency=1, requests_per_worker=1,
                payload_pool=[payload], prepared=True,
            )

    with pytest.raises(ValueError):
        asyncio.run(prepared_pool_rejected())


# --------------------------------------------------- lane-packed tables


@pytest.mark.parametrize(
    "kind,embed_dim,pack",
    # The two benchmark shapes at a small vocab: DCN-v2's 16-wide rows pack
    # eight to a lane row; DLRM's 128-wide rows are lane rows already.
    [("dcn_v2", 16, 8), ("dlrm", 128, 1)],
    ids=["dcn_v2_d16", "dlrm_d128"],
)
def test_demo_servable_is_served_lane_packed(kind, embed_dim, pack):
    """build_stack's demo servable holds its table in the serving shape,
    the values of the logical init; it scores like the logical tree; the
    runtime block's `embedding_pack` says which shape is held."""
    from distributed_tf_serving_tpu.serving.server import build_stack
    from distributed_tf_serving_tpu.utils.config import ServerConfig

    vocab = 4096
    model_config = ModelConfig(
        name="M", num_fields=8, vocab_size=vocab, embed_dim=embed_dim, mlp_dims=(16,),
        bottom_mlp_dims=(16, embed_dim), num_cross_layers=1,
    )
    cfg = ServerConfig(
        model_kind=kind, model_name="M", num_fields=8, buckets=(16,), warmup=False
    )
    _registry, batcher, impl, sv, _mesh, _watcher = build_stack(cfg, model_config=model_config)
    try:
        table = sv.params["embedding"]
        assert table.shape == (vocab // pack, 128)
        assert sv.embedding_pack == pack
        assert impl.runtime_stats()["embedding_pack"] == {"M:1": pack}
        logical = jax.jit(sv.model.init)(jax.random.PRNGKey(0))
        assert logical["embedding"].shape == (vocab, embed_dim)
        np.testing.assert_array_equal(
            np.asarray(table).reshape(vocab, embed_dim), np.asarray(logical["embedding"])
        )
        rng = np.random.RandomState(4)
        arrays = {
            "feat_ids": rng.randint(0, 1 << 40, size=(11, 8)).astype(np.int64),
            "feat_wts": rng.rand(11, 8).astype(np.float32),
        }
        if kind == "dlrm":
            arrays["dense_features"] = rng.rand(11, 13).astype(np.float32)
        got = batcher.submit(sv, arrays).result(timeout=120)["prediction_node"]
        batch = dict(arrays, feat_ids=fold_ids_host(arrays["feat_ids"], vocab))
        want = jax.jit(sv.model.apply)(logical, batch)["prediction_node"]
        np.testing.assert_allclose(got, np.asarray(want), rtol=1e-6, atol=1e-6)
    finally:
        batcher.stop()
