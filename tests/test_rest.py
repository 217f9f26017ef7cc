"""REST gateway (serving/rest.py): TF-Serving's :8501 surface — row and
columnar predict formats, error classification onto HTTP statuses, status and
metadata routes — over a real aiohttp server, scored against the model's
own forward."""

import asyncio

import numpy as np
import pytest

jax = pytest.importorskip("jax")
aiohttp = pytest.importorskip("aiohttp")

from distributed_tf_serving_tpu import native
from distributed_tf_serving_tpu.models import (
    ModelConfig,
    Servable,
    ServableRegistry,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.serving import DynamicBatcher, PredictionServiceImpl
from distributed_tf_serving_tpu.serving.rest import start_rest_gateway

F = 6
VOCAB = 1 << 12
CFG = ModelConfig(
    name="DCN", num_fields=F, vocab_size=VOCAB, embed_dim=8,
    mlp_dims=(16,), num_cross_layers=2, cross_full_matrix=True,
)


@pytest.fixture(scope="module")
def stack():
    model = build_model("dcn_v2", CFG)
    sv = Servable(
        name="DCN", version=1, model=model,
        params=jax.jit(model.init)(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(F),
    )
    registry = ServableRegistry()
    registry.load(sv)
    batcher = DynamicBatcher(buckets=(32, 64), max_wait_us=0).start()
    impl = PredictionServiceImpl(registry, batcher)
    yield impl, sv
    batcher.stop()


def _native_scores(sv, ids, wts):
    return np.asarray(sv.model.apply(
        sv.params,
        {"feat_ids": native.fold_ids(ids, VOCAB), "feat_wts": wts},
    )["prediction_node"])


def _run(impl, handler):
    async def go():
        runner, port = await start_rest_gateway(impl, port=0)
        try:
            async with aiohttp.ClientSession(
                f"http://127.0.0.1:{port}"
            ) as session:
                return await handler(session)
        finally:
            await runner.cleanup()

    return asyncio.run(go())


def test_predict_instances_row_format(stack):
    impl, sv = stack
    rng = np.random.RandomState(3)
    ids = rng.randint(0, 1 << 40, size=(5, F)).astype(np.int64)
    wts = rng.rand(5, F).astype(np.float32)

    async def handler(session):
        body = {"instances": [
            {"feat_ids": ids[i].tolist(), "feat_wts": wts[i].tolist()}
            for i in range(5)
        ]}
        async with session.post("/v1/models/DCN:predict", json=body) as r:
            assert r.status == 200, await r.text()
            return await r.json()

    out = _run(impl, handler)
    preds = out["predictions"]
    assert len(preds) == 5
    # The signature declares two outputs (prediction_node + logits), so row
    # format yields one object per instance (TF-Serving REST semantics).
    got = np.asarray([p["prediction_node"] for p in preds], np.float32)
    np.testing.assert_allclose(got, _native_scores(sv, ids, wts), rtol=1e-5)


def test_predict_columnar_inputs(stack):
    impl, sv = stack
    rng = np.random.RandomState(4)
    ids = rng.randint(0, 1 << 40, size=(4, F)).astype(np.int64)
    wts = rng.rand(4, F).astype(np.float32)

    async def handler(session):
        body = {"inputs": {"feat_ids": ids.tolist(), "feat_wts": wts.tolist()},
                "signature_name": "serving_default"}
        async with session.post(
            "/v1/models/DCN/versions/1:predict", json=body
        ) as r:
            assert r.status == 200, await r.text()
            return await r.json()

    out = _run(impl, handler)
    got = np.asarray(out["outputs"]["prediction_node"], np.float32)
    np.testing.assert_allclose(got, _native_scores(sv, ids, wts), rtol=1e-5)


def test_error_kinds_maps_to_http(stack):
    impl, _sv = stack

    async def handler(session):
        results = {}
        async with session.post("/v1/models/NOPE:predict",
                                json={"instances": [{"feat_ids": [1] * F,
                                                     "feat_wts": [0.5] * F}]}) as r:
            results["unknown_model"] = (r.status, await r.json())
        async with session.post("/v1/models/DCN:predict",
                                json={"instances": []}) as r:
            results["empty"] = (r.status, await r.json())
        async with session.post("/v1/models/DCN:predict",
                                data=b"not json") as r:
            results["bad_json"] = (r.status, await r.json())
        async with session.post(
            "/v1/models/DCN:predict",
            json={"instances": [{"feat_ids": [1] * F}]}  # missing feat_wts
        ) as r:
            results["missing_input"] = (r.status, await r.json())
        async with session.post(
            "/v1/models/DCN:predict",
            json={"instances": [1], "inputs": {}}
        ) as r:
            results["both_formats"] = (r.status, await r.json())
        async with session.post(
            "/v1/models/DCN/versions/latest:predict",
            json={"instances": [{"feat_ids": [1] * F, "feat_wts": [0.5] * F}]}
        ) as r:
            results["bad_version"] = (r.status, await r.json())
        return results

    res = _run(impl, handler)
    assert res["unknown_model"][0] == 404
    assert res["empty"][0] == 400
    assert res["bad_json"][0] == 400
    assert res["missing_input"][0] == 400
    assert res["both_formats"][0] == 400
    assert res["bad_version"][0] == 400  # not 500: client error classification
    for status, body in res.values():
        assert "error" in body


def test_label_routes(stack):
    """/labels/{l} routes resolve through the registry's label map for all
    three POST verbs; unknown labels take the NOT_FOUND classification."""
    impl, sv = stack
    impl.registry.set_label("DCN", "stable", 1)
    rng = np.random.RandomState(9)
    ids = rng.randint(0, 1 << 40, size=(3, F)).astype(np.int64)
    wts = rng.rand(3, F).astype(np.float32)

    async def handler(session):
        body = {"inputs": {"feat_ids": ids.tolist(), "feat_wts": wts.tolist()}}
        async with session.post("/v1/models/DCN/labels/stable:predict", json=body) as r:
            assert r.status == 200, await r.text()
            pred = np.asarray((await r.json())["outputs"]["prediction_node"], np.float32)
        ex_body = {"examples": [
            {"feat_ids": ids[i].tolist(), "feat_wts": wts[i].tolist()}
            for i in range(3)
        ]}
        async with session.post("/v1/models/DCN/labels/stable:classify", json=ex_body) as r:
            classify_status = r.status
        async with session.post("/v1/models/DCN/labels/stable:regress", json=ex_body) as r:
            regress_status = r.status
        async with session.post("/v1/models/DCN/labels/nope:predict", json=body) as r:
            unknown = (r.status, await r.json())
        return pred, classify_status, regress_status, unknown

    pred, c_status, r_status, unknown = _run(impl, handler)
    np.testing.assert_allclose(pred, _native_scores(sv, ids, wts), rtol=1e-5)
    assert c_status == 200 and r_status == 200
    assert unknown[0] == 404 and "error" in unknown[1]


def test_metadata_version_and_label_variants(stack):
    impl, _sv = stack
    impl.registry.set_label("DCN", "meta_label", 1)

    async def handler(session):
        out = {}
        for path in ("/v1/models/DCN/versions/1/metadata",
                     "/v1/models/DCN/labels/meta_label/metadata"):
            async with session.get(path) as r:
                out[path] = (r.status, await r.json())
        async with session.get("/v1/models/DCN/labels/nope/metadata") as r:
            out["unknown"] = (r.status, await r.json())
        return out

    res = _run(impl, handler)
    for path in ("/v1/models/DCN/versions/1/metadata",
                 "/v1/models/DCN/labels/meta_label/metadata"):
        code, body = res[path]
        assert code == 200
        assert body["model_spec"]["version"] == "1"
        assert "serving_default" in body["metadata"]["signature_def"]["signature_def"]
    assert res["unknown"][0] == 404


def test_metadata_without_serving_default(stack):
    """A model serving purely by explicit signature names (a supported
    import shape) must still answer /metadata with its signature set."""
    import dataclasses as dc

    from distributed_tf_serving_tpu.models import Servable, build_model

    impl, sv = stack
    only_custom = Servable(
        name="CUSTOM_SIG", version=1, model=sv.model, params=sv.params,
        signatures={"score_items": sv.signatures["serving_default"]},
    )
    impl.registry.load(only_custom)
    try:
        async def handler(session):
            async with session.get("/v1/models/CUSTOM_SIG/metadata") as r:
                return r.status, await r.json()

        code, body = _run(impl, handler)
        assert code == 200
        assert list(body["metadata"]["signature_def"]["signature_def"]) == ["score_items"]
    finally:
        impl.registry.unload("CUSTOM_SIG")


def test_status_and_metadata_routes(stack):
    impl, _sv = stack

    async def handler(session):
        async with session.get("/v1/models/DCN") as r:
            status = (r.status, await r.json())
        async with session.get("/v1/models/DCN/metadata") as r:
            meta = (r.status, await r.json())
        async with session.get("/v1/models/NOPE") as r:
            missing = r.status
        # Past-int64 version segment: JSON 400, not a text/plain 500.
        async with session.get(
            "/v1/models/DCN/versions/99999999999999999999"
        ) as r:
            assert r.status == 400 and "error" in await r.json()
        return status, meta, missing

    (s_code, s_body), (m_code, m_body), missing = _run(impl, handler)
    assert s_code == 200
    assert s_body["model_version_status"][0]["state"] == "AVAILABLE"
    assert m_code == 200
    sd = m_body["metadata"]["signature_def"]["signature_def"]
    assert "serving_default" in sd and "classify" in sd
    # Enum by NAME, matching tensorflow_model_server's proto3-JSON output.
    assert sd["serving_default"]["inputs"]["feat_ids"]["dtype"] == "DT_INT64"
    assert missing == 404


def test_classify_route_matches_grpc(stack):
    """REST :classify must produce the same label/score pairs as the gRPC
    Classify RPC fed the equivalent ExampleList (one impl, two surfaces)."""
    impl, _sv = stack
    from distributed_tf_serving_tpu.proto import serving_apis_pb2 as apis
    from distributed_tf_serving_tpu.serving.example_codec import make_example

    rng = np.random.RandomState(11)
    ids = rng.randint(0, 1 << 40, size=(4, F)).astype(np.int64)
    wts = rng.rand(4, F).astype(np.float32)

    req = apis.ClassificationRequest()
    req.model_spec.name = "DCN"
    for i in range(4):
        req.input.example_list.examples.append(make_example(ids[i], wts[i]))
    grpc_out = impl.classify(req)
    grpc_results = [
        [[c.label, c.score] for c in cls.classes]
        for cls in grpc_out.result.classifications
    ]

    async def handler(session):
        body = {"examples": [
            {"feat_ids": ids[i].tolist(), "feat_wts": wts[i].tolist()}
            for i in range(4)
        ]}
        async with session.post("/v1/models/DCN:classify", json=body) as r:
            assert r.status == 200, await r.text()
            return await r.json()

    out = _run(impl, handler)
    assert len(out["results"]) == 4
    for rest_cls, grpc_cls in zip(out["results"], grpc_results):
        assert [c[0] for c in rest_cls] == [c[0] for c in grpc_cls]
        np.testing.assert_allclose(
            [c[1] for c in rest_cls], [c[1] for c in grpc_cls], rtol=1e-6
        )


def test_regress_route_with_context(stack):
    """REST :regress with a shared context Example (feat_wts hoisted into
    the context, per-example feat_ids) matches the gRPC Regress RPC fed
    the equivalent ExampleListWithContext."""
    impl, _sv = stack
    from distributed_tf_serving_tpu.proto import serving_apis_pb2 as apis
    from distributed_tf_serving_tpu.serving.example_codec import make_example

    rng = np.random.RandomState(12)
    ids = rng.randint(0, 1 << 40, size=(3, F)).astype(np.int64)
    ctx_wts = rng.rand(F).astype(np.float32)

    req = apis.RegressionRequest()
    req.model_spec.name = "DCN"
    req.input.example_list_with_context.context.CopyFrom(
        make_example([], ctx_wts)
    )
    del req.input.example_list_with_context.context.features.feature["feat_ids"]
    for i in range(3):
        req.input.example_list_with_context.examples.append(make_example(ids[i]))
    grpc_vals = [r.value for r in impl.regress(req).result.regressions]

    async def handler(session):
        body = {
            "context": {"feat_wts": ctx_wts.tolist()},
            "examples": [{"feat_ids": ids[i].tolist()} for i in range(3)],
        }
        async with session.post("/v1/models/DCN:regress", json=body) as r:
            assert r.status == 200, await r.text()
            return await r.json()

    out = _run(impl, handler)
    np.testing.assert_allclose(out["results"], grpc_vals, rtol=1e-6)


def test_classify_regress_error_kinds(stack):
    impl, _sv = stack

    async def handler(session):
        results = {}
        async with session.post("/v1/models/NOPE:classify",
                                json={"examples": [{"feat_ids": [1] * F}]}) as r:
            results["unknown_model"] = (r.status, await r.json())
        async with session.post("/v1/models/DCN:classify", json={}) as r:
            results["no_examples"] = (r.status, await r.json())
        async with session.post(
            "/v1/models/DCN:regress",
            json={"examples": [{"feat_ids": [1] * (F - 1)}]}  # wrong arity
        ) as r:
            results["bad_arity"] = (r.status, await r.json())
        async with session.post(
            "/v1/models/DCN:classify",
            json={"examples": [{"feat_ids": ["x"] * F}]}  # strings, not ids
        ) as r:
            results["bad_type"] = (r.status, await r.json())
        async with session.post(
            "/v1/models/DCN:classify",
            json={"examples": [{"feat_ids": [1 << 63] * F}]}  # > int64 max
        ) as r:
            results["out_of_range"] = (r.status, await r.json())
        return results

    res = _run(impl, handler)
    assert res["unknown_model"][0] == 404
    assert res["no_examples"][0] == 400
    assert res["bad_arity"][0] == 400
    assert res["bad_type"][0] == 400
    assert res["out_of_range"][0] == 400  # protobuf range error, not a 500
    for _status, body in res.values():
        assert "error" in body


def test_prometheus_monitoring_endpoint(stack):
    """/monitoring/prometheus/metrics serves TF-Serving-named metrics in
    text format 0.0.4: OK and ERROR counters, a monotone latency histogram
    with matching _count, and the batcher gauges."""
    impl, _sv = stack
    ids = np.ones((2, F), np.int64)
    wts = np.ones((2, F), np.float32)

    async def handler(session):
        body = {"inputs": {"feat_ids": ids.tolist(), "feat_wts": wts.tolist()}}
        for _ in range(3):
            async with session.post("/v1/models/DCN:predict", json=body) as r:
                assert r.status == 200
        async with session.post("/v1/models/NOPE:predict", json=body) as r:
            assert r.status == 404
        async with session.get("/monitoring/prometheus/metrics") as r:
            return r.status, r.headers["Content-Type"], await r.text()

    status, ctype, text = _run(impl, handler)
    assert status == 200
    assert "version=0.0.4" in ctype
    ok = err = None
    hist_counts, hist_count_line = [], None
    for ln in text.splitlines():
        if ln.startswith('#'):
            continue
        name, _, value = ln.rpartition(" ")
        if name.startswith(':tensorflow:serving:request_count{entrypoint="REST.Predict"'):
            if 'status="OK"' in name:
                ok = int(value)
            elif 'status="ERROR"' in name:
                err = int(value)
        elif name.startswith(':tensorflow:serving:request_latency_bucket{entrypoint="REST.Predict"'):
            hist_counts.append(int(value))
        elif name.startswith(':tensorflow:serving:request_latency_count{entrypoint="REST.Predict"'):
            hist_count_line = int(value)
    assert ok == 3 and err == 1
    assert hist_counts == sorted(hist_counts)  # cumulative => monotone
    assert hist_counts[-1] == hist_count_line == 4  # +Inf bucket == count
    assert "dts_tpu_batcher_batches_total" in text


def test_rest_and_grpc_same_scores(stack):
    """The REST gateway and the gRPC path hand identical protos to the
    same impl: scores must agree bitwise."""
    impl, sv = stack
    from distributed_tf_serving_tpu.client import ShardedPredictClient
    from distributed_tf_serving_tpu.serving.server import create_server

    rng = np.random.RandomState(6)
    ids = rng.randint(0, 1 << 40, size=(7, F)).astype(np.int64)
    wts = rng.rand(7, F).astype(np.float32)

    server, gport = create_server(impl, "127.0.0.1:0")
    server.start()
    try:
        async def grpc_call():
            async with ShardedPredictClient(
                [f"127.0.0.1:{gport}"], "DCN", output_key="prediction_node"
            ) as c:
                return await c.predict({"feat_ids": ids, "feat_wts": wts})

        grpc_scores = asyncio.run(grpc_call())

        async def rest_call(session):
            body = {"inputs": {"feat_ids": ids.tolist(), "feat_wts": wts.tolist()}}
            async with session.post("/v1/models/DCN:predict", json=body) as r:
                return np.asarray(
                    (await r.json())["outputs"]["prediction_node"], np.float32
                )

        rest_scores = _run(impl, rest_call)
        np.testing.assert_array_equal(np.sort(rest_scores), np.sort(grpc_scores))
    finally:
        server.stop(0)
