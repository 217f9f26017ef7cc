"""Satellite regression tests riding the output-pipeline PR (ISSUE 1):

- GetModelStatus reports START (not NOT_FOUND) for a configured-but-not-
  ready model, so TF-Serving-style readiness probes survive a rollout;
- a lifecycle reload held open on the server stalls no other RPC (a model
  load holds its own handler thread alone);
- the CRC32C table is built eagerly at import (the lazy appender raced
  concurrent first callers, ADVICE round 5).
"""

import threading
import time

import jax
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import (
    ModelConfig,
    Servable,
    ServableRegistry,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.proto import serving_apis_pb2 as apis
from distributed_tf_serving_tpu.serving import (
    DynamicBatcher,
    PredictionServiceImpl,
    ServiceError,
)

CFG = ModelConfig(
    num_fields=8, vocab_size=1009, embed_dim=4, mlp_dims=(16,), num_cross_layers=1,
    compute_dtype="float32",
)


def _impl():
    registry = ServableRegistry()
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0)
    return registry, PredictionServiceImpl(registry, batcher)


def _load_dcn(registry):
    model = build_model("dcn", CFG)
    registry.load(
        Servable(
            name="DCN", version=1, model=model,
            params=model.init(jax.random.PRNGKey(0)),
            signatures=ctr_signatures(CFG.num_fields),
        )
    )


def _status_request(name):
    req = apis.GetModelStatusRequest()
    req.model_spec.name = name
    return req


# ------------------------------------------------ GetModelStatus readiness


def test_get_model_status_start_for_configured_not_ready():
    """A model the server watches (single-model --model-base-path mode)
    whose first version hasn't landed reports START, not NOT_FOUND."""
    _registry, impl = _impl()
    impl.served_sources["DCN"] = ("/models/dcn", "dcn_v2")
    resp = impl.get_model_status(_status_request("DCN"))
    assert len(resp.model_version_status) == 1
    st = resp.model_version_status[0]
    assert st.state == apis.ModelVersionStatus.START
    assert st.version == 0  # no version directory discovered yet
    assert st.status.error_code == 0


def test_get_model_status_start_via_lifecycle():
    """Multi-model mode: a name the ModelLifecycle owns a watcher for is
    configured even before its first version loads."""

    class Lifecycle:
        def configured_models(self):
            return {"PENDING"}

    _registry, impl = _impl()
    impl.model_lifecycle = Lifecycle()
    resp = impl.get_model_status(_status_request("PENDING"))
    assert resp.model_version_status[0].state == apis.ModelVersionStatus.START


def test_get_model_status_unknown_model_stays_not_found():
    _registry, impl = _impl()
    impl.served_sources["DCN"] = ("/models/dcn", "dcn_v2")
    with pytest.raises(ServiceError) as e:
        impl.get_model_status(_status_request("NOPE"))
    assert e.value.code == "NOT_FOUND"


def test_get_model_status_loaded_still_available():
    registry, impl = _impl()
    _load_dcn(registry)
    impl.served_sources["DCN"] = ("/models/dcn", "dcn_v2")  # configured AND ready
    resp = impl.get_model_status(_status_request("DCN"))
    assert resp.model_version_status[0].state == apis.ModelVersionStatus.AVAILABLE


# ------------------------------------- a slow reload holds one handler alone


def test_lifecycle_reload_held_open_does_not_stall_predict():
    """While HandleReloadConfigRequest with a slow lifecycle reload is held
    open on the server (a real reload loads and warms a model there), a
    Predict on another connection is answered: the reload holds its own
    handler thread and nothing the other RPCs need."""
    import grpc

    from distributed_tf_serving_tpu.client import build_predict_request
    from distributed_tf_serving_tpu.proto import ModelServiceStub, PredictionServiceStub
    from distributed_tf_serving_tpu.serving import create_server

    entered, release = threading.Event(), threading.Event()
    applied = []

    class SlowLifecycle:
        def apply(self, entries):
            entered.set()
            release.wait(timeout=30)
            applied.append([mc.name for mc in entries])

        def configured_models(self):
            return {"DCN"}

    registry, impl = _impl()
    _load_dcn(registry)
    impl.model_lifecycle = SlowLifecycle()
    impl.batcher.start()
    server, port = create_server(impl, "127.0.0.1:0")
    server.start()

    req = apis.ReloadConfigRequest()
    mc = req.config.model_config_list.config.add()
    mc.name = "DCN"
    mc.base_path = "/models/dcn"
    rng = np.random.RandomState(5)
    arrays = {
        "feat_ids": rng.randint(0, 1 << 40, size=(4, CFG.num_fields)).astype(np.int64),
        "feat_wts": rng.rand(4, CFG.num_fields).astype(np.float32),
    }
    try:
        with grpc.insecure_channel(f"127.0.0.1:{port}") as reload_ch, \
                grpc.insecure_channel(f"127.0.0.1:{port}") as predict_ch:
            reload_call = ModelServiceStub(reload_ch).HandleReloadConfigRequest.future(
                req, timeout=60
            )
            assert entered.wait(timeout=30)
            resp = PredictionServiceStub(predict_ch).Predict(
                build_predict_request(arrays, "DCN"), timeout=30
            )
            assert resp.outputs["prediction_node"].tensor_shape.dim[0].size == 4
            assert not reload_call.done()  # the reload is still parked
            release.set()
            assert reload_call.result(timeout=30).status.error_code == 0
        assert applied == [["DCN"]]
    finally:
        release.set()
        server.stop(0)
        impl.batcher.stop()


# --------------------------------------------------------- CRC table safety


def test_crc_table_eager_and_thread_consistent():
    """The table exists fully-built at import; hammering crc32c from many
    threads yields one consistent answer (the lazy-init race corrupted
    first-call results when the request-log writer raced warmup replay)."""
    from distributed_tf_serving_tpu.serving import warmup

    assert len(warmup._CRC_TABLE) == 256
    assert warmup._crc_table() is warmup._CRC_TABLE
    # Known-answer check (CRC32C of b"123456789" is the classic vector).
    assert warmup.crc32c(b"123456789") == 0xE3069283

    data = np.random.RandomState(0).bytes(4096)
    want = warmup.crc32c(data)
    results = []
    threads = [
        threading.Thread(target=lambda: results.append(warmup.crc32c(data)))
        for _ in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == [want] * 8
