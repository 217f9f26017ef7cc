"""TLS serving (the --ssl_config_file surface): SSLConfig textproto with
inline PEMs -> secured gRPC port; secure clients score, plaintext clients
are rejected, and client_verify enforces mTLS."""

import asyncio
import subprocess

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import grpc

from distributed_tf_serving_tpu import codec
from distributed_tf_serving_tpu.client import ShardedPredictClient, build_predict_request
from distributed_tf_serving_tpu.models import (
    ModelConfig,
    Servable,
    ServableRegistry,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.serving import DynamicBatcher, PredictionServiceImpl
from distributed_tf_serving_tpu.serving.server import create_server, load_ssl_credentials

F = 6
CFG = ModelConfig(
    name="DCN", num_fields=F, vocab_size=1 << 12, embed_dim=8,
    mlp_dims=(16,), num_cross_layers=1, compute_dtype="float32",
)


def _openssl(*args):
    subprocess.run(["openssl", *args], check=True, capture_output=True)


@pytest.fixture(scope="module")
def pki(tmp_path_factory):
    """Self-signed CA + server cert (CN=localhost, SAN for 127.0.0.1) +
    client cert, all via the openssl CLI."""
    d = tmp_path_factory.mktemp("pki")
    _openssl("req", "-x509", "-newkey", "rsa:2048", "-nodes",
             "-keyout", str(d / "ca.key"), "-out", str(d / "ca.crt"),
             "-days", "1", "-subj", "/CN=test-ca")
    for name, cn in (("server", "localhost"), ("client", "test-client")):
        _openssl("req", "-newkey", "rsa:2048", "-nodes",
                 "-keyout", str(d / f"{name}.key"),
                 "-out", str(d / f"{name}.csr"), "-subj", f"/CN={cn}")
        ext = d / f"{name}.ext"
        ext.write_text("subjectAltName=DNS:localhost,IP:127.0.0.1\n")
        _openssl("x509", "-req", "-in", str(d / f"{name}.csr"),
                 "-CA", str(d / "ca.crt"), "-CAkey", str(d / "ca.key"),
                 "-CAcreateserial", "-days", "1",
                 "-extfile", str(ext), "-out", str(d / f"{name}.crt"))
    return d


@pytest.fixture(scope="module")
def stack():
    model = build_model("dcn_v2", CFG)
    sv = Servable(
        name="DCN", version=1, model=model,
        params=model.init(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(F),
    )
    registry = ServableRegistry()
    registry.load(sv)
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0).start()
    yield PredictionServiceImpl(registry, batcher), sv
    batcher.stop()


def _arrays(n=4, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "feat_ids": rng.randint(0, 1 << 40, size=(n, F)).astype(np.int64),
        "feat_wts": rng.rand(n, F).astype(np.float32),
    }


def _ssl_config(pki, tmp_path, client_verify=False) -> str:
    def pem(name):
        # Inline PEM contents, escaped for text format (upstream convention:
        # the config file carries the PEMs themselves, not paths).
        return (pki / name).read_text().replace("\n", "\\n")

    cfg = tmp_path / "ssl.pbtxt"
    body = (
        f'server_key: "{pem("server.key")}"\n'
        f'server_cert: "{pem("server.crt")}"\n'
    )
    if client_verify:
        body += f'custom_ca: "{pem("ca.crt")}"\nclient_verify: true\n'
    cfg.write_text(body)
    return str(cfg)


def test_tls_serves_and_rejects_plaintext(pki, stack, tmp_path):
    impl, sv = stack
    creds = load_ssl_credentials(_ssl_config(pki, tmp_path))
    server, port = create_server(impl, "localhost:0", credentials=creds)
    server.start()
    try:
        arrays = _arrays()
        chan_creds = grpc.ssl_channel_credentials(
            root_certificates=(pki / "ca.crt").read_bytes()
        )

        async def go():
            async with ShardedPredictClient(
                [f"localhost:{port}"], "DCN",
                channel_credentials=chan_creds,
            ) as c:
                return await c.predict(arrays)

        scores = asyncio.run(go())
        want = np.asarray(sv.model.apply(sv.params, {
            "feat_ids": arrays["feat_ids"] % CFG.vocab_size,
            "feat_wts": arrays["feat_wts"],
        })["prediction_node"])
        np.testing.assert_allclose(scores, want, rtol=1e-5)

        # Plaintext against the TLS port: rejected, not served.
        from distributed_tf_serving_tpu.proto import PredictionServiceStub

        with grpc.insecure_channel(f"localhost:{port}") as ch:
            with pytest.raises(grpc.RpcError):
                PredictionServiceStub(ch).Predict(
                    build_predict_request(arrays, "DCN"), timeout=10
                )
    finally:
        server.stop(0)


@pytest.mark.parametrize("listeners", [2, 4])
def test_tls_on_every_listener(pki, stack, tmp_path, listeners):
    """--ssl-config-file with several listeners on the port (PR 34): the
    credentials go on every one, so whichever listener the kernel hands a
    connection to completes the handshake, and none serves plaintext."""
    from distributed_tf_serving_tpu.proto import PredictionServiceStub

    impl, sv = stack
    creds = load_ssl_credentials(_ssl_config(pki, tmp_path))
    server, port = create_server(impl, "localhost:0", credentials=creds, listeners=listeners)
    assert len(server.servers) == listeners
    server.start()
    try:
        arrays = _arrays(seed=4)
        want = np.asarray(sv.model.apply(sv.params, {
            "feat_ids": arrays["feat_ids"] % CFG.vocab_size,
            "feat_wts": arrays["feat_wts"],
        })["prediction_node"])
        chan_creds = grpc.ssl_channel_credentials(
            root_certificates=(pki / "ca.crt").read_bytes()
        )
        local = [("grpc.use_local_subchannel_pool", 1)]
        for _ in range(3 * listeners):
            with grpc.secure_channel(f"localhost:{port}", chan_creds, options=local) as ch:
                resp = PredictionServiceStub(ch).Predict(
                    build_predict_request(arrays, "DCN"), timeout=10
                )
            np.testing.assert_allclose(
                codec.to_ndarray(resp.outputs["prediction_node"]), want, rtol=1e-5)
            with grpc.insecure_channel(f"localhost:{port}", options=local) as ch:
                with pytest.raises(grpc.RpcError):
                    PredictionServiceStub(ch).Predict(
                        build_predict_request(arrays, "DCN"), timeout=5
                    )
    finally:
        server.stop(0)


def test_mtls_requires_client_certificate(pki, stack, tmp_path):
    impl, _sv = stack
    creds = load_ssl_credentials(_ssl_config(pki, tmp_path, client_verify=True))
    server, port = create_server(impl, "localhost:0", credentials=creds)
    server.start()
    try:
        arrays = _arrays(seed=2)
        from distributed_tf_serving_tpu.proto import PredictionServiceStub

        # Without a client cert: handshake refused.
        no_cert = grpc.ssl_channel_credentials(
            root_certificates=(pki / "ca.crt").read_bytes()
        )
        with grpc.secure_channel(f"localhost:{port}", no_cert) as ch:
            with pytest.raises(grpc.RpcError):
                PredictionServiceStub(ch).Predict(
                    build_predict_request(arrays, "DCN"), timeout=10
                )

        # With a CA-signed client cert (via the CONFIG path — the TOML
        # tls_* knobs exercise client_from_config end to end): served, and
        # scores match the native forward.
        import dataclasses as dc

        from distributed_tf_serving_tpu.client import client_from_config
        from distributed_tf_serving_tpu.utils.config import ClientConfig

        ccfg = dc.replace(
            ClientConfig(),
            hosts=(f"localhost:{port}",),
            tls_root_certs_file=str(pki / "ca.crt"),
            tls_client_key_file=str(pki / "client.key"),
            tls_client_cert_file=str(pki / "client.crt"),
        )

        async def go():
            async with client_from_config(ccfg) as c:
                return await c.predict(arrays)

        scores = asyncio.run(go())
        want = np.asarray(_sv.model.apply(_sv.params, {
            "feat_ids": arrays["feat_ids"] % CFG.vocab_size,
            "feat_wts": arrays["feat_wts"],
        })["prediction_node"])
        np.testing.assert_allclose(scores, want, rtol=1e-5)
    finally:
        server.stop(0)


def test_client_config_partial_tls_is_an_error(pki):
    """A half-set mTLS identity pair must be a config error, and a lone
    key must not silently downgrade to plaintext."""
    import dataclasses as dc

    from distributed_tf_serving_tpu.client import client_from_config
    from distributed_tf_serving_tpu.utils.config import ClientConfig

    half = dc.replace(
        ClientConfig(), hosts=("h:1",),
        tls_client_key_file=str(pki / "client.key"),
    )
    with pytest.raises(ValueError, match="must be set together"):
        client_from_config(half)


def test_ssl_config_validation(pki, tmp_path):
    bad = tmp_path / "bad.pbtxt"
    bad.write_text('server_key: "k"\n')  # missing cert
    with pytest.raises(ValueError, match="server_key and server_cert"):
        load_ssl_credentials(bad)
    # client_verify without custom_ca: grpc-python itself refuses client
    # auth without roots, so the config error must name the fix.
    bad.write_text('server_key: "k"\nserver_cert: "c"\nclient_verify: true\n')
    with pytest.raises(ValueError, match="client_verify requires custom_ca"):
        load_ssl_credentials(bad)
