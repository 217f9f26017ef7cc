"""The autotuned kernel plane (ISSUE 12) is gone (ISSUE 62): which kernel
runs a step is chosen at trace time by the families' `*_choice` functions and
by nothing else. Each door the plane had now refuses BY NAME, through the
check every unknown section, flag, key and keyword already meets."""

import asyncio

import jax
import pytest

from distributed_tf_serving_tpu.models import (
    ModelConfig,
    Servable,
    ServableRegistry,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.utils.config import ServerConfig, load_config


def _toml_section(tmp_path):
    path = tmp_path / "k.toml"
    path.write_text("[server]\nnum_fields = 6\n[kernels]\nenabled = true\n")
    load_config(str(path))


def _cli_flag(tmp_path):
    from distributed_tf_serving_tpu.serving.server import serve

    serve(["--kernels"])


def _model_key(tmp_path):
    path = tmp_path / "m.toml"
    path.write_text("[model]\nnum_fields = 6\nuse_pallas_cross = true\n")
    load_config(str(path))


def _client_option(tmp_path):
    from distributed_tf_serving_tpu.client import ShardedPredictClient

    ShardedPredictClient(["127.0.0.1:1"], "DCN", score_wire_int8=True)


def _build_stack_keyword(tmp_path):
    from distributed_tf_serving_tpu.serving.server import build_stack

    build_stack(ServerConfig(num_fields=6, warmup=False), kernels_config=None)


@pytest.mark.parametrize("door, error, names", [
    (_toml_section, ValueError, "unknown config sections: ['kernels']"),
    (_cli_flag, SystemExit, "unrecognized arguments: --kernels"),
    (_model_key, (TypeError, ValueError), "use_pallas_cross"),
    (_client_option, TypeError, "score_wire_int8"),
    (_build_stack_keyword, TypeError, "kernels_config"),
], ids=["toml_section", "cli_flag", "model_key", "client_option", "build_stack_keyword"])
def test_a_door_of_the_plane_refuses_by_name(door, error, names, tmp_path, capsys):
    with pytest.raises(error) as refused:
        door(tmp_path)
    assert names in str(refused.value) + capsys.readouterr().err


def test_the_monitoring_section_is_refused_by_name():
    import aiohttp

    from distributed_tf_serving_tpu.serving import DynamicBatcher, PredictionServiceImpl
    from distributed_tf_serving_tpu.serving.rest import start_rest_gateway

    config = ModelConfig(num_fields=6, vocab_size=509, embed_dim=8, mlp_dims=(16,), num_cross_layers=1)
    model = build_model("dcn_v2", config)
    registry = ServableRegistry()
    registry.load(Servable(name="DCN", version=1, model=model, params=model.init(jax.random.PRNGKey(0)),
                           signatures=ctr_signatures(6)))
    batcher = DynamicBatcher(buckets=(4,), max_wait_us=0).start()
    impl = PredictionServiceImpl(registry, batcher)

    async def go():
        runner, port = await start_rest_gateway(impl, port=0)
        try:
            async with aiohttp.ClientSession(f"http://127.0.0.1:{port}") as session:
                async with session.get("/monitoring", params={"section": "kernels"}) as r:
                    refused = r.status, await r.text()
                async with session.get("/monitoring") as r:
                    whole = await r.json()
                async with session.get("/monitoring/prometheus/metrics") as r:
                    return refused, whole, await r.text()
        finally:
            await runner.cleanup()

    try:
        (status, body), whole, prom = asyncio.run(go())
    finally:
        batcher.stop()
    assert status == 400 and "unknown section 'kernels'" in body
    assert "kernels" not in whole and "runtime" in whole and "dts_tpu_kernel_" not in prom
    # What the families' choices left is where it was: a stamp a served kernel.
    assert {"gather", "attention", "grouped", "delta_rule", "ssd"} <= set(whole["runtime"]["startup"])
