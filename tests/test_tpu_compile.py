"""Compiles for a TPU v5e that is described, not attached: what the chip's
compiler makes of the lane-packed embedding table at the benchmark's real
size (DCN-v2, 1 << 27 rows of 16 float32: 8 GiB on a 16 GiB chip). Nothing
runs, so these say nothing about times; they guard the two facts the
packing rests on: the table is never on the device twice, and the step reads
it row-major, one whole lane row a lookup. All such compiles live in this one
file: the process that describes the topology holds the TPU library."""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_tf_serving_tpu.models import ModelConfig, build_model

GIB = 1 << 30
VOCAB, DIM, FIELDS = 1 << 27, 16, 43


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def model():
    return build_model(
        "dcn_v2", ModelConfig(name="DCN", num_fields=FIELDS, vocab_size=VOCAB, embed_dim=DIM)
    )


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: keep it out."""
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def test_packed_init_never_holds_the_table_twice(one_chip, model, no_compile_cache):
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = (
        jax.jit(functools.partial(model.init, packed=True), out_shardings=one_chip)
        .lower(key)
        .compile()
    )
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes >= 8 * GIB  # the table itself
    assert memory.temp_size_in_bytes < GIB // 2  # and no second one beside it
    assert f"f32[{VOCAB * DIM // 128},128]{{1,0:T(8,128)}}" in compiled.as_text()


@pytest.mark.parametrize("bucket", [1024])
def test_step_gathers_whole_lane_rows_of_the_packed_table(
    one_chip, model, no_compile_cache, bucket
):
    shapes = jax.eval_shape(functools.partial(model.init, packed=True), jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes
    )
    batch = {
        "feat_ids": jax.ShapeDtypeStruct((bucket, FIELDS), jnp.int32, sharding=one_chip),
        "feat_wts": jax.ShapeDtypeStruct((bucket, FIELDS), jnp.bfloat16, sharding=one_chip),
    }
    compiled = (
        jax.jit(lambda p, b: model.apply(p, b)["prediction_node"]).lower(params, batch).compile()
    )
    text = compiled.as_text()
    rows, lookups = VOCAB * DIM // 128, bucket * FIELDS
    # The table stays row-major (a [V, 16] table is stored dimension 0 minor),
    # and one gather fusion reads it, cast to bf16 on the way out.
    assert f"f32[{rows},128]{{1,0:T(8,128)}} parameter" in text
    assert re.search(
        rf"bf16\[{lookups},128\]\S* fusion\(%p__embedding__\S*, \S+\), kind=kCustom", text
    ), [line for line in text.splitlines() if "p__embedding__" in line][:6]
    # No table-sized temporary: nothing converts or copies the whole table.
    assert compiled.memory_analysis().temp_size_in_bytes < GIB // 8


def test_bags_pool_in_one_matmul_fused_with_their_weights(one_chip, no_compile_cache):
    """MLPerf DLRM-DCNv2 at its published widths (214 ids a row in 26 bags,
    an 8 GiB table), bucket 4096: ONE gather of whole lane rows, cast on the
    way out, and a pooling matmul that takes the weights in; no float32 copy
    of the [n, 214, 128] rows (static slices summed in float32 kept one:
    0.68 GB of temporaries at this bucket against 0.45, PERF.md, PR 26)."""
    bags = (3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100, 27, 10, 3, 1, 1)
    bucket = 4096
    bag_model = build_model("dlrm_dcnv2", ModelConfig(
        name="DCN", num_fields=sum(bags), multi_hot_sizes=bags, vocab_size=1 << 24, embed_dim=128,
        bottom_mlp_dims=(512, 256, 128), mlp_dims=(1024, 1024, 512, 256), cross_low_rank=512,
    ))
    shapes = jax.eval_shape(functools.partial(bag_model.init, packed=True), jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes
    )
    batch = {
        "feat_ids": jax.ShapeDtypeStruct((bucket, sum(bags)), jnp.int32, sharding=one_chip),
        "feat_wts": jax.ShapeDtypeStruct((bucket, sum(bags)), jnp.bfloat16, sharding=one_chip),
        "dense_features": jax.ShapeDtypeStruct((bucket, 13), jnp.float32, sharding=one_chip),
    }
    compiled = (
        jax.jit(lambda p, b: bag_model.apply(p, b)["prediction_node"]).lower(params, batch).compile()
    )
    text = compiled.as_text()
    assert re.search(rf"bf16\[{sum(bags) * bucket},128\]\S* fusion\(%p__embedding__", text)
    assert compiled.memory_analysis().temp_size_in_bytes < GIB // 2
