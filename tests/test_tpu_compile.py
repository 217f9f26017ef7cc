"""Compiles for a TPU v5e that is described, not attached: what the chip's
compiler makes of the lane-packed embedding table at the benchmark's real
size (DCN-v2, 1 << 27 rows of 16 float32: 8 GiB on a 16 GiB chip). Nothing
runs, so these say nothing about times; they guard the two facts the
packing rests on: the table is never on the device twice, and the step reads
it row-major, one whole lane row a lookup. All such compiles live in this one
file: the process that describes the topology holds the TPU library."""

import functools
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_tf_serving_tpu.models import ModelConfig, build_model
from distributed_tf_serving_tpu.models.base import step_jit

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
        "dcn_v2", ModelConfig(name="c4b1ba715cf54e70", num_fields=FIELDS, vocab_size=VOCAB, embed_dim=DIM)
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
        name="c4b1ba715cf54e70", num_fields=sum(bags), multi_hot_sizes=bags, vocab_size=1 << 24, embed_dim=128,
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


# ------------------------------------------------ the Pallas gather (PR 39)
#
# Interpret mode cannot see what Mosaic refuses (a slice off the tiling, too
# much scalar or vector memory, a semaphore too many): the kernel at the three
# CTR cells' top rungs, and the DCN-v2 step through it.


@pytest.mark.parametrize("shape", [(32768, 43), (8192, 214), (16384, 26), (512 * 26,)], ids=str)
def test_gather_kernel_compiles_at_the_cells_top_rungs(one_chip, no_compile_cache, shape):
    from distributed_tf_serving_tpu.ops.gather_kernel import gather_rows

    table = jax.ShapeDtypeStruct((1 << 24, 128), jnp.float32, sharding=one_chip)
    rows = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    compiled = (
        jax.jit(functools.partial(gather_rows, dtype=jnp.bfloat16)).lower(table, rows).compile()
    )
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    # The table is read where it lies: nothing table-sized beside it.
    assert compiled.memory_analysis().temp_size_in_bytes < GIB // 2


def test_a_bfloat16_lane_row_table_is_refused_by_mosaic(one_chip, no_compile_cache):
    """Why gather_choice leaves a bfloat16 [V, 128] table to XLA: two of its
    rows share a 32-bit sublane, and a one-row copy is off the tiling."""
    from distributed_tf_serving_tpu.ops.gather_kernel import gather_rows

    table = jax.ShapeDtypeStruct((1 << 20, 128), jnp.bfloat16, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((1024, 43), jnp.int32, sharding=one_chip)
    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(functools.partial(gather_rows, dtype=jnp.bfloat16)).lower(table, rows).compile()


def test_step_through_the_gather_kernel_has_no_xla_gather(one_chip, model, no_compile_cache, monkeypatch):
    """The DCN-v2 step as a TPU traces it (here the backend is the CPU, so the
    test says `tpu` where lookup_rows asks, inside serving_gathers as the
    batcher's entry is): the rows come from the kernel as
    [n, F, 128], so neither XLA's gather fusion nor the relayout after it is
    in the program; the table is still read in place."""
    from distributed_tf_serving_tpu.models import embeddings

    monkeypatch.setattr(embeddings.jax, "default_backend", lambda: "tpu")
    bucket = 1024
    shapes = jax.eval_shape(functools.partial(model.init, packed=True), jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes
    )
    batch = {
        "feat_ids": jax.ShapeDtypeStruct((bucket, FIELDS), jnp.int32, sharding=one_chip),
        "feat_wts": jax.ShapeDtypeStruct((bucket, FIELDS), jnp.bfloat16, sharding=one_chip),
    }
    with embeddings.serving_gathers([]) as notes:
        compiled = (
            jax.jit(lambda p, b: model.apply(p, b)["prediction_node"]).lower(params, batch).compile()
        )
    assert notes == [{
        "kernel": "pallas", "row_bytes": 512, "in_flight": 32 * FIELDS, "picked_in_kernel": False}]
    text = compiled.as_text()
    assert re.search(rf"bf16\[{bucket},{FIELDS},128\]\S* custom-call\(", text)
    assert not re.search(rf"bf16\[{bucket * FIELDS},128\]\S* fusion\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < GIB // 8


# ----------------------- the sequence cells' top-bucket steps (PR 43, PR 44)
#
# `bytes accessed` is the compiler's own count of what the step's operations
# read and write. It says, without a chip, in which PR a change puts a score
# tile back through memory: one product a PAIR of pieces read 145.1 / 203.9 /
# 189.3 GB in the three steps (PR 43's tree); with the pairs added up inside
# a product they read 115.9 / 138.7 / 139.3 (models/sequence.py::product,
# PR 44). Since PR 48 the steps are compiled as the batcher's entry traces
# them on a TPU (inside `serving_attention`, the backend answering `tpu`):
# the attention at all positions is the Pallas kernel, a custom call that
# counts its operands and results alone (its `cost_estimate`), and the steps
# read 100.4 / 94.9 / 86.1 GB and olmo_hybrid's 160.7 where it read 169.2, and no
# score tile is a fusion's result any more (six and twelve were, in the two
# routed steps: SCORE_TILE). Since PR 57 two pieces meet a weight in ONE
# product (`sequence.product`'s contracted form), whose float32 result is
# written once: the three two-piece steps read 74.9 / 105.5 / 61.7 GB where
# they read 100.4 / 143.1 / 87.4 (`phi4flash`, `olmo_hybrid`, `falcon_h1`).

SCORE_TILE = re.compile(r"f32\[[\d,]*,512,(?:1024|2048)\]\S* fusion\(")
REPEATED_WEIGHT = re.compile(r"^\s*%\S+ = bf16\[2,(\d+),(\d+)\]\S* (?!bitcast\()(\w[\w\-]*)\(.*?op_name=\"([^\"]*)\"", re.M)


def repeated_weights(text: str, name: str, kind: str) -> list[tuple[int, str]]:
    """(bytes, the scope that made it) of every array the ENTRY computation
    holds of one of the configuration's weights `[k, n]` repeated over the second
    contracted axis of `sequence.product`'s contracted form, `bf16[2, k, n]`:
    the broadcast where the compiler did not keep it inside the product."""
    shapes = jax.eval_shape(cells_model(name, kind)[0].init, jax.random.PRNGKey(0))
    weights = {leaf.shape for leaf in jax.tree.leaves(shapes) if len(leaf.shape) == 2}
    entry = text[text.index("\nENTRY "):]
    return [(2 * int(k) * int(n) * 2, scope) for k, n, _op, scope in REPEATED_WEIGHT.findall(entry[:entry.index("\n}")])
            if (int(k), int(n)) in weights]


def assert_no_large_weight_is_copied(text: str, name: str, kind: str, count: int) -> None:
    """No weight of the MLP or of a mixer's projections (42 to 220 MB) stands
    repeated among the entry's instructions: there the broadcast stays inside
    the product. The ones that do are the attention's q, k and v at all
    positions (26 to 29 MB a weight, 52 to 59 repeated, and 5 MB ones), whose
    products the compiler turns round to write their result head-major for
    the attention kernel, the weight as the streamed operand: exactly `count`
    of them as read at PR 57's tree, so that one more is seen (and one fewer:
    lower the count), temporaries of the step and no more. ISSUE 57 asked for
    none; PERF.md section 7, PR 57 (b) has what they cost and the repair."""
    repeated = repeated_weights(text, name, kind)
    assert len(repeated) == count and all(size <= 64 << 20 and "attn" in scope for size, scope in repeated), repeated


@pytest.fixture()
def served_on_a_tpu(monkeypatch):
    """The backend is the CPU here: say `tpu` where `sequence.kernel_serves`
    asks, as the gather's step test does for `lookup_rows`."""
    from distributed_tf_serving_tpu.models import sequence

    monkeypatch.setattr(sequence.jax, "default_backend", lambda: "tpu")


def cells_model(name: str, kind: str):
    """(the model of the benchmark configuration `name`, its top bucket's rows, its fields)."""
    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "benchmark", "configs", name, "config.json")) as f:
        config = json.load(f)["toml"]
    shape = {k: tuple(v) if isinstance(v, list) else v for k, v in config["model"].items()}
    return build_model(kind, ModelConfig(**shape)), max(config["server"]["buckets"]), shape["num_fields"]


STEPS: dict = {}  # (name, kind) -> sequence_cells_step's result: a step is compiled once a session


def sequence_cells_step(name: str, kind: str, one_chip, rows: int | None = None):
    """(the compiled top-bucket step of the configuration `name` as its cell
    serves it, with its counters where it has them; its `bytes accessed`);
    another rung's where `rows` says which."""
    if (name, kind, rows) not in STEPS:
        STEPS[name, kind, rows] = _sequence_cells_step(name, kind, one_chip, rows)
    return STEPS[name, kind, rows]


def _sequence_cells_step(name: str, kind: str, one_chip, rung: int | None = None):
    from distributed_tf_serving_tpu.models import sequence

    model, rows, fields = cells_model(name, kind)
    rows = rung or rows
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    batch = {
        "feat_ids": jax.ShapeDtypeStruct((rows, fields), jnp.int32, sharding=one_chip),
        "feat_wts": jax.ShapeDtypeStruct((rows, fields), jnp.float32, sharding=one_chip),
    }
    run = model.apply_stats if model.step_stats else model.apply

    def served(p, b):
        with sequence.serving_attention([]):
            return run(p, b)

    compiled = step_jit(model, served, "tpu").lower(params, batch).compile()
    cost = compiled.cost_analysis()
    return compiled, (cost[0] if isinstance(cost, (list, tuple)) else cost)["bytes accessed"]


def test_exaone_moes_four_row_step_compiles_at_the_published_cut(one_chip, no_compile_cache, served_on_a_tpu):
    """K-EXAONE's share as `k_exaone_moe_rerank-bulk` serves it (2.386 B
    parameters, rows of 2,048 tokens), the top bucket's step with its counters:
    the chip's compiler takes the band's batched blocks, the 512-query blocks
    of the full layer and the experts' loops, and what it holds beside the
    4.77 GB of weights fits the chip's 16 GB."""
    compiled, accessed = sequence_cells_step("k_exaone_moe_rerank", "exaone_moe", one_chip)
    memory = compiled.memory_analysis()
    assert 4.7e9 < memory.argument_size_in_bytes < 4.8e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12 * GIB
    assert memory.generated_code_size_in_bytes < 64 << 20  # 37.7 MB; 49.9 on the XLA path
    assert accessed < 93e9  # 86.1 GB; 139.3 on the XLA path
    assert not SCORE_TILE.search(compiled.as_text())
    # the attention's kernel a layer but the last, and since PR 51 the grouped kernels' two a routed layer
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 4 + 2 * 4


def test_phi4flashs_eight_row_step_writes_a_score_tile_once(one_chip, no_compile_cache, served_on_a_tpu):
    """Phi-4-mini-flash at the 16 layers `phi4_mini_flash_rerank-bulk` serves
    (2.19 B parameters, 8 rows of 1,024 tokens): the weights, what the step
    holds beside them, the ladder's largest executable, and the bytes."""
    compiled, accessed = sequence_cells_step("phi4_mini_flash_rerank", "phi4flash", one_chip)
    memory = compiled.memory_analysis()
    assert 4.3e9 < memory.argument_size_in_bytes < 4.5e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12 * GIB
    # 74.8 MB (106.9 while two pieces met a weight in a product a piece: PR 48 to PR 56; 116.2 on the XLA path)
    assert memory.generated_code_size_in_bytes < 86 << 20
    assert accessed < 80e9  # 74.9 GB (100.4 with a product a piece; 115.9 on the XLA path)
    assert_no_large_weight_is_copied(compiled.as_text(), "phi4_mini_flash_rerank", "phi4flash", count=4)  # the full and the three cross layers' q


def test_pangu_moes_eight_row_step_writes_a_score_tile_once(one_chip, no_compile_cache, served_on_a_tpu):
    """openPangu-Ultra-MoE's share as `pangu_ultra_moe_rerank-bulk` serves it
    (2.585 B parameters, 8 rows of 1,024 tokens), with its counters."""
    compiled, accessed = sequence_cells_step("pangu_ultra_moe_rerank", "pangu_moe", one_chip)
    memory = compiled.memory_analysis()
    assert 5.1e9 < memory.argument_size_in_bytes < 5.3e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12 * GIB
    assert memory.generated_code_size_in_bytes < 71 << 20  # 60.9 MB; 65.6 on the XLA path
    assert accessed < 101e9  # 94.9 GB; 138.7 on the XLA path
    assert not SCORE_TILE.search(compiled.as_text())


DEFAULT_VMEM = 16 << 20  # what a kernel has where it asks for no more


def kernels_vmem(text: str, name: str) -> list[int]:
    """The bytes of VMEM each compiled `tpu_custom_call` named `name` was given."""
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line and f"%{name}" in line]
    if name == "causal_conv":  # its result is the SSD kernel's operand three times: the convolution's own lines only
        calls = [line for line in calls if re.match(r"\s*(ROOT )?%causal_conv", line)]
    return [int(re.search(r'used_scoped_memory_configs":\[\{"memory_space":"1","offset":"0","size":"(\d+)"', line).group(1))
            for line in calls]


def assert_the_mixers_channels_cross_where_they_lie(text: str, rows: int, width: int, d_ssm: int, channels: int, layers: int):
    """PR 63, of a compiled step whose `layers` Mamba-2 mixers' last reads one
    position: every mixer's convolution is the kernel, handed the input
    projection's array `[rows, 2048, width]` WHOLE, twice (its blocks and the
    tile before them), so no `f32[rows, 2048, channels]` slice of it exists;
    and of every mixer at all positions the kernel's one result is read by
    the SSD's kernel three times (x, B and C its windows) and by the fusion
    that adds `D x`, by no slice and no copy. The last mixer's hand-overs are
    XLA's scan, which cuts x, B and C out as before (ROADMAP S13(g))."""
    entry = text[text.index("ENTRY"):].splitlines()
    shape = lambda lanes: rf"f32\[{rows},2048,{lanes}\]"  # noqa: E731
    convs = [re.match(rf"\s*%(causal_conv[.\d]*) = {shape(channels)}\S* custom-call\(%([\w.\-]+), %([\w.\-]+),", line)
             for line in entry if line.lstrip().startswith("%causal_conv")]
    assert len(convs) == layers and all(convs), len(convs)
    made = {m.group(1): re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\(", line)
            for line in entry if (m := re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line))}
    for conv in convs:
        assert conv.group(2) == conv.group(3) and re.match(shape(width), made[conv.group(2)].group(1)), conv.group(0)
    assert not [line for line in entry if re.match(rf"\s*%[\w.\-]+ = {shape(channels)}\S* (slice|copy|fusion)\(", line)]
    read_whole = 0
    for conv in convs:
        users = [line for line in entry if re.search(rf"%{re.escape(conv.group(1))}[,)]", line) and line != conv.string]
        kinds = sorted(made[re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line).group(1)].group(2) for line in users)
        walks = [line for line in users if line.lstrip().startswith("%ssd_chunks")]
        if walks:
            assert kinds == ["custom-call", "fusion"] and walks[0].count(f"%{conv.group(1)},") == 3, (kinds, walks[0][:400])
            read_whole += 1
    assert read_whole == layers - 1


@pytest.mark.parametrize("rows", [4, 2])
def test_olmo_hybrids_steps_compile_at_the_published_cut(one_chip, no_compile_cache, served_on_a_tpu, rows):
    """Olmo-Hybrid-7B's first pipeline stage as `olmo_hybrid_rerank-bulk`
    serves it (2.050 B parameters, rows of 2,048 tokens), both rungs of its
    ladder with their counters, compiled as the batcher compiles them
    (`base.step_jit`): the rule's solve is the block form (no triangular-solve
    custom call and no loop of its own), its chunk pass ONE Pallas kernel a
    linear layer and no `while` (PR 52; six chunk loops before it), the
    kernel's VMEM inside the 16 MiB a kernel has by default and no more asked
    for, what the step holds beside the 4.10 GB of weights fits the chip's
    16 GB, and the layers share one copy of a fusion (the family's compiler
    option; without it the 4-row step was 164.4 MB of code: PERF.md section 6,
    PR 47)."""
    compiled, accessed = sequence_cells_step("olmo_hybrid_rerank", "olmo_hybrid", one_chip, rows)
    memory, text = compiled.memory_analysis(), compiled.as_text()
    assert 4.0e9 < memory.argument_size_in_bytes < 4.2e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12 * GIB
    assert memory.generated_code_size_in_bytes < 64 << 20  # 25.9 MB at 4 rows; 36.1 a product a piece, 39.1 with the chunk loops
    # 105.5 GB at 4 rows and 63.5 at 2, two pieces against a weight ONE product (PR 57); a product a piece read 143.1
    # and 84.2 (160.7 with the chunk loops, 169.2 with XLA's attention too)
    assert accessed < {4: 112e9, 2: 68e9}[rows]
    assert_no_large_weight_is_copied(text, "olmo_hybrid_rerank", "olmo_hybrid", count={4: 2, 2: 2}[rows])  # the first full layer's q and k
    assert "Triangular" not in text  # InvertDiagBlocksLowerTriangular, what triangular_solve lowers to
    assert not re.findall(r"\) while\(", text)
    # the first full layer's attention and the six linear layers' rules
    assert text.count('custom_call_target="tpu_custom_call"') == 1 + 6 and "vmem_limit" not in text
    vmem = kernels_vmem(text, "delta_rule")
    assert len(vmem) == 6 and all(0 < size < DEFAULT_VMEM // 2 for size in vmem)  # 6.7 MB


def test_olmo_hybrids_entry_as_the_batcher_builds_it_is_scheduled_as_the_bare_step_is(
        one_chip, no_compile_cache, served_on_a_tpu, monkeypatch):
    """The 4-row entry `batcher._build_entry` traces (the one-buffer upload's
    unpack, the counters beside the outputs under a key that sorts FIRST),
    compiled for a described v5e from shapes alone, beside the same step
    traced bare with its counters last (`sequence_cells_step`): the compiler
    schedules both alike. Where the counters stand among an executable's
    results decided that until `forward` tied them to the logits with one
    barrier: the entry lost the prefetch of seven MLP `up` weights and 11 ms a
    step on the chip while the bare step kept them (PERF.md section 6,
    PR 52). Since PR 57 the MLP's `gate` and `up` are ONE product each a layer
    (fourteen fusions with an 11,008-wide result where a product a piece made
    twenty-eight), which read their weight as it lies (one of the fourteen
    through a prefetch, in the bare step and in the entry alike), so what is
    held is that the two agree: in those fusions' prefetched operands and in
    the prefetches they start overall. A guard on a compiler's heuristic, so
    a failure here says "read the served step again on the chip", not "the
    program is wrong"."""
    import numpy as np

    from distributed_tf_serving_tpu.ops.transfer import combined_layout, combined_words, transfer_spec
    from distributed_tf_serving_tpu.serving import batcher as batcher_mod

    model, rows, fields = cells_model("olmo_hybrid_rerank", "olmo_hybrid")
    on_chip = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(on_chip, jax.eval_shape(functools.partial(model.init, packed=True), jax.random.PRNGKey(0)))
    layout = combined_layout(
        {"feat_ids": np.empty((0, fields), np.int32), "feat_wts": np.empty((0, fields), np.float32)},
        transfer_spec(model), rows=rows)
    compiled = []

    def compile_only(model, run, platform=None):
        def call(p, b):
            compiled.append(step_jit(model, run, "tpu").lower(p, b).compile())
            raise StopIteration
        return call

    class Servable:  # what _build_entry reads of one
        name, version = "olmo_hybrid", 1

    Servable.model = model
    monkeypatch.setattr(batcher_mod, "step_jit", compile_only)
    fn, _spec, combined = batcher_mod.DynamicBatcher(buckets=(2, 4))._build_entry(Servable(), True)
    with pytest.raises(StopIteration):
        fn(params, jax.ShapeDtypeStruct((combined_words(layout),), jnp.uint32, sharding=one_chip), layout)
    text = compiled[0].as_text()
    assert combined and text.count('custom_call_target="tpu_custom_call"') == 1 + 6
    bare = sequence_cells_step("olmo_hybrid_rerank", "olmo_hybrid", one_chip, 4)[0].as_text()

    def wide(hlo: str) -> list[bool]:
        """Whether each fusion with an 11,008-wide result reads a prefetched operand."""
        return ["%copy-done" in operands for operands in re.findall(
            r"^\s*%fusion\.\d+ = \(?(?:f32|bf16)\[(?:1,)?4,2048,11008[^\n]*? fusion\(([^)]*)\)", hlo, re.M)]

    # the recorded counts: 1 of 14 prefetched on both sides at PR 57's tree; PR 52 to PR 56 held 7 of 7 here (the second
    # piece's product with `up`, a `convolution_add_fusion` each), which the entry had lost before PR 52's barrier
    assert len(wide(text)) == len(wide(bare)) == 14 and sum(wide(text)) == sum(wide(bare)) == 1
    assert abs(text.count(" copy-start(") - bare.count(" copy-start(")) <= 4 < bare.count(" copy-start(")  # 244, 242


def test_mimo_v2s_four_row_step_compiles_at_the_published_cut(one_chip, no_compile_cache, served_on_a_tpu):
    """MiMo-V2.5's share as `mimo_v2_5_rerank-bulk` serves it (2.144 B
    parameters, rows of 2,048 tokens), the top bucket's step with its seven
    counters: a kernel a layer but the last, four of them with a sink (its
    logits in SMEM, the share a second result), 192-wide keys over 128-wide
    values, and what the step holds beside the 4.29 GB of weights fits the
    chip's 16 GB."""
    compiled, accessed = sequence_cells_step("mimo_v2_5_rerank", "mimo_v2", one_chip)
    memory = compiled.memory_analysis()
    assert 4.2e9 < memory.argument_size_in_bytes < 4.4e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12 * GIB  # 4.29 GB + 3.8 GiB
    assert memory.generated_code_size_in_bytes < 64 << 20  # 37.5 MB
    assert accessed < 106e9  # 99.1 GB
    assert not SCORE_TILE.search(compiled.as_text())
    # the attention's kernel a layer but the last, and since PR 51 the grouped kernels' two a routed layer
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 6 + 2 * 6


@pytest.mark.parametrize("rows", [4, 2])
def test_falcon_h1s_steps_compile_at_the_published_cut(one_chip, no_compile_cache, served_on_a_tpu, rows):
    """Falcon-H1-34B's first pipeline stage as `falcon_h1_34b_rerank-bulk`
    serves it (3.49 B parameters at five layers, rows of 2,048 tokens), both
    rungs of its ladder with their five counters: the attention's kernel a
    layer but the last at 20 query heads over 4 key-value heads (5 a group, a
    grouping no other cell has), the SSD's chunk walk ONE Pallas kernel a
    layer but the last too (PR 55; a `while` a layer before it, the state's
    hand-over through HBM), whose VMEM is inside the 16 MiB a kernel has by
    default and no more asked for; the convolution before it ONE kernel in
    every layer (PR 63), which reads the input projection where it lies and
    whose one result the SSD's kernel reads as three windows; the one loop left
    is the last layer's hand-overs, whose `y` is read at the last position
    alone; and what the step holds beside the 6.98 GB of weights fits the
    chip's 16 GB."""
    compiled, accessed = sequence_cells_step("falcon_h1_34b_rerank", "falcon_h1", one_chip, rows)
    memory, text = compiled.memory_analysis(), compiled.as_text()
    layers = len(cells_model("falcon_h1_34b_rerank", "falcon_h1")[0].layer_plan)
    assert layers in (4, 5) and {5: 6.9e9, 4: 6.0e9}[layers] < memory.argument_size_in_bytes < {5: 7.1e9, 4: 6.2e9}[layers]
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14 * GIB
    assert memory.generated_code_size_in_bytes < 64 << 20
    # the attention's kernel and the SSD's, a layer but the last, and the convolution's in every layer
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * (layers - 1) + layers and "vmem_limit" not in text
    vmem = kernels_vmem(text, "ssd_chunks")
    assert len(vmem) == layers - 1 and all(0 < size < DEFAULT_VMEM * 3 // 4 for size in vmem)  # 8.1 MB
    assert [0 < size <= DEFAULT_VMEM - (1 << 19) for size in kernels_vmem(text, "causal_conv")] == [True] * layers  # 8.5 MB
    assert_the_mixers_channels_cross_where_they_lie(text, rows, 9248, 4096, 5120, layers)
    assert len(re.findall(r"\) while\(", text)) == 1  # the last layer's hand-over scan, and no other loop
    assert not SCORE_TILE.search(text)
    # 58.1 GB at 4 rows (61.7 before PR 63: the channels sliced out of the projection, and x, B and C copied out of the
    # convolution's result); two pieces against a weight ONE product (PR 57): 87.4 a product a piece
    assert accessed < {4: 60e9, 2: 42e9}[rows]
    assert_no_large_weight_is_copied(text, "falcon_h1_34b_rerank", "falcon_h1", count={4: 10, 2: 10}[rows])  # q, k and v of the layers at all positions


def test_qwen3_nexts_eight_row_step_compiles_at_the_published_cut(one_chip, no_compile_cache, served_on_a_tpu):
    """Qwen3-Next's share as `qwen3_next_80b_rerank-bulk` serves it (2.508 B
    parameters, 128 of 512 experts a layer, 8 rows of 2,048 tokens), the top
    bucket's step with its counters: the four linear layers' rules ONE Pallas
    kernel each at 32 value heads of 128, the five routed layers' held experts
    the two grouped kernels each over a layout of `T x k` rows and a tile an
    expert, no loop of XLA's anywhere (the dispatch is one sort a layer), and
    the full layer's attention the attention kernel (PR 61): heads 256 wide at
    three pieces held compact (`attention_kernel.held_compact`), 15.45 MiB of
    the 16 a kernel has by this compiler's count and no more asked for. In
    pairs this compiler counted 21.5 and the chip refused it. Necessary, not
    sufficient: the chip decides (PERF.md section 6, PR 58 and PR 61)."""
    compiled, accessed = sequence_cells_step("qwen3_next_80b_rerank", "qwen3_next", one_chip)
    memory, text = compiled.memory_analysis(), compiled.as_text()
    assert 5.0e9 < memory.argument_size_in_bytes < 5.1e9  # 2,508 M parameters in bfloat16
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12 * GIB  # 2.9 GB of temporaries
    assert memory.generated_code_size_in_bytes < 64 << 20  # 37 MB; 46 with XLA's blocks
    assert not re.findall(r"\) while\(", text)
    assert text.count('custom_call_target="tpu_custom_call"') == 4 + 1 + 2 * 5 and "vmem_limit" not in text
    assert len(kernels_vmem(text, "delta_rule")) == 4
    assert all(0 < size < DEFAULT_VMEM // 2 for name in ("delta_rule", "grouped_gate_up", "grouped_down")
               for size in kernels_vmem(text, name))
    # the chip took the kernel at this count (PR 61); it has refused kernels this compiler passed (PR 50), so half a
    # MiB under the 16 is the margin a change to the kernel has to keep here
    assert [0 < size <= DEFAULT_VMEM - (1 << 19) for size in kernels_vmem(text, "attention")] == [True]
    assert not SCORE_TILE.search(text)
    assert accessed < 118e9  # 107 GB; 145 with XLA's blocks


def test_nemotron_hs_eight_row_step_compiles_at_the_published_cut(one_chip, no_compile_cache, served_on_a_tpu):
    """Nemotron-3-Super's share as `nemotron3_super_120b_rerank-bulk` serves it
    (3.265 B parameters, 64 of 512 experts a routed layer, 8 rows of 2,048
    tokens), the top bucket's step with its ten counters: the five Mamba-2
    layers at all positions ONE SSD kernel each at 128 heads of 64 (a whole
    group of 16 a step) behind the convolution's kernel (PR 63: all six
    Mamba-2 layers', the input projection read where it lies and x, B and C
    three windows of its one result), the one attention layer the attention kernel at 16
    query heads a key-value head, the five routed layers the two grouped
    kernels each at the ungated form (`grouped_up` against one weight) over
    1,024-wide rows and a layout of `T x 22` rows and a tile an expert (3.96 GB
    between the kernels, one layer's at a time), and one loop of XLA's: the
    last layer's state hand-overs, whose `y` is read at the last position.
    Necessary, not sufficient: the chip decides (PERF.md section 6, PR 60)."""
    compiled, accessed = sequence_cells_step("nemotron3_super_120b_rerank", "nemotron_h", one_chip)
    memory, text = compiled.memory_analysis(), compiled.as_text()
    assert 6.5e9 < memory.argument_size_in_bytes < 6.6e9  # 3,264.6 M parameters in bfloat16
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14 * GIB  # 6.51 GB of temporaries
    assert memory.generated_code_size_in_bytes < 64 << 20  # 29 MB
    assert len(re.findall(r"\) while\(", text)) == 1
    assert text.count('custom_call_target="tpu_custom_call"') == 5 + 6 + 1 + 2 * 5 and "vmem_limit" not in text
    assert len(kernels_vmem(text, "ssd_chunks")) == 5 and len(kernels_vmem(text, "attention")) == 1
    assert [0 < size <= DEFAULT_VMEM - (1 << 19) for size in kernels_vmem(text, "causal_conv")] == [True] * 6  # 8.5 MB
    assert_the_mixers_channels_cross_where_they_lie(text, 8, 18560, 8192, 10240, 6)
    # (a line that names `%grouped_up` is the kernel's own or the next one's, which reads its result)
    assert len(kernels_vmem(text, "grouped_up")) == 2 * len(kernels_vmem(text, "grouped_down")) == 10
    assert not kernels_vmem(text, "grouped_gate_up")
    assert all(0 < size < DEFAULT_VMEM for name in ("ssd_chunks", "attention", "grouped_up", "grouped_down")
               for size in kernels_vmem(text, name))  # 8.2, 10.4, 4.3 and 14.7 MB
    assert accessed < 225e9  # 213.5 GB; 229 before PR 63


def test_sdar_moes_eight_row_step_compiles_at_the_published_cut(one_chip, no_compile_cache, served_on_a_tpu):
    """SDAR-30B-A3B's first pipeline stage as `sdar_30b_a3b_rerank-bulk` serves
    it (3.427 B parameters, ALL 128 experts of every layer, 8 rows of 2,048
    tokens), the top bucket's step with its eight counters: the four layers at
    all positions ONE attention kernel each under the block mask (the same
    kernel and tiles as a causal layer's: the mask is two lines of it), the
    five routed layers the two grouped kernels each over a layout of 147,456
    rows of 768, and no loop of XLA's. Necessary, not sufficient: the chip
    decides (PERF.md section 6, PR 64)."""
    compiled, _ = sequence_cells_step("sdar_30b_a3b_rerank", "sdar_moe", one_chip)
    memory, text = compiled.memory_analysis(), compiled.as_text()
    assert 6.8e9 < memory.argument_size_in_bytes < 6.9e9  # 3,426.8 M parameters in bfloat16
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14 * GIB
    assert memory.generated_code_size_in_bytes < 64 << 20
    assert not re.findall(r"\) while\(", text)
    assert text.count('custom_call_target="tpu_custom_call"') == 4 + 2 * 5 and "vmem_limit" not in text
    assert len(kernels_vmem(text, "attention")) == 4
    assert len(kernels_vmem(text, "grouped_gate_up")) == 2 * len(kernels_vmem(text, "grouped_down")) == 10
    assert all(0 < size < DEFAULT_VMEM for name in ("attention", "grouped_gate_up", "grouped_down")
               for size in kernels_vmem(text, name))


# ------------------------------------------- the Pallas attention (PR 48)
#
# What interpret mode cannot see: Mosaic's verdict on the kernel's slices,
# scratch and products at the four cells' top rungs (head-major operands, as
# `sequence.attention` hands them over).

# (the queries' parts, the keys' parts, the values, the window, the pieces,
# whether a sink logit a head joins the softmax)
ATTENTION_SHAPES = {
    "exaone_moe_full": (((4, 64, 2048, 128),), ((4, 8, 2048, 128),), (4, 8, 2048, 128), None, 3),
    "exaone_moe_window": (((4, 64, 2048, 128),), ((4, 8, 2048, 128),), (4, 8, 2048, 128), 128, 3),
    "pangu_moe": (
        ((8, 32, 1024, 128), (8, 32, 1024, 64)), ((8, 32, 1024, 128), (8, 1, 1024, 64)), (8, 32, 1024, 128), None, 3),
    "phi4flash": (((8, 40, 1024, 64),), ((8, 20, 1024, 64),), (8, 10, 1024, 128), 512, 2),
    "olmo_hybrid": (((4, 30, 2048, 128),), ((4, 30, 2048, 128),), (4, 30, 2048, 128), None, 2),
    # mimo_v2_5_rerank: 192-wide keys over 128-wide values, 4 key-value heads on a full layer, 8 and a sink on a
    # window layer: the keys' pieces of a head are 2048 x 1152 bfloat16, the largest scratch of any cell
    "mimo_v2_full": (((4, 64, 2048, 192),), ((4, 4, 2048, 192),), (4, 4, 2048, 128), None, 3),
    "mimo_v2_window_sink": (((4, 64, 2048, 192),), ((4, 8, 2048, 192),), (4, 8, 2048, 128), 128, 3, True),
    "mimo_v2_full_sink": (((4, 64, 2048, 192),), ((4, 4, 2048, 192),), (4, 4, 2048, 128), None, 3, True),
    # falcon_h1_34b_rerank: 20 query heads over 4 key-value heads, 5 a group (the others' groups are 1, 2, 8 and 16)
    "falcon_h1": (((4, 20, 2048, 128),), ((4, 4, 2048, 128),), (4, 4, 2048, 128), None, 2),
    # nemotron3_super_120b_rerank (PR 60): 32 query heads over 2 key-value heads, 16 a group at head 128 (mimo_v2's 16
    # are 192 wide), 8 rows, three pieces
    "nemotron_h": (((8, 32, 2048, 128),), ((8, 2, 2048, 128),), (8, 2, 2048, 128), None, 3),
    # qwen3_next_80b_rerank (PR 61): 16 query heads over 2 key-value heads of 256 / 256 at three pieces, which fits held
    # compact and is refused in pairs (21.5 MiB by this compiler's count)
    "qwen3_next": (((8, 16, 2048, 256),), ((8, 2, 2048, 256),), (8, 2, 2048, 256), None, 3),
    # PR 50's edge (PERF.md section 7, closed in PR 61): mimo_v2's 192 / 128 at 16 query heads a key-value head with a
    # window's mask AND a sink, and the same at four pieces; the chip's verdicts are in PERF.md section 6, PR 61
    "mimo_v2_edge_window_sink": (((4, 64, 2048, 192),), ((4, 4, 2048, 192),), (4, 4, 2048, 128), 128, 3, True),
    "mimo_v2_edge_four_pieces": (((4, 64, 2048, 192),), ((4, 4, 2048, 192),), (4, 4, 2048, 128), 128, 4, True),
    # sdar_30b_a3b_rerank (PR 64): 32 query heads over 4 key-value heads of 128, 8 rows, three pieces, under the BLOCK
    # mask (ATTENTION_SPANS: a query sees to the end of its block of 4; the key blocks walked are the causal mask's)
    "sdar_moe": (((8, 32, 2048, 128),), ((8, 4, 2048, 128),), (8, 4, 2048, 128), None, 3),
}
# The block mask's span, for the forms that have one (`attention(span=)`); every other form compiles with None.
ATTENTION_SPANS = {"sdar_moe": 4}


@pytest.mark.parametrize("form", sorted(ATTENTION_SHAPES))
def test_attention_kernel_compiles_at_the_cells_top_rungs(one_chip, no_compile_cache, form):
    from distributed_tf_serving_tpu.ops.attention_kernel import attention

    qs, ks, v, window, count, *sunk = ATTENTION_SHAPES[form]
    shaped = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)  # noqa: E731
    run = functools.partial(
        attention, scale=sum(q[-1] for q in qs) ** -0.5, window=window, cd=jnp.dtype(jnp.bfloat16), count=count,
        span=ATTENTION_SPANS.get(form))
    sink = {"sink": shaped((qs[0][1],))} if sunk else {}  # within the default VMEM: the kernel asks for no more
    compiled = jax.jit(run).lower(tuple(map(shaped, qs)), tuple(map(shaped, ks)), shaped(v), **sink).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    assert compiled.memory_analysis().generated_code_size_in_bytes < 2 << 20  # one kernel a layer


# ------------------------------------------- the gated delta rule's chunk pass (PR 52)
#
# Mosaic's verdict on the kernel alone at the cell's two rungs: a head's tile
# a static slice of lanes that are no whole vregs (96 and 192 columns a head),
# the transposed product of the state's update, a last group of heads that
# hangs over the array's edge (30 heads in groups of 8).

# (key heads, value heads, key and value width a head): olmo_hybrid_rerank's 30 heads of 96 / 192 one to one, a group
# of 8 that hangs over the edge; qwen3_next_80b_rerank's 32 value heads over 16 key heads of 128 / 128 (PR 58: every
# head a lane block; PR 59: a step's 8 value heads read the 4 key heads' 512 lanes of q and k as they lie), at its
# three pieces
DELTA_SHAPES = {"olmo_hybrid": (30, 30, 96, 192, (4, 2), (2, 1)), "qwen3_next": (16, 32, 128, 128, (8, 2), (3,))}


@pytest.mark.parametrize("form,rows,count", [
    (form, rows, count) for form, (*_, rungs, counts) in sorted(DELTA_SHAPES.items())
    for rows in rungs for count in counts])
def test_delta_kernel_compiles_at_the_cells_rungs(one_chip, no_compile_cache, form, rows, count):
    from distributed_tf_serving_tpu.ops.delta_kernel import chunk_pass

    keys, heads, dk, dv = DELTA_SHAPES[form][:4]
    length, chunk = 2048, 64
    shaped = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)  # noqa: E731
    run = functools.partial(chunk_pass, heads=heads, cd=jnp.dtype(jnp.bfloat16), count=count)
    compiled = jax.jit(run).lower(
        shaped(rows, heads, length // chunk, chunk), shaped(rows, length, keys * dk), shaped(rows, length, keys * dk),
        shaped(rows, length, heads * dv), shaped(rows, length // chunk, heads, chunk, chunk),
        shaped(rows, heads, dk, dv)).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text and "vmem_limit" not in text
    assert all(0 < size < DEFAULT_VMEM // 2 for size in kernels_vmem(text, "delta_rule"))  # 6.7 MB; 4.7 MB at 128 / 128
    assert compiled.memory_analysis().generated_code_size_in_bytes < 2 << 20  # one kernel a layer


# ------------------------------------------- Mamba-2's SSD chunk walk (PR 55)
#
# Mosaic's verdict on the kernel alone at the cell's two rungs: a head's tile
# a static 128-lane slice of the step's 1,024, B turned once a step and the
# states turned in and out at a row's ends (`[128, 256]` float32), eight
# states in scratch beside the pipeline's blocks.

# (heads, groups, a head's width, the state's width, the rungs' rows, the pieces): falcon_h1_34b_rerank's 32 heads of
# 128 over 2 groups, a `[128, 256]` state, 8 heads a step; nemotron3_super_120b_rerank's 128 heads of 64 (HALF a lane
# tile: a head's tile a static 64-lane slice of the step's 1,024) over 8 groups, a `[64, 128]` state, a whole group of
# 16 a step (PR 60), at its three pieces
SSD_SHAPES = {"falcon_h1": (32, 2, 128, 256, (4, 2), (2, 1)), "nemotron_h": (128, 8, 64, 128, (8, 2), (3,))}


@pytest.mark.parametrize("form,rows,count", [
    (form, rows, count) for form, (*_, rungs, counts) in sorted(SSD_SHAPES.items()) for rows in rungs for count in counts])
def test_ssd_kernel_compiles_at_the_cells_rungs(one_chip, no_compile_cache, form, rows, count):
    from distributed_tf_serving_tpu.ops.ssd_kernel import chunk_walk

    heads, groups, width, wide = SSD_SHAPES[form][:4]
    length, chunk = 2048, 128
    shaped = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)  # noqa: E731
    run = functools.partial(chunk_walk, heads=heads, groups=groups, cd=jnp.dtype(jnp.bfloat16), count=count)
    compiled = jax.jit(run).lower(
        shaped(rows, heads, length // chunk, chunk), shaped(rows, heads, length // chunk, chunk),
        shaped(rows, length, heads * width), shaped(rows, length, groups * wide), shaped(rows, length, groups * wide),
        shaped(rows, heads, width, wide)).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text and "vmem_limit" not in text
    # Falcon-H1's: 9.9 MiB alone, 8.1 MB in the step; Nemotron-H's 8.2 MB in the step
    assert all(0 < size < DEFAULT_VMEM * 3 // 4 for size in kernels_vmem(text, "ssd_chunks"))
    assert compiled.memory_analysis().generated_code_size_in_bytes < 2 << 20  # one kernel a layer


# ------------------------------------------- the convolution's kernel (PR 63)
#
# Mosaic's verdict on the sublane rolls, the walk's loop and the blocks at a lane offset of the projection's array, at
# both cells' widths and rungs.

# (the projection's width [z | x | B | C | dt], the channels' offset in it, the channels x | B | C, the rungs' rows)
CONV_SHAPES = {"falcon_h1": (9248, 4096, 5120, (4, 2)), "nemotron_h": (18560, 8192, 10240, (8, 4, 2))}


@pytest.mark.parametrize("form,rows", [(form, rows) for form, (*_, rungs) in sorted(CONV_SHAPES.items()) for rows in rungs])
def test_conv_kernel_compiles_at_the_cells_rungs(one_chip, no_compile_cache, form, rows):
    from distributed_tf_serving_tpu.ops.conv_kernel import causal_conv

    width, offset, channels = CONV_SHAPES[form][:3]
    shaped = lambda *shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    run = functools.partial(causal_conv, offset=offset, channels=channels)
    compiled = jax.jit(run).lower(shaped(rows, 2048, width), shaped(channels, 4, dtype=jnp.bfloat16),
                                  shaped(channels, dtype=jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text and "vmem_limit" not in text
    # the projection crosses whole, twice, and no slice of it is made (alone, the compiler lays Falcon-H1's 9,248-wide
    # ARGUMENT out positions-minor and turns it round first; in the step the product writes it as the kernel reads it:
    # `assert_the_mixers_channels_cross_where_they_lie`)
    assert re.search(rf"%causal_conv[.\d]* = f32\[{rows},2048,{channels}\]\S* custom-call\(%(\S+), %\1, ", text)
    assert " slice(" not in text[text.index("ENTRY"):]
    # 8.45 MB by Mosaic's count (two 2 MiB blocks in and out, twice each for the pipeline): under the 16 MiB a kernel
    # has by default with half a MiB to spare, as the compact attention is held (a 4 MiB block is refused by the chip)
    assert [0 < size <= DEFAULT_VMEM - (1 << 19) for size in kernels_vmem(text, "causal_conv")] == [True]
    assert compiled.memory_analysis().generated_code_size_in_bytes < 1 << 20


# ------------------------------------------- the grouped kernels (PR 51)
#
# The held experts of a routed layer as one pass of two Pallas kernels over
# row tiles (ops/grouped_kernel.py): Mosaic's verdict on the strided row
# copies, the pieces one under the other and the scratch at the three routed
# cells' top rungs and at the last layer's few tokens, within the 16 MiB of
# VMEM a kernel has by default; and what the served steps hold since.

# (hidden, an expert's width, tokens a routed layer at all positions[, the compute dtype where not bfloat16
# [, the experts held and the experts a token where not 8 and 8[, the experts' form where not the gated one]]])
GROUPED_SHAPES = {
    "exaone_moe": (6144, 2048, 8192), "pangu_moe": (7680, 2048, 8192), "mimo_v2": (4096, 2048, 8192),
    "exaone_moe_last_layer": (6144, 2048, 4), "pangu_moe_last_layer": (7680, 2048, 8),
    # a float32 compute dtype (the precision readings' stand-in for the reference, a float32 TOML): the weights'
    # blocks hold half the columns, the same bytes. As first written they held as many, and inside a whole step the
    # chip refused `grouped_down` at run time (PERF.md section 6, PR 51, call 7) though this compile of the kernels
    # alone passed: necessary, not sufficient, as PR 50 found of the attention's.
    "pangu_moe_float32": (7680, 2048, 8192, jnp.float32), "mimo_v2_float32": (4096, 2048, 8192, jnp.float32),
    # configs/*_moe_small.toml and mimo_v2_small.toml (what `chip_smoke.py --config` serves): an expert half a lane row wide
    "small_tomls": (128, 64, 160),
    # qwen3_next_80b_rerank (PR 58): 128 of 512 experts held at top-10, the gates' lane row exactly full; the layout's
    # 1,408 tiles where `[held, T]` rows would be 16,384 tiles (4.3 GB between the kernels)
    "qwen3_next": (2048, 512, 16384, jnp.bfloat16, 128, 10), "qwen3_next_last_layer": (2048, 512, 8, jnp.bfloat16, 128, 10),
    # nemotron3_super_120b_rerank (PR 60): UNGATED experts (two matrices, `grouped_up` against one weight) whose rows are
    # the LATENT's 1,024 wide and not the residual's 4,096, 2,688 wide inside (21 lane tiles: blocks of 896), 64 of 512
    # held at top-22: a layout of 2,880 tiles (3.96 GB between the kernels) of which the even share fills an eighth
    "nemotron_h": (1024, 2688, 16384, jnp.bfloat16, 64, 22, "relu2"), "nemotron_h_bottom_rung": (1024, 2688, 4096, jnp.bfloat16, 64, 22, "relu2"),
    # sdar_30b_a3b_rerank (PR 64): the first layer held WHOLE, 128 of 128 experts at top-8 (every one of a token's choices
    # is here: a layout of 131,072 + 128 x 128 = 147,456 rows, 453 MB between the kernels), and the narrowest experts
    # yet, 768 wide: six lane tiles, `_block(768, N_BLOCK)` is 768, the first block that is no power of two
    "sdar_moe": (2048, 768, 16384, jnp.bfloat16, 128, 8), "sdar_moe_last_layer": (2048, 768, 8, jnp.bfloat16, 128, 8),
}


@pytest.mark.parametrize("form", sorted(GROUPED_SHAPES))
def test_grouped_kernels_compile_at_the_cells_top_rungs(one_chip, no_compile_cache, form):
    from distributed_tf_serving_tpu.models.routed import layout_tiles
    from distributed_tf_serving_tpu.ops.grouped_kernel import TILE, grouped_experts

    hidden, width, tokens, *rest = GROUPED_SHAPES[form]
    cd = jnp.dtype(rest[0] if rest else jnp.bfloat16)
    held, top_k, *form = rest[1:] or (8, 8)
    tiles = layout_tiles(tokens, top_k, held, TILE)
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    run = functools.partial(grouped_experts, cd=cd, count=3)
    compiled = jax.jit(run).lower(
        None if form == ["relu2"] else shaped((held, hidden, width), cd), shaped((held, hidden, width), cd),
        shaped((held, width, hidden), cd), shaped((tokens, hidden), jnp.float32),
        shaped((tokens, held), jnp.float32), shaped((tiles, TILE), jnp.int32), shaped((tiles,), jnp.int32),
        shaped((tiles,), jnp.int32), shaped((), jnp.int32)).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert text.count('custom_call_target="tpu_custom_call"') == 2 and "vmem_limit" not in text
    assert ("grouped_up" if form else "grouped_gate_up") in text and "grouped_down" in text
    assert not re.findall(r"\) while\(", text)  # the routing decides the grids' length, and no loop of XLA's
    # Worst-case buffers: `[T x min(k, held) + held x TILE, F]` float32 between the kernels and nothing else the size
    # of the tokens (the sorted tokens and the sorted result are never made; the tokens' and the result's own bytes
    # are read as they lie).
    assert memory.temp_size_in_bytes < tiles * TILE * width * 4 + (8 << 20)
    assert memory.generated_code_size_in_bytes < 2 << 20


# The parent's (PR 50's) code bytes of the three routed cells' top rungs; each routed layer held eight `while` loops.
ROUTED_CELLS = {
    "k_exaone_moe_rerank": ("exaone_moe", 37.7e6), "pangu_ultra_moe_rerank": ("pangu_moe", 60.9e6),
    "mimo_v2_5_rerank": ("mimo_v2", 37.5e6),
}


@pytest.mark.parametrize("name", sorted(ROUTED_CELLS))
def test_a_routed_cells_step_holds_no_expert_loop(name, one_chip, no_compile_cache, served_on_a_tpu):
    """The top rung as the cell serves it: no `while` at all (the experts'
    loops were the step's only ones: 32, 32 and 48), the grouped kernels at
    the VMEM a kernel has by default, and no more code than the parent's."""
    kind, parents_code = ROUTED_CELLS[name]
    compiled, _ = sequence_cells_step(name, kind, one_chip)
    text = compiled.as_text()
    assert not re.findall(r"\) while\(", text)
    assert text.count("grouped_gate_up") and text.count("grouped_down") and "vmem_limit" not in text
    assert compiled.memory_analysis().generated_code_size_in_bytes < parents_code  # 23.1 / 23.1 / 25.4 MB


# sha256 (its first 16 digits) of the lowered text of a top-rung step at the
# parent (PR 50's tree, on this container's jax). Inside the served entry on a
# backend that answers `tpu` for the families without a routed layer, whose
# served steps PR 51 leaves as they were; OUTSIDE the entry (every CPU run, the
# GSPMD executors, `shard_map_score`, the trainer) for the three routed
# families, which keep XLA's loops there: their text is the parent's but for
# the one counter PR 51 adds to the loops (`moe.rows_computed`), so these three
# are PR 51's own, held here for the next change to be seen against. PR 57
# re-pins the two two-piece families here (`phi4flash` served, `olmo_hybrid`
# on both paths): their pieces meet a weight in ONE product, where
# `sequence.product` left them a product a piece; the three routed families'
# digests, three pieces a product, passed that change untouched.
LOWERED_TEXT = {
    "phi4_mini_flash_rerank/phi4flash/served": "8e0e9b40cf785a63",
    # PR 52: the rule's chunk pass is the kernel and the counters leave with the logits (one barrier) ...; PR 57's text
    "olmo_hybrid_rerank/olmo_hybrid/served": "013d7048fcf30596",
    # ... and XLA's path is the parent's but for that barrier (970016a6f6fb2d72 on both trees before it); PR 57's text
    "olmo_hybrid_rerank/olmo_hybrid/outside": "2796b2ffd7c03eba",
    "dcn_v2_ref43/dcn_v2/served": "c4b1ba715cf54e70",
    "dlrm_dcnv2_mlperf/dlrm_dcnv2/served": "9bbd2eb81f11ee6f",
    # PR 58: the pairs of a routed layer laid out by ONE sort and walked by ONE loop over their tiles, whatever the
    # number of experts held, and a fifth counter (`moe.experts_hit`): the three routed families' XLA path is PR 58's
    # own (e21defa4c02679fe, e9d6693e5443e71c, cf958181426246d5 before it), held here for the next change
    "k_exaone_moe_rerank/exaone_moe/outside": "64088851d872d0ce",
    "pangu_ultra_moe_rerank/pangu_moe/outside": "501f02b4922b3960",
    "mimo_v2_5_rerank/mimo_v2/outside": "08288534fdd2baf4",
    # PR 59: the rule's key-side work once a KEY head (q and k the 16 key heads they are into the kernel, `K K'` and
    # `Q K'` made for a key head and read by its two value heads); olmo_hybrid's two digests above, one key head a
    # value head, passed that change untouched. PR 59's own, held here for the next change to be seen against
    # PR 61: the full layer's attention at all positions is the attention kernel, held compact (one more custom call,
    # its keys and values handed over where they lie, and XLA's four query blocks gone: 7b7b2b0f57ed4461 before it); the
    # nine other digests, every served step that runs the same kernel in pairs among them, passed that change untouched
    "qwen3_next_80b_rerank/qwen3_next/served": "bed69f12ee1b07f2",
    # PR 60: the eighth family's served step (the SSD and attention kernels, the grouped kernels at the ungated form
    # over the latent's rows); the seven digests above passed PR 60's changes to `routed.held_experts`, `route`,
    # `falcon_h1.ssm` and both kernels' files untouched. PR 60's own, held here for the next change to be seen against
    # PR 63: the six Mamba-2 mixers' convolution is the kernel that reads the projection where it lies, and the five
    # SSD kernels read x, B and C as windows of its one result (six more custom calls; aa7a7c3ae1dcfb18 before it); the
    # nine other digests, `phi4flash`, `olmo_hybrid` and `qwen3_next` (`sequence.causal_conv`'s other callers) among
    # them, passed that change untouched
    "nemotron3_super_120b_rerank/nemotron_h/served": "b7516d9acbaf0e05",
    # PR 64: the ninth family's served step (the attention kernel under the block mask, `span` 4; the grouped kernels over
    # a layer held whole); the eleven digests above passed PR 64's `span` through `sequence.causal_softmax`,
    # `query_blocks`, `attention_choice`, `blocked_attention` and `ops/attention_kernel.py` untouched: with `span=None`
    # every other family lowers to the text it had. PR 64's own, held here for the next change to be seen against
    "sdar_30b_a3b_rerank/sdar_moe/served": "7e268c6c2d5f5e92",
}


def lowered_text_digest(name: str, kind: str, where: str, one_chip) -> str:
    from distributed_tf_serving_tpu.models import embeddings, sequence

    model, rows, fields = cells_model(name, kind)
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)
    batch = {"feat_ids": jax.ShapeDtypeStruct((rows, fields), jnp.int32),
             "feat_wts": jax.ShapeDtypeStruct((rows, fields), jnp.bfloat16)}
    if model.takes_dense:
        batch["dense_features"] = jax.ShapeDtypeStruct((rows, model.config.num_dense_features), jnp.float32)
    run = model.apply_stats if model.step_stats else model.apply

    def served(p, b):
        with embeddings.serving_gathers([]), sequence.serving_attention([]):
            return run(p, b)

    params = jax.eval_shape(functools.partial(model.init, packed=True), jax.random.PRNGKey(0))
    text = jax.jit(served if where == "served" else run).lower(on_chip(params), on_chip(batch)).as_text()
    # A Pallas kernel's serialized body names the files and the callers it was traced from (this test's own among
    # them): left out. Its operands, results, grid and name stay in.
    text = re.sub(r'\\22body\\22: \\22[A-Za-z0-9+/=]*\\22', "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("key", sorted(LOWERED_TEXT))
def test_a_step_outside_the_grouped_kernels_lowers_to_the_text_it_had(key, one_chip, served_on_a_tpu, monkeypatch):
    from distributed_tf_serving_tpu.models import embeddings

    monkeypatch.setattr(embeddings.jax, "default_backend", lambda: "tpu")
    name, kind, where = key.split("/")
    assert lowered_text_digest(name, kind, where, one_chip) == LOWERED_TEXT[key]
