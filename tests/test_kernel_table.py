"""The batcher's one table of served Pallas kernels (serving/batcher.py
SERVED_KERNELS): every kind's stamp, counter and phase derive from its row,
and the bookkeeping around a trace is one loop for all six.

The model here is a small DCN-v2 whose `apply` also notes, as a family's
`takes_kernel` would at trace time, what a kind's `*_choice` "chose" for the
rung it is traced at: the table's bookkeeping is what is under test, not a
kernel (tests/test_<kind>_kernel.py hold each kernel to its XLA path)."""

import dataclasses
import pathlib

import jax
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import (
    ModelConfig,
    Servable,
    build_model,
    ctr_signatures,
    embeddings,
    sequence,
)
from distributed_tf_serving_tpu.serving.batcher import (
    SERVED_KERNELS,
    BatcherStats,
    DynamicBatcher,
    prepare_inputs,
)
from distributed_tf_serving_tpu.utils.metrics import ServerMetrics
from distributed_tf_serving_tpu.utils.tracing import request_trace

# What the readers pin (benchmark/layers/pallas_<reader>_pct.py, the metrics
# block, tests/test_request_timeline.py): kind -> (stamp, counter, phase,
# the reader's file, the note's key that says "pallas").
PINNED = {
    "gather": ("gather", "gather_kernel_batches", "batch.gather_kernel", "gather", "kernel"),
    "attention": ("attention", "attention_kernel_batches", "batch.attention_kernel", "attention", "kernel"),
    "grouped": ("grouped", "grouped_kernel_batches", "batch.grouped_kernel", "grouped", "kernel"),
    "delta": ("delta_rule", "delta_kernel_batches", "batch.delta_kernel", "delta", "kernel"),
    "ssd": ("ssd", "ssd_kernel_batches", "batch.ssd_kernel", "ssd", "path"),
    "conv": ("conv", "conv_kernel_batches", "batch.conv_kernel", "conv", "path"),
}
KINDS = list(PINNED)
# A kind's note as its `*_choice` writes it: `which` under the row's key, `v`
# under the field the row ranks by (the delta rule's rank is empty).
NOTE = {
    "gather": lambda which, v: {"kernel": which, "row_bytes": 512, "in_flight": v, "picked_in_kernel": False},
    "attention": lambda which, v: {"kernel": which, "block": v, "pieces": 3},
    "grouped": lambda which, v: {"kernel": which, "tile": 128, "pieces": 3, "held": 4, "rows": v},
    "delta": lambda which, v: {"kernel": which, "chunk": 64, "pieces": 2},
    "ssd": lambda which, v: {"path": which, "chunk": v, "state_bytes_a_row": 16384},
    "conv": lambda which, v: {"path": which, "lanes": 1024, "positions": v},
}
CFG = ModelConfig(num_fields=6, vocab_size=509, embed_dim=8, mlp_dims=(16,), num_cross_layers=1,
                  cross_full_matrix=True, compute_dtype="float32")


def _notes_of(kind):
    """The list a family's trace appends that kind's choice to, None outside
    the batcher's one-chip entry."""
    if kind == "gather":
        entry = getattr(embeddings._served, "entry", None)
        return None if entry is None else entry[0]
    served = sequence.served_entry()
    return None if served is None else served.notes if kind == "attention" else getattr(served, kind)


def _servable(kind, choose, version=1):
    """DCN-v2 whose trace notes `choose(rows)`, a (which, v), for `kind`."""
    base = build_model("dcn_v2", CFG)

    def apply(params, batch):
        notes = _notes_of(kind)
        if notes is not None:
            notes.append(NOTE[kind](*choose(batch["feat_ids"].shape[0])))
        return base.apply(params, batch)

    return Servable(name="M", version=version, model=dataclasses.replace(base, apply=apply),
                    params=base.init(jax.random.PRNGKey(0)), signatures=ctr_signatures(CFG.num_fields))


def _payload(n, seed=0):
    rng = np.random.RandomState(seed)
    return {"feat_ids": rng.randint(0, 1 << 40, size=(n, CFG.num_fields)).astype(np.int64),
            "feat_wts": rng.rand(n, CFG.num_fields).astype(np.float32)}


def _phase(name):
    return request_trace.snapshot().get(name, {}).get("count", 0)


def _row(kind):
    return next(k for k in SERVED_KERNELS if k.kind == kind)


def test_the_table_holds_the_six_kinds_in_order():
    assert [k.kind for k in SERVED_KERNELS] == KINDS


@pytest.mark.parametrize("kind", KINDS)
def test_a_rows_names_are_the_ones_the_readers_pin(kind):
    stamp, counter, phase, reader, pallas_key = PINNED[kind]
    row = _row(kind)
    assert (row.stamp, row.counter, row.phase, row.pallas_key) == (stamp, counter, phase, pallas_key)
    assert counter in {f.name for f in dataclasses.fields(BatcherStats)}
    source = (pathlib.Path(__file__).parents[1] / "benchmark" / "layers" / f"pallas_{reader}_pct.py").read_text()
    assert f'.get("{stamp}")' in source and f'phase_count(ctx, "{phase}")' in source
    batcher = DynamicBatcher(buckets=(4,), max_wait_us=0)
    assert set(batcher.kernel_stamps()) == {p[0] for p in PINNED.values()}
    method = {"gather": batcher.gathers, "attention": batcher.attentions, "grouped": batcher.groupeds,
              "delta": batcher.delta_rules, "ssd": batcher.ssds,
              "conv": lambda: batcher._stamp("conv")}[kind]  # the newest kind has no older name
    assert method() == {} == batcher.kernel_stamps()[stamp]


@pytest.mark.parametrize("kind", KINDS)
def test_the_metrics_block_counts_a_kinds_batches(kind):
    counter = PINNED[kind][1]
    stats = BatcherStats(batches=3, fused_batches=3, **{counter: 2})
    block = ServerMetrics().snapshot(batcher_stats=stats)["batcher"]
    assert block["batches"] == 3 and block[counter] == 2
    others = [PINNED[k][1] for k in KINDS if k != kind]
    assert [block[c] for c in others] == [0] * len(others) and stats.kernel_batches()[counter] == 2


@pytest.mark.parametrize("kind", KINDS)
def test_building_the_entry_again_resets_a_kinds_notes_and_membership(kind):
    """A rebuilt entry (the per-key fallback of _execute; a swapped version is
    a new Servable) starts from no notes and no membership: what the old
    entry's traces chose does not count the new one's batches."""
    which = ["pallas"]
    sv = _servable(kind, lambda rows: (which[0], 64))
    row, counter = _row(kind), PINNED[kind][1]
    batcher = DynamicBatcher(buckets=(4,), max_wait_us=0).start()
    try:
        before = _phase(row.phase)
        batcher.submit(sv, _payload(3)).result(timeout=120)
        assert batcher.kernel_stamps()[row.stamp] == {"M:1": NOTE[kind]("pallas", 64)}
        assert getattr(batcher.stats, counter) == 1 and _phase(row.phase) - before == 1
        assert batcher._kernel_kinds[sv] == (row,)
        which[0] = "xla"
        with batcher._jit_lock:
            batcher._jitted[sv] = batcher._build_entry(sv, combined=True)
        assert sv not in batcher._kernel_kinds and batcher._stamp(kind) == {}
        batcher.submit(sv, _payload(3, seed=1)).result(timeout=120)
        assert batcher.kernel_stamps()[row.stamp] == {"M:1": NOTE[kind]("xla", 64)}
        assert batcher._kernel_kinds[sv] == ()
        assert getattr(batcher.stats, counter) == 1 and _phase(row.phase) - before == 1
        assert batcher.stats.batches == 2
    finally:
        batcher.stop()


@pytest.mark.parametrize("kind", KINDS)
def test_a_custom_run_fn_leaves_no_stamp_and_counts_nothing(kind):
    """A run_fn (the mesh executors) traces `model.apply` itself, outside
    serving_gathers and serving_attention: the model sees no served entry."""
    sv = _servable(kind, lambda rows: ("pallas", 64))
    seen = []

    def run_fn(servable, arrays):
        seen.append(_notes_of(kind))
        return servable.model.apply(servable.params, arrays)

    row = _row(kind)
    batcher = DynamicBatcher(buckets=(4,), max_wait_us=0, run_fn=run_fn).start()
    try:
        before = _phase(row.phase)
        got = batcher.submit(sv, _payload(3)).result(timeout=120)["prediction_node"]
        assert seen == [None] and got.shape == (3,)
        assert batcher.kernel_stamps() == {k.stamp: {} for k in SERVED_KERNELS}
        assert batcher.stats.batches == 1 and batcher.stats.kernel_batches() == {k.counter: 0 for k in SERVED_KERNELS}
        assert _phase(row.phase) == before and not batcher._kernel_kinds
    finally:
        batcher.stop()


@pytest.mark.parametrize("kind", KINDS)
def test_where_rungs_differ_the_stamp_is_the_rows_highest_note(kind):
    """The kernel's note before XLA's whatever the rest says, then the row's
    own order; and one rung with the kernel makes every batch of the servable
    count, as the five `in` tests did."""
    chosen = {4: ("xla", 900), 8: ("pallas", 100), 16: ("pallas", 200)}
    sv = _servable(kind, chosen.__getitem__)
    row, counter = _row(kind), PINNED[kind][1]
    batcher = DynamicBatcher(buckets=(4, 8, 16), max_wait_us=0).start()
    try:
        batcher.submit(sv, _payload(3)).result(timeout=120)
        assert batcher._stamp(kind) == {"M:1": NOTE[kind]("xla", 900)} and getattr(batcher.stats, counter) == 0
        for n in (7, 13, 2):
            batcher.submit(sv, _payload(n, seed=n)).result(timeout=120)
        want = NOTE[kind]("pallas", 100 if not row.rank(NOTE[kind]("pallas", 100)) else 200)
        assert batcher._stamp(kind) == {"M:1": want}
        assert batcher.stats.batches == 4 and getattr(batcher.stats, counter) == 3
    finally:
        batcher.stop()


def test_the_stamps_reach_the_runtime_block_by_the_tables_walk():
    from distributed_tf_serving_tpu.models import ServableRegistry
    from distributed_tf_serving_tpu.serving import PredictionServiceImpl

    sv = _servable("ssd", lambda rows: ("pallas", 64))
    registry = ServableRegistry()
    registry.load(sv)
    batcher = DynamicBatcher(buckets=(4,), max_wait_us=0).start()
    try:
        impl = PredictionServiceImpl(registry, batcher)
        want = np.asarray(sv.model.apply(sv.params, prepare_inputs(sv.model, _payload(3)))["prediction_node"])
        got = batcher.submit(sv, _payload(3)).result(timeout=120)["prediction_node"]
        np.testing.assert_allclose(got, want, rtol=1e-6)
        startup = impl.runtime_stats()["startup"]
        assert startup["ssd"] == {"M:1": NOTE["ssd"]("pallas", 64)}
        assert startup["gather"]["M:1"]["kernel"] == "xla"  # DCN's own lookup, on the CPU
        assert [startup[s] for s in ("attention", "grouped", "delta_rule", "conv", "products")] == [{}] * 5
    finally:
        batcher.stop()
