"""The reader of `fused_products_pct.bulk` on recorded runtime blocks: the
`startup.products` stamp as the program writes it since ISSUE 57 (a two-piece
servable whose every weight product is one product, a three-piece one; and
what the metric is kept for, a stamp whose operations are not all in one
product, which no form `product` has today writes), and the blocks of programs without it (the
commit before ISSUE 57, which the driver runs this reader over; a CTR
servable)."""
import os

import pytest

from benchmark.common import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# The `startup` block of `/monitoring?section=runtime` for `configs/falcon_h1_small.toml` on the CPU after one request
# of two rows (one rung traced), cut to what the reader reads and to its neighbours; and `configs/pangu_moe_small.toml`'s.
RECORDED = {
    "platform": "cpu", "warmup_s": 0.0,
    "startup": {
        "warmup_s": 0.0, "ssd": {"M:1": {"path": "xla", "chunk": 64, "state_bytes_a_row": 16384}},
        "attention": {"M:1": {"kernel": "xla", "block": 0, "pieces": 2}},
        "products": {"M:1": {"ops": 910622720, "fused_ops": 910622720, "forms": {"contracted": 36}}},
    },
}
RECORDED_THREE_PIECES = {
    "platform": "cpu", "warmup_s": 0.0,
    "startup": {
        "warmup_s": 0.0, "ssd": {}, "attention": {"M:1": {"kernel": "xla", "block": 0, "pieces": 3}},
        "products": {"M:1": {"ops": 428470272, "fused_ops": 428470272, "forms": {"stacked": 48}}},
    },
}


@pytest.fixture(scope="module")
def read():
    return load_module(
        os.path.join(ROOT, "benchmark", "layers", "fused_products_pct.py"), "reader_fused_products_pct").read


@pytest.mark.parametrize("runtime", [RECORDED, RECORDED_THREE_PIECES], ids=["two pieces", "three pieces"])
def test_the_recorded_block_reads_every_operation_in_one_product(read, runtime):
    assert read({"phases": {}, "runtime": runtime}) == 100.0


@pytest.mark.parametrize("stamp, want", [
    ({"M:1": {"ops": 400, "fused_ops": 400, "forms": {"stacked": 7}}}, 100.0),  # three pieces: the stacked form
    ({"M:1": {"ops": 400, "fused_ops": 400, "forms": {"contracted": 5, "stacked": 2}}}, 100.0),  # both forms, two rungs
    ({"M:1": {"ops": 400, "fused_ops": 300, "forms": {}}}, 75.0),  # the tripwire: a rule that leaves a shape unfused
    ({"M:1": {"ops": 400, "fused_ops": 0, "forms": {}}}, 0.0),
    ({"A:1": {"ops": 300, "fused_ops": 300, "forms": {}}, "B:2": {"ops": 100, "fused_ops": 0, "forms": {}}}, 75.0),
    ({"M:1": {"ops": 0, "fused_ops": 0, "forms": {}}}, None),  # nothing in pieces against a weight
    ({}, None),  # a CTR servable: no product in pieces
    (None, None),  # the commit before ISSUE 57: no stamp, the metric is left out
])
def test_share_of_the_operations_in_one_product(read, stamp, want):
    startup = {"warmup_s": 1.2} if stamp is None else {"warmup_s": 1.2, "products": stamp}
    assert read({"phases": {}, "runtime": {"startup": startup}}) == want


def test_a_runtime_block_without_startup_reads_nothing(read):
    assert read({"phases": {"batch.dispatch": {"count": 3, "total_ms": 1.0}}, "runtime": {}}) is None
