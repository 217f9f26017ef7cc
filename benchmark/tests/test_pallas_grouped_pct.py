"""The reader of `pallas_grouped_pct.bulk` on made-up windows: with the
program's `startup.grouped` stamp and without it (the commit before ISSUE 51,
which the driver runs this reader over)."""
import os

import pytest

from benchmark.common import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
KERNEL = {"kernel": "pallas", "tile": 128, "pieces": 3}
XLA = {"kernel": "xla", "tile": 256, "pieces": 3}


@pytest.fixture(scope="module")
def read():
    import sys

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "layers"))
    try:
        return load_module(
            os.path.join(ROOT, "benchmark", "layers", "pallas_grouped_pct.py"), "reader_pallas_grouped_pct"
        ).read
    finally:
        sys.path.pop(0)


@pytest.mark.parametrize("stamp, batches, kernel_batches, want", [
    ({"mimo_v2:1": KERNEL}, 120, 120, 100.0),  # every batch ran the kernels' entry
    ({"mimo_v2:1": KERNEL}, 120, 30, 25.0),
    ({"M:1": XLA}, 12, 0, 0.0),  # stamped, and XLA's loops
    (None, 120, 0, None),  # the parent: no stamp, the metric is left out
    ({}, 400, 0, None),  # a family without a routed layer: nothing stamped
    ({"mimo_v2:1": KERNEL}, 0, 0, None),  # a window without a batch
])
def test_share_of_the_windows_batches(read, stamp, batches, kernel_batches, want):
    phases = {}
    if batches:
        phases["batch.dispatch"] = {"count": batches, "total_ms": 1.0}
    if kernel_batches:
        phases["batch.grouped_kernel"] = {"count": kernel_batches, "total_ms": 0.0}
    startup = {"warmup_s": 1.2} if stamp is None else {"warmup_s": 1.2, "grouped": stamp}
    assert read({"phases": phases, "runtime": {"startup": startup}}) == want


def test_a_runtime_block_without_startup_reads_nothing(read):
    assert read({"phases": {"batch.dispatch": {"count": 3, "total_ms": 1.0}}, "runtime": {}}) is None
