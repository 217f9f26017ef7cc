"""The reader of `pallas_delta_pct.bulk` on made-up windows: with the
program's `startup.delta_rule` stamp and without it (the commit before
ISSUE 52, which the driver runs this reader over)."""
import os

import pytest

from benchmark.common import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
KERNEL = {"kernel": "pallas", "chunk": 64, "pieces": 2}
XLA = {"kernel": "xla", "chunk": 64, "pieces": 2}


@pytest.fixture(scope="module")
def read():
    import sys

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "layers"))
    try:
        return load_module(
            os.path.join(ROOT, "benchmark", "layers", "pallas_delta_pct.py"), "reader_pallas_delta_pct"
        ).read
    finally:
        sys.path.pop(0)


@pytest.mark.parametrize("stamp, batches, kernel_batches, want", [
    ({"olmo_hybrid:1": KERNEL}, 120, 120, 100.0),  # every batch ran the kernel's entry
    ({"olmo_hybrid:1": KERNEL}, 120, 30, 25.0),
    ({"M:1": XLA}, 12, 0, 0.0),  # stamped, and XLA's scan
    (None, 120, 0, None),  # the parent: no stamp, the metric is left out
    ({}, 400, 0, None),  # a family without the rule: nothing stamped
    ({"olmo_hybrid:1": KERNEL}, 0, 0, None),  # a window without a batch
])
def test_share_of_the_windows_batches(read, stamp, batches, kernel_batches, want):
    phases = {}
    if batches:
        phases["batch.dispatch"] = {"count": batches, "total_ms": 1.0}
    if kernel_batches:
        phases["batch.delta_kernel"] = {"count": kernel_batches, "total_ms": 0.0}
    startup = {"warmup_s": 1.2} if stamp is None else {"warmup_s": 1.2, "delta_rule": stamp}
    assert read({"phases": phases, "runtime": {"startup": startup}}) == want


def test_a_runtime_block_without_startup_reads_nothing(read):
    assert read({"phases": {"batch.dispatch": {"count": 3, "total_ms": 1.0}}, "runtime": {}}) is None
