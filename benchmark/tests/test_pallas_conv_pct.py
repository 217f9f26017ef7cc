"""The reader of `pallas_conv_pct.bulk` on made-up windows: with the program's
`startup.conv` stamp naming the kernel's path, naming XLA's (a CPU rehearsal,
a shape that is no whole blocks), and without it (the commit before ISSUE 63,
which the driver runs this reader over, and every family without the mixer)."""
import os

import pytest

from benchmark.common import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
KERNEL = {"path": "pallas", "lanes": 1024, "positions": 512}
XLA = {"path": "xla", "lanes": 0, "positions": 0, "why": "positions"}


@pytest.fixture(scope="module")
def read():
    import sys

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "layers"))
    try:
        return load_module(
            os.path.join(ROOT, "benchmark", "layers", "pallas_conv_pct.py"), "reader_pallas_conv_pct"
        ).read
    finally:
        sys.path.pop(0)


@pytest.mark.parametrize("stamp, batches, kernel_batches, want", [
    ({"falcon_h1:1": KERNEL}, 120, 120, 100.0),  # every batch ran the kernel's entry
    ({"falcon_h1:1": KERNEL}, 120, 30, 25.0),
    ({"M:1": XLA}, 12, 0, 0.0),  # stamped, and XLA's form: a row of no whole sublane tiles, a CPU rehearsal
    ({"M:1": {"lanes": 128}}, 12, 0, None),  # a stamp that names no path
    (None, 120, 0, None),  # no stamp (the parent): the metric is left out
    ({}, 400, 0, None),  # a family without the mixer: nothing stamped
    ({"falcon_h1:1": KERNEL}, 0, 0, None),  # a window without a batch
])
def test_share_of_the_windows_batches(read, stamp, batches, kernel_batches, want):
    phases = {}
    if batches:
        phases["batch.dispatch"] = {"count": batches, "total_ms": 1.0}
    if kernel_batches:
        phases["batch.conv_kernel"] = {"count": kernel_batches, "total_ms": 0.0}
    startup = {"warmup_s": 1.2} if stamp is None else {"warmup_s": 1.2, "conv": stamp}
    assert read({"phases": phases, "runtime": {"startup": startup}}) == want


def test_a_runtime_block_without_startup_reads_nothing(read):
    assert read({"phases": {"batch.dispatch": {"count": 3, "total_ms": 1.0}}, "runtime": {}}) is None
