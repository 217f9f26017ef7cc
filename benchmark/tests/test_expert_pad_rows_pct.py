"""The reader of `expert_pad_rows_pct.bulk` on made-up windows: with the
step's `moe.rows_computed` counter and without it (the commit before ISSUE 51,
which the driver runs this reader over)."""
import os

import pytest

from benchmark.common import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def read():
    import sys

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "layers"))
    try:
        return load_module(
            os.path.join(ROOT, "benchmark", "layers", "expert_pad_rows_pct.py"), "reader_expert_pad_rows_pct"
        ).read
    finally:
        sys.path.pop(0)


@pytest.mark.parametrize("assignments, computed, want", [
    (3654, 4096, 100.0 * (1 - 3654 / 4096)),  # the kernels' tiles of 128 rows
    (3654, 4608, 100.0 * (1 - 3654 / 4608)),  # XLA's blocks of 256
    (512, 512, 0.0),  # every tile full
    (0, 128, 100.0),  # one tile walked and no token in it
    (3654, None, None),  # the parent: it counts the assignments and not the rows
    (None, None, None),  # a family without a routed layer
    (0, 0, None),  # a window without a batch
])
def test_share_of_the_rows_computed_that_held_no_token(read, assignments, computed, want):
    phases = {"batch.dispatch": {"count": 3, "total_ms": 1.0}}
    if assignments is not None:
        phases["moe.assignments_here"] = {"count": assignments, "total_ms": 0.0}
        phases["moe.tokens"] = {"count": 8192, "total_ms": 0.0}
    if computed is not None:
        phases["moe.rows_computed"] = {"count": computed, "total_ms": 0.0}
    got = read({"phases": phases, "runtime": {"startup": {}}})
    assert got == want if want is None else got == pytest.approx(want)
