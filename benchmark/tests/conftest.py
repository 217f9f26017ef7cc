"""Tests of the benchmark's own arithmetic: `python -m pytest benchmark/tests -q`
from the root of the checkout, a few seconds on the CPU. They are the
benchmark's, not the program's: tier-1 (`tests/`) does not collect them."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for path in (ROOT, os.path.join(ROOT, "benchmark", "layers")):
    if path not in sys.path:
        sys.path.insert(0, path)
