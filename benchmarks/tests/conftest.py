"""The harness's own tests: ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests -q``. Not part of tier-1 (``tests/``)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, os.path.join(BENCH, "readers"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
