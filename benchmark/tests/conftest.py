"""Tests of the benchmark's own code: CPU only, run with
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`` (not part of tier-1)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
