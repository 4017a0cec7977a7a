"""Tests of the yardstick itself.  They run on the CPU in seconds
(``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``) and are not
part of tier 1's ``tests/``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
