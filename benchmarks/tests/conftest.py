"""Tests of the benchmark's own arithmetic. Run from the repo root:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
