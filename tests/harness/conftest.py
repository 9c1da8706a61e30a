"""The benchmark harness's own tests (``benchmarks/tests``), collected
by the tier-1 command through one shim module a file. The shims' names
differ from the originals': ``test_second_family`` imports
``test_dry_run`` as a top-level module from ``benchmarks/tests``, which
a shim of that name would shadow."""

import os
import sys

_HARNESS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "benchmarks", "tests")
if _HARNESS not in sys.path:
    sys.path.insert(0, _HARNESS)
