"""The benchmark harness's own tests (``benchmarks/tests``), collected
by the tier-1 command through one shim module a file. The shims' names
differ from the originals': ``test_second_family`` imports
``test_dry_run`` as a top-level module from ``benchmarks/tests``, which
a shim of that name would shadow."""

import os
import sys

import pytest

_HARNESS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "benchmarks", "tests")
if _HARNESS not in sys.path:
    sys.path.insert(0, _HARNESS)

#: fail by construction on the second configuration (PERF.md section
#: 7 h): the ``benchmark`` PR that moves the model-specific lines behind
#: the family file drops these marks (strict: a pass fails the run)
_KNOWN_FAILURES = {
    "test_harness_manifest.py::"
    "test_config_file_loads_through_the_programs_own_checks"
    "[nemotron3-nano-l14-ep2]",
    "test_harness_floor.py::"
    "test_largest_bucket_clears_the_floor[nemotron3-nano-l14-ep2]",
}


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.split("harness/", 1)[-1] in _KNOWN_FAILURES:
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="PERF.md section 7 h: model-specific "
                "lines of the harness's test, not yet behind the family "
                "file"))
