"""Collects ``benchmarks/tests/test_xplane.py`` under tier-1."""

from benchmarks.tests.test_xplane import *  # noqa: F401,F403
