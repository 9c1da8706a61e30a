"""Collects ``benchmarks/tests/test_floor.py`` under tier-1."""

from benchmarks.tests.test_floor import *  # noqa: F401,F403
