"""Collects ``benchmarks/tests/test_traffic.py`` under tier-1."""

from benchmarks.tests.test_traffic import *  # noqa: F401,F403
