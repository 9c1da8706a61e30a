"""Collects ``benchmarks/tests/test_stamps.py`` under tier-1."""

from benchmarks.tests.test_stamps import *  # noqa: F401,F403
