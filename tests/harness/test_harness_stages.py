"""Collects ``benchmarks/tests/test_stages.py`` under tier-1."""

from benchmarks.tests.test_stages import *  # noqa: F401,F403
