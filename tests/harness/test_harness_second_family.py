"""Collects ``benchmarks/tests/test_second_family.py`` under tier-1."""

from benchmarks.tests.test_second_family import *  # noqa: F401,F403
