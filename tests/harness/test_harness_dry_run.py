"""Collects ``benchmarks/tests/test_dry_run.py`` under tier-1."""

from benchmarks.tests.test_dry_run import *  # noqa: F401,F403
