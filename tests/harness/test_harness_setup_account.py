"""Collects ``benchmarks/tests/test_setup_account.py`` under tier-1."""

from benchmarks.tests.test_setup_account import *  # noqa: F401,F403
