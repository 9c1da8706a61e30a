"""Collects ``benchmarks/tests/test_manifest.py`` under tier-1."""

from benchmarks.tests.test_manifest import *  # noqa: F401,F403
