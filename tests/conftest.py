"""Test harness: force an 8-virtual-device CPU JAX backend.

Multi-core placement, sharding and mesh logic all run on a simulated
8-device CPU platform so the suite never needs TPU hardware — the
idiomatic JAX substitute for a fake backend (SURVEY.md §4).

The platform is forced twice, before the first backend use: the
JAX_PLATFORMS default covers a plain shell, and the jax.config update
overrides an exported ``JAX_PLATFORMS=tpu,cpu`` — what a machine with
a chip sets — so the suite never opens an accelerator that a serving
process on the same host may hold (one process at a time owns a chip).
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import pytest

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# the token families' shared assertions live in a module that is not
# collected: its ``assert``s report what they compared all the same
pytest.register_assert_rewrite("family_contract")


@pytest.fixture(autouse=True)
def _lock_witness():
    """Run every test under the runtime lock-order witness: any pool,
    cache, pager or health object a test constructs gets
    witnessed locks, so lock-order inversions and ``*_locked``
    convention breaches surface as recorded violations wherever a test
    (or the races gate) chooses to assert on them. The fixture itself
    never asserts — a test that wants the discipline checked reads
    ``lockwitness.summary()`` explicitly."""
    from rnb_tpu import lockwitness
    lockwitness.enable()
    lockwitness.reset()
    yield
    lockwitness.reset()
