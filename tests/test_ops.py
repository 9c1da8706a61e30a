"""Ingest preprocess: ``normalize_u8`` against a NumPy formula.

One jnp form since PR 45 (the Pallas kernel these cases once ran
through the interpreter lost its measurement on the chip); the cases
keep its shapes — a clip, a block that does not divide, the range's
ends, the production dtype — against the formula written in NumPy.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from rnb_tpu.ops import normalize_u8

LANES = 128


def _formula(x):
    """(2x - 255) / 255 in float32: one rounding multiply."""
    return (np.asarray(x, np.float32) * 2.0 - 255.0) \
        * np.float32(1.0 / 255.0)


@pytest.mark.parametrize("shape", [(2, 2, 16, 16, 3), (15, 8, 112, 8, 2)])
def test_kernel_matches_reference(shape):
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, shape, dtype=np.uint8)
    got = normalize_u8(jnp.asarray(x), jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), _formula(x))


def test_kernel_ragged_final_block():
    # an element count that no lane-aligned block divides
    rng = np.random.default_rng(1)
    x = rng.integers(0, 256, (40, LANES - 1), dtype=np.uint8)
    got = normalize_u8(jnp.asarray(x), jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), _formula(x))


def test_range_endpoints():
    x = jnp.asarray([[0] * LANES, [255] * LANES], dtype=jnp.uint8)
    y = np.asarray(normalize_u8(x, jnp.float32))
    assert y.min() == pytest.approx(-1.0)
    assert y.max() == pytest.approx(1.0)


def test_kernel_matches_reference_bf16():
    # at the PRODUCTION dtype: rounded to bf16 exactly once, from the
    # same f32 intermediate
    x = jnp.arange(256, dtype=jnp.uint8).reshape(2, LANES)
    got = normalize_u8(x, jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    want = jnp.asarray(_formula(x)).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_empty_input_dispatch():
    x = jnp.zeros((0, 8, LANES), dtype=jnp.uint8)
    y = normalize_u8(x)
    assert y.shape == (0, 8, LANES) and y.dtype == jnp.bfloat16


def test_dispatch_off_tpu_falls_back():
    # the default dtype is the network's: bf16, contract numerics
    x = np.full((4, LANES), 128, dtype=np.uint8)
    y = normalize_u8(jnp.asarray(x))
    assert y.dtype == jnp.bfloat16
    want = jnp.asarray(_formula(x)).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(want, np.float32))


def test_network_normalize_delegates():
    from rnb_tpu.models.r2p1d.network import normalize_u8 as net_norm
    x = np.full((2, LANES), 255, dtype=np.uint8)
    np.testing.assert_array_equal(
        np.asarray(net_norm(jnp.asarray(x)), np.float32),
        np.asarray(normalize_u8(jnp.asarray(x)), np.float32))
