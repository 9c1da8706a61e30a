"""Rotary position embedding over a packed pool of rows, with YaRN's
frequencies.

A token's position is its index *inside its request*: in a packed pool
that is ``(row - row_start[row]) * Q + column`` (``row_start`` from the
segment table), so positions restart at each request and a prompt's
result does not depend on what it is packed beside.

The frequencies are YaRN's (Peng et al., arXiv:2309.00071, as the
DeepSeek-V2 modelling code computes them): the plain ``theta ** (-2i /
dim)`` for the dimensions that turn often inside the original context,
those divided by ``factor`` for the ones that turn rarely, and a linear
ramp between the two correction dimensions of ``beta_fast`` and
``beta_slow``. They are constants of the configuration, computed on
the host in float64 and kept in float32.

The rotation is the half-split one: the ``dim`` columns are two
halves, ``out = [x1 cos - x2 sin, x2 cos + x1 sin]``. A model published
with interleaved pairs (DeepSeek-V2 de-interleaves before rotating)
stores its rotary projections' columns evens first, then odds
(``models/seeded.py``: ``TensorSpec.halves``), at set-up and once.
Angles, sines and the rotation are float32; the result is cast to the
input's dtype.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def _correction_dim(turns: float, dim: int, theta: float,
                    original: int) -> float:
    """The (fractional) dimension whose wave makes ``turns`` turns over
    ``original`` positions."""
    return dim * math.log(original / (turns * 2 * math.pi)) \
        / (2 * math.log(theta))


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """(dim // 2,) float32 inverse frequencies."""
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    low = max(math.floor(_correction_dim(beta_fast, dim, theta, original)),
              0)
    high = min(math.ceil(_correction_dim(beta_slow, dim, theta, original)),
               dim - 1)
    if low == high:
        high += 0.001   # as the published code avoids the singularity
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return (plain / factor * (1.0 - keep) + plain * keep) \
        .astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """The factor YaRN puts on the attention's temperature (on cos and
    sin, or squared on the softmax scale)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def pool_positions(row_start, qlen: int):
    """(rows, Q) int32: each token's index inside its request."""
    rows = row_start.shape[0]
    first = (jnp.arange(rows, dtype=jnp.int32)
             - row_start.astype(jnp.int32)) * qlen
    return first[:, None] + jnp.arange(qlen, dtype=jnp.int32)[None, :]


def rotate(x, positions, inv_freq):
    """``x`` (rows, Q, ..., dim) in half-split layout; ``positions``
    (rows, Q); ``inv_freq`` (dim // 2,). -> x's shape and dtype."""
    angles = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(inv_freq, jnp.float32)
    lead = angles.shape[:2] + (1,) * (x.ndim - 3) + angles.shape[-1:]
    cos, sin = jnp.cos(angles).reshape(lead), jnp.sin(angles).reshape(lead)
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def turn_tables(positions, inv_freq, first: int, width: int,
                mscale: float = 1.0):
    """:func:`rotate` as two products a column, for a product that
    brings the turned halves ``[-x2 | x1]`` itself (``ops/mla.py``):
    ``rotate(x) * mscale == x * cos + turned * sin``, in the same
    float32 numbers where ``mscale`` is 1. ``positions`` (tokens,);
    the rotary columns are the ``2 * len(inv_freq)`` from ``first`` of
    ``width``. -> (cos, sin) float32 (tokens, width): the cosines
    and sines under both halves, 1 and 0 in front of them (columns
    that pass as they are), 0 and 0 behind."""
    angles = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    tokens, half = angles.shape
    cos, sin = jnp.cos(angles) * mscale, jnp.sin(angles) * mscale
    front = jnp.zeros((tokens, first), jnp.float32)
    behind = jnp.zeros((tokens, width - first - 2 * half), jnp.float32)
    return (jnp.concatenate([front + 1.0, cos, cos, behind], 1),
            jnp.concatenate([front, sin, sin, behind], 1))
