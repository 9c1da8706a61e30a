"""Ingest preprocess: uint8 frames -> normalized bfloat16.

This is the op a batch of RGB u8 frames crosses on its way from the
host decoder into the network (the TPU-native analog of the
reference's post-NVVL ``.float()`` cast, reference
models/r2p1d/model.py:149-151):

    y = x.astype(bf16) * (2/255) - 1        # [0,255] -> [-1,1]

One form, plain jnp, for every caller: the RGB loader's stand-alone
preprocess program (models/r2p1d/model.py ``_shared_preprocess``), the
rgb branch of the mesh step (parallel/sharded.py), and an ingest that
computes its u8 frames *inside its consumer's jit* (ops/yuv.py;
ops/dct.py writes the same formulation into its own conversion), where
XLA fuses the normalization with its producer and lays the result out
for the first convolution. A Pallas kernel over a flat ``(M, 128)``
view stood here until PR 45: on the chip it took 37.557 ms against
0.323 ms for this form on 48 x 32 frames, bit-equal (PERF.md section
6, PRs 32 and 45).
"""

from __future__ import annotations

import jax.numpy as jnp


def normalize_u8(x, dtype=jnp.bfloat16):
    """uint8 [0,255] frames -> ``dtype`` in [-1, 1].

    Written as ``(2x - 255) * (1/255)``: the inner term is exact
    integer arithmetic in f32 (|2x-255| <= 255), leaving a single
    rounding multiply — no mul+add pair a compiler could contract into
    an FMA — so every backend (XLA CPU/TPU, Mosaic, interpret mode)
    produces bit-identical f32, rounded to ``dtype`` exactly once.
    """
    xf = x.astype(jnp.float32)
    return ((xf * 2.0 - 255.0) * jnp.float32(1.0 / 255.0)).astype(dtype)
