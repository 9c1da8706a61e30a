"""Ingest preprocess kernel: uint8 frames -> normalized bfloat16.

This is the op a batch of RGB u8 frames crosses on its way from the
host decoder into the network (the TPU-native analog of the
reference's post-NVVL ``.float()`` cast, reference
models/r2p1d/model.py:149-151):

    y = x.astype(bf16) * (2/255) - 1        # [0,255] -> [-1,1]

Two forms of it, and which caller takes which:

* ``normalize_u8`` — on a TPU a Pallas kernel — is the stand-alone
  preprocess program's (the RGB pixel path's loader,
  models/r2p1d/model.py ``_shared_preprocess``): its u8 batch arrives
  from the host and its result crosses a ``device_put`` / ring
  boundary, so there is no consumer in the program to fuse into, and
  the kernel keeps the uint8->bf16 widening on the VPU with
  lane-aligned tiles. (The rgb branch of the mesh step,
  parallel/sharded.py, also calls it, in front of its network inside
  one jit: no cell measures that branch, and it is left as it was.)
* ``normalize_u8_reference`` — plain jnp, the numerics contract the
  kernel was written against — is what an ingest calls that computes
  its u8 frames *inside its consumer's jit* (ops/yuv.py; ops/dct.py
  writes the same formulation into its own conversion): there XLA
  fuses the normalization with its producer and lays the result out
  for the first convolution, and an opaque kernel over a flat
  ``(M, 128)`` view between the two costs two relayouts and a
  3-channel clip padded to 128 lanes (PERF.md section 6, PR 32).

Layout strategy: the logical clip shape ``(N, F, H, W, 3)`` is
irrelevant to an elementwise op, so the wrapper flattens to
``(M, 128)`` lanes and grids over row blocks; Pallas masks the ragged
final block. Inputs whose element count is not lane-divisible (never
the case for the 112x112x3 production geometry) take the jnp path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LANES = 128
#: uint8 min sublane tile is 32; use a healthy multiple for fewer grid
#: steps while staying far under VMEM (2 x 512 x 128 x ~3B per step).
BLOCK_ROWS = 512


def normalize_u8_reference(x, dtype=jnp.bfloat16):
    """The jnp formulation (also the numerics contract for the kernel).

    Written as ``(2x - 255) * (1/255)``: the inner term is exact
    integer arithmetic in f32 (|2x-255| <= 255), leaving a single
    rounding multiply — no mul+add pair a compiler could contract into
    an FMA — so every backend (XLA CPU/TPU, Mosaic, interpret mode)
    produces bit-identical f32, rounded to ``dtype`` exactly once.
    """
    xf = x.astype(jnp.float32)
    return ((xf * 2.0 - 255.0) * jnp.float32(1.0 / 255.0)).astype(dtype)


def _normalize_kernel(x_ref, o_ref):
    # Mosaic has no direct uint8->bf16 cast; widen via int32/f32 on the
    # VPU. Same FMA-proof formulation as normalize_u8_reference.
    x = x_ref[:].astype(jnp.int32).astype(jnp.float32)
    o_ref[:] = ((x * 2.0 - 255.0)
                * jnp.float32(1.0 / 255.0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _normalize_u8_pallas(x, dtype=jnp.bfloat16):
    from jax.experimental import pallas as pl

    flat = x.reshape(-1, LANES)
    rows = flat.shape[0]
    block = min(BLOCK_ROWS, rows)
    out = pl.pallas_call(
        _normalize_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), dtype),
        grid=(pl.cdiv(rows, block),),
        in_specs=[pl.BlockSpec((block, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, LANES), lambda i: (i, 0)),
    )(flat)
    return out.reshape(x.shape)


def normalize_u8(x, dtype=jnp.bfloat16):
    """uint8 [0,255] frames -> ``dtype`` in [-1, 1].

    The stand-alone normalization of a u8 batch that came from the
    host (the RGB loader's preprocess program; also the rgb branch of
    the sharded mesh step). An ingest that computes its u8 frames in
    the same jit as their consumer calls ``normalize_u8_reference``
    instead (module docstring). When the element count is
    lane-divisible the choice between the Pallas kernel and jnp is made
    at lowering time, by the platform the computation is compiled for
    (``lax.platform_dependent``) — the device the operand lives on, not
    the process default: a host-placed stage of a TPU process gets the
    jnp form, and a TPU gets the kernel or, if Mosaic refuses it, the
    compiler's error — never the twin.
    """
    if x.dtype == jnp.uint8 and x.size > 0 and x.size % LANES == 0:
        return jax.lax.platform_dependent(
            x, tpu=functools.partial(_normalize_u8_pallas, dtype=dtype),
            default=functools.partial(normalize_u8_reference, dtype=dtype))
    return normalize_u8_reference(x, dtype=dtype)
