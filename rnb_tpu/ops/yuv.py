"""On-device 4:2:0 ingest: packed YUV planes -> normalized bfloat16.

The ``yuv420`` pixel path moves the per-pixel colourspace work off the
host (round 5's single host core was the throughput ceiling: 2026-07,
previous transport, not reproduced) and onto the accelerator, where it
fuses with the ingest normalization into one XLA producer for the
network's first convolution:

    host:   y4m payload --pure byte gathers--> packed 4:2:0 planes
    wire:   1.5 bytes/pixel  (vs 3 for RGB u8, 6 for bf16 frames)
    device: nearest chroma upsample -> BT.601 -> clip/quantize ->
            normalize -> network   (all inside the stage's jit)

The reference did this balance the opposite way — NVVL's NVDEC decoded
on the GPU *because the GPU had a video ASIC* (reference
README.md:42-110). A TPU has none, so the split that minimizes host
work and wire bytes is: gather on host, arithmetic on device.

The normalization here is the plain jnp form
(``ops.preprocess.normalize_u8``), the only one since PR 45. The u8
frames are computed in the same jit as the convolution that reads
them, so XLA fuses the normalization into its producer; the Pallas
kernel that once stood between the two cost two relayouts and a
3-channel clip padded to 128 lanes twice a dispatch (PERF.md section
6, PR 32). Every caller of this module — the bucketed and the ragged
stage program, the mesh step, the sharded ring — normalizes inside its
consumer's jit.

Packed layout per frame (geometry must be even): ``Y`` (H*W bytes),
then ``U`` and ``V`` ((H/2)*(W/2) bytes each) — ``packed_frame_bytes``
total, flattened on the trailing axis so clip batches are
``(N, F, packed)`` and row bucketing/fusing work unchanged.

Numerics contract: luma uses the RGB path's exact nearest index map;
chroma keeps its own nearest map at half output resolution (standard
4:2:0 subsampling), so the two pixel paths may differ by one source
pixel in chroma. Within the yuv420 path, the numpy and native backends
are bit-exact; this device converter mirrors the numpy float32 op
order, with XLA FMA contraction allowed (±1 u8 LSB — asserted in
tests/test_yuv.py).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from rnb_tpu.ops.preprocess import normalize_u8


def packed_frame_bytes(height: int, width: int) -> int:
    """Bytes of one packed 4:2:0 frame; geometry must be even."""
    if height % 2 or width % 2:
        raise ValueError("packed 4:2:0 needs even geometry, got %dx%d"
                         % (height, width))
    return height * width * 3 // 2


def yuv420_to_rgb_u8(x, height: int, width: int):
    """Packed u8 planes ``(..., packed)`` -> RGB u8 ``(..., H, W, 3)``.

    jnp mirror of the numpy oracle (decode.yuv420_to_rgb_numpy): nearest
    2x chroma upsample, full-range BT.601, clip, truncate to u8.
    """
    hw = height * width
    q = (height // 2) * (width // 2)
    lead = x.shape[:-1]
    y = x[..., :hw].reshape(lead + (height, width)).astype(jnp.float32)
    u = x[..., hw:hw + q].reshape(lead + (height // 2, width // 2))
    v = x[..., hw + q:].reshape(lead + (height // 2, width // 2))
    u = jnp.repeat(jnp.repeat(u, 2, axis=-2), 2, axis=-1)
    v = jnp.repeat(jnp.repeat(v, 2, axis=-2), 2, axis=-1)
    uf = u.astype(jnp.float32) - 128.0
    vf = v.astype(jnp.float32) - 128.0
    rgb = jnp.stack([
        y + 1.402 * vf,
        y - 0.344136 * uf - 0.714136 * vf,
        y + 1.772 * uf,
    ], axis=-1)
    return jnp.clip(rgb, 0.0, 255.0).astype(jnp.uint8)


def normalize_yuv420(x, height: int = 112, width: int = 112,
                     dtype=jnp.bfloat16):
    """Packed u8 planes -> ``dtype`` NDHWC frames in [-1, 1].

    The u8 quantization step between conversion and normalization is
    kept deliberately: it makes the network's input identical to what
    a host-side converter would have produced, so accuracy is a
    property of the pixel path, not of where it runs.
    """
    return normalize_u8(yuv420_to_rgb_u8(x, height, width), dtype=dtype)


def yuv420_to_rgb_numpy(x: np.ndarray, height: int,
                        width: int) -> np.ndarray:
    """The numpy oracle for :func:`yuv420_to_rgb_u8` (tests only)."""
    hw = height * width
    q = (height // 2) * (width // 2)
    lead = x.shape[:-1]
    y = x[..., :hw].reshape(lead + (height, width)).astype(np.float32)
    u = x[..., hw:hw + q].reshape(lead + (height // 2, width // 2))
    v = x[..., hw + q:].reshape(lead + (height // 2, width // 2))
    u = u.repeat(2, axis=-2).repeat(2, axis=-1).astype(np.float32) - 128.0
    v = v.repeat(2, axis=-2).repeat(2, axis=-1).astype(np.float32) - 128.0
    rgb = np.stack([
        y + 1.402 * v,
        y - 0.344136 * u - 0.714136 * v,
        y + 1.772 * u,
    ], axis=-1)
    return np.clip(rgb, 0.0, 255.0).astype(np.uint8)
