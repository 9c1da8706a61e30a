"""The plain reference of R(2+1)D.

The forward pass of Tran et al.'s R(2+1)D (CVPR 2018) written straight
from the published structure in ``jax.numpy`` and float32, with no
kernels, batching or bf16: stem (1x7x7 spatial to M_i channels, BN,
ReLU, 3x1x1 temporal), BN, ReLU; four residual stages of
``layer_sizes`` blocks, 64/128/256/512 wide, stages 2-4 of them
halving T, H and W in their first block with a strided 1x1x1
projection + BN on the shortcut; global average pool; linear head.
Departures from the paper that it shares with the program (and that
the weights' tree fixes): BN + ReLU after the stem, a plain rather than
factored projection shortcut.

It reads the same variable tree the program serves (a Flax
``{"params", "batch_stats"}`` dict of arrays) but shares no code with
``rnb_tpu.models.r2p1d.network``. Matmul precision is pinned to
``highest``: on a TPU a float32 convolution otherwise runs in bf16
passes.

The raw-video ingest has its reference here too: nearest 2x chroma
upsampling, full-range BT.601, clip, truncate to u8, then
``x / 127.5 - 1``.
"""

from __future__ import annotations

from typing import Sequence

BN_EPS = 1e-5


def _conv(x, kernel, strides, padding):
    from jax import lax
    return lax.conv_general_dilated(
        x, kernel, window_strides=strides, padding=padding,
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
        precision=lax.Precision.HIGHEST)


def _bn(x, params, stats):
    import jax.numpy as jnp
    return (x - stats["mean"]) * (params["scale"]
                                  / jnp.sqrt(stats["var"] + BN_EPS)) \
        + params["bias"]


def _st_conv(x, p, s, kernel, stride):
    import jax.numpy as jnp
    (kt, kd), (st, sd) = kernel, stride
    x = _conv(x, p["spatial"]["kernel"], (1, sd, sd),
              ((0, 0), (kd // 2, kd // 2), (kd // 2, kd // 2)))
    x = jnp.maximum(_bn(x, p["bn"], s["bn"]), 0.0)
    return _conv(x, p["temporal"]["kernel"], (st, 1, 1),
                 ((kt // 2, kt // 2), (0, 0), (0, 0)))


def _block(x, p, s, downsample: bool):
    import jax.numpy as jnp
    stride = 2 if downsample else 1
    res = _st_conv(x, p["conv1"], s["conv1"], (3, 3), (stride, stride))
    res = jnp.maximum(_bn(res, p["bn1"], s["bn1"]), 0.0)
    res = _st_conv(res, p["conv2"], s["conv2"], (3, 3), (1, 1))
    res = _bn(res, p["bn2"], s["bn2"])
    if downsample:
        x = _conv(x, p["shortcut"]["kernel"], (2, 2, 2),
                  ((0, 0), (0, 0), (0, 0)))
        x = _bn(x, p["shortcut_bn"], s["shortcut_bn"])
    return jnp.maximum(x + res, 0.0)


def forward(variables, x, layer_sizes: Sequence[int]):
    """Logits (rows, classes) in float32 for normalized NDHWC clips."""
    import jax.numpy as jnp
    p, s = variables["params"], variables["batch_stats"]
    net_p, net_s = p["net"], s["net"]
    x = x.astype(jnp.float32)
    x = _st_conv(x, net_p["conv1"], net_s["conv1"], (3, 7), (1, 2))
    x = jnp.maximum(_bn(x, net_p["stem_bn"], net_s["stem_bn"]), 0.0)
    for stage, blocks in enumerate(layer_sizes):
        name = "conv%d" % (stage + 2)
        for block in range(blocks):
            x = _block(x, net_p[name]["block%d" % block],
                       net_s[name]["block%d" % block],
                       downsample=(stage > 0 and block == 0))
    x = jnp.mean(x, axis=(1, 2, 3))
    return jnp.dot(x, p["linear"]["kernel"],
                   precision="highest") + p["linear"]["bias"]


def normalize_yuv420(planes, height: int = 112, width: int = 112):
    """Packed u8 4:2:0 planes (..., H*W*3/2) -> float32 NDHWC in
    [-1, 1]."""
    import jax.numpy as jnp
    hw, q = height * width, (height // 2) * (width // 2)
    lead = planes.shape[:-1]
    y = planes[..., :hw].reshape(lead + (height, width)) \
        .astype(jnp.float32)

    def chroma(flat):
        c = flat.reshape(lead + (height // 2, width // 2))
        c = jnp.repeat(jnp.repeat(c, 2, axis=-2), 2, axis=-1)
        return c.astype(jnp.float32) - 128.0

    u, v = chroma(planes[..., hw:hw + q]), chroma(planes[..., hw + q:])
    rgb = jnp.stack([y + 1.402 * v,
                     y - 0.344136 * u - 0.714136 * v,
                     y + 1.772 * u], axis=-1)
    return normalize_rgb_u8(jnp.clip(rgb, 0.0, 255.0).astype(jnp.uint8))


def normalize_rgb_u8(rgb):
    import jax.numpy as jnp
    return rgb.astype(jnp.float32) / 127.5 - 1.0
