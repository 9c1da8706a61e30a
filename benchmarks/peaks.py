"""The table of peaks and the operation counts: the yardstick's numbers.

One row: a TPU v5e chip, which JAX reports as ``TPU v5 lite``. Source:
Google Cloud documentation, "TPU v5e" system architecture: 197 TFLOP/s
bf16, 819 GB/s of HBM bandwidth, 16 GB of HBM per chip. A device that
is not in the table is an error, not a default.

The FLOP count of one clip through R(2+1)D is computed here from the
published structure (Tran et al., CVPR 2018): each 3-D convolution is
a spatial (1,d,d) convolution to the parameter-matched width M_i, then
a temporal (t,1,1) one; 2 FLOPs per multiply-add. It is the count the
algorithm needs, independent of how the program schedules it, and a
test holds it equal to the program's own
``rnb_tpu.models.r2p1d.flops.range_flops_per_clip``.
"""

from __future__ import annotations

from typing import Sequence

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
    "TPU v5e": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9,
                "source": "Google Cloud documentation, TPU v5e"},
}


def peak_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind.strip()]
    except KeyError:
        raise KeyError("device_kind %r is not in benchmarks/peaks.py "
                       "(%s): a rate against an unknown peak is not "
                       "reported" % (device_kind, sorted(PEAKS))) from None


def _out(extent: int, kernel: int, stride: int) -> int:
    return (extent + 2 * (kernel // 2) - kernel) // stride + 1


def _mid(c_in: int, c_out: int, t: int, d: int) -> int:
    return max(1, (t * d * d * c_in * c_out)
               // (d * d * c_in + t * c_out))


def _st_conv(t, h, w, c_in, c_out, kt, kd, st, sd):
    mid = _mid(c_in, c_out, kt, kd)
    h2, w2 = _out(h, kd, sd), _out(w, kd, sd)
    t2 = _out(t, kt, st)
    flops = 2 * t * h2 * w2 * mid * kd * kd * c_in \
        + 2 * t2 * h2 * w2 * c_out * kt * mid
    return flops, (t2, h2, w2)


def r2p1d_flops_per_clip(layer_sizes: Sequence[int], frames: int,
                         hw: int = 112, num_classes: int = 400) -> int:
    """FLOPs of one ``frames`` x ``hw`` x ``hw`` clip through the stem,
    the four residual stages of ``layer_sizes`` blocks (64/128/256/512
    wide, stages 2-4 of them downsampling by a strided 1x1x1
    projection) and the classifier."""
    total, (t, h, w) = _st_conv(frames, hw, hw, 3, 64, 3, 7, 1, 2)
    c = 64
    for stage, blocks in enumerate(layer_sizes):
        c_out = 64 * 2 ** stage
        for block in range(blocks):
            down = stage > 0 and block == 0
            if down:
                total += 2 * _out(t, 1, 2) * _out(h, 1, 2) \
                    * _out(w, 1, 2) * c_out * c
            stride = 2 if down else 1
            flops, (t2, h2, w2) = _st_conv(t, h, w, c, c_out, 3, 3,
                                           stride, stride)
            total += flops
            flops, _ = _st_conv(t2, h2, w2, c_out, c_out, 3, 3, 1, 1)
            total += flops
            t, h, w, c = t2, h2, w2, c_out
    return total + 2 * c * num_classes
