"""Analytic FLOP counter for R(2+1)D layer ranges.

Walks exactly the convolution schedule of :mod:`.network` (stem
(2+1)D conv, residual stages with factored pairs, projection shortcuts,
classification head) and counts multiply-accumulates as 2 FLOPs, the
MFU convention. Elementwise work (BatchNorm, ReLU, residual adds,
pooling) is excluded — on any matmul-class accelerator it is bandwidth,
not FLOPs, and XLA fuses it into the convs anyway.

The benchmark's own count (``benchmarks/families/r2p1d.py``) is held
to these numbers by its tests, and they are cross-checked in tests against XLA's own ``cost_analysis()`` of the
compiled program, so the analytic walk cannot silently drift from the
network it claims to describe.

Reference context: the reference never measured device utilization — its
methodology stopped at videos/sec (reference README.md:176-185). MFU is
the evidence this framework adds on top.
"""

from __future__ import annotations

from typing import Sequence, Tuple

from rnb_tpu.models.r2p1d.network import (KINETICS_CLASSES, LAYER_FEATURES,
                                          LAYER_INPUT_SHAPES, NUM_LAYERS,
                                          R18_LAYER_SIZES,
                                          factored_channels)

#: Dense bf16 peak TFLOP/s per *jax.Device* by device_kind, for the MFU
#: denominator. v2/v3 report one device per core (chip peak halved);
#: v4 onward one device per chip (megacore). Public spec-sheet numbers.
TPU_PEAK_TFLOPS = {
    "TPU v2": 22.5,
    "TPU v3": 61.5,
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5": 459.0,
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
    "TPU7x": 2307.0,
}


def peak_tflops_for(device_kind: str, platform: str = ""):
    """Peak lookup for a ``jax.Device.device_kind`` string. Exact match
    only — a prefix fallback would hand e.g. a 'TPU v4 lite' variant
    the full v4 peak and silently corrupt the published MFU; unknown
    kinds belong in the table, not guessed. Off the chip path an
    unknown kind gives None (mfu is then unreported rather than
    wrong); with ``platform == "tpu"`` it raises — a measurement on a
    chip whose peak nobody wrote down must stop, not print null."""
    peak = TPU_PEAK_TFLOPS.get(device_kind.strip())
    if peak is None and platform == "tpu":
        raise KeyError(
            "device_kind %r is not in TPU_PEAK_TFLOPS (%s); add its "
            "spec-sheet bf16 peak before measuring on it"
            % (device_kind, __file__))
    return peak


def _conv_out(extent: int, kernel: int, stride: int, pad: int) -> int:
    return (extent + 2 * pad - kernel) // stride + 1


def _st_conv_flops(t_in: int, h: int, w: int, c_in: int, c_out: int,
                   kernel: Tuple[int, int], stride: Tuple[int, int]
                   ) -> Tuple[int, Tuple[int, int, int]]:
    """FLOPs + output dims of one factored SpatioTemporalConv
    (network.py SpatioTemporalConv: spatial (1,d,d) conv to the
    parameter-matched mid width, then temporal (t,1,1) conv)."""
    kt, kd = kernel
    st, sd = stride
    mid = factored_channels(c_in, c_out, kt, kd)
    h_out = _conv_out(h, kd, sd, kd // 2)
    w_out = _conv_out(w, kd, sd, kd // 2)
    spatial = 2 * t_in * h_out * w_out * mid * (kd * kd * c_in)
    t_out = _conv_out(t_in, kt, st, kt // 2)
    temporal = 2 * t_out * h_out * w_out * c_out * (kt * mid)
    return spatial + temporal, (t_out, h_out, w_out)


def range_flops_per_clip(start: int = 1, end: int = NUM_LAYERS,
                         consecutive_frames: int = 8,
                         num_classes: int = KINETICS_CLASSES,
                         layer_sizes: Sequence[int] = R18_LAYER_SIZES,
                         frame_hw: int = None,
                         factored_shortcut: bool = False) -> int:
    """Conv+dense FLOPs for ONE clip row through layers [start..end].

    ``frame_hw``/``consecutive_frames`` describe the *layer-1* input
    geometry; for ``start > 1`` the walk derives the range's input dims
    from the downsampling schedule (same rule as
    network.range_output_shape), so partial ranges stay consistent with
    whatever geometry the pipeline actually flows.
    """
    if not (1 <= start <= end <= NUM_LAYERS):
        raise ValueError("invalid layer range [%s..%s]" % (start, end))
    t = int(consecutive_frames)
    h = w = int(frame_hw) if frame_hw is not None else \
        LAYER_INPUT_SHAPES[1][1]
    c = 3
    for layer in range(1, start):  # walk dims up to the range's input
        if layer == 1:
            h, w, c = -(-h // 2), -(-w // 2), 64
        else:
            c = LAYER_FEATURES[layer]
            if layer >= 3:
                t, h, w = -(-t // 2), -(-h // 2), -(-w // 2)
    total = 0
    for layer in range(start, end + 1):
        if layer == 1:
            flops, (t, h, w) = _st_conv_flops(t, h, w, c, 64,
                                              kernel=(3, 7), stride=(1, 2))
            total += flops
            c = 64
            continue
        c_out = LAYER_FEATURES[layer]
        downsample = layer >= 3
        for block in range(layer_sizes[layer - 2]):
            block_down = downsample and block == 0
            stride = 2 if block_down else 1
            if block_down:
                if factored_shortcut:
                    flops, _ = _st_conv_flops(t, h, w, c, c_out,
                                              kernel=(1, 1),
                                              stride=(2, 2))
                    total += flops
                else:
                    t_s = _conv_out(t, 1, 2, 0)
                    h_s = _conv_out(h, 1, 2, 0)
                    w_s = _conv_out(w, 1, 2, 0)
                    total += 2 * t_s * h_s * w_s * c_out * c
            flops, (t2, h2, w2) = _st_conv_flops(
                t, h, w, c, c_out, kernel=(3, 3), stride=(stride, stride))
            total += flops
            flops, _ = _st_conv_flops(t2, h2, w2, c_out, c_out,
                                      kernel=(3, 3), stride=(1, 1))
            total += flops
            t, h, w, c = t2, h2, w2, c_out
    if end == NUM_LAYERS:
        total += 2 * c * num_classes  # classification head
    return int(total)
