"""The work of R(2+1)D's stages, for ``benchmarks/stages.py``: operations
and bytes of one clip under each of the network's named scopes.

Counted from the configuration's ``model`` block alone (``layer_sizes``,
``stage_widths``, ``consecutive_frames``, ``frame_hw``, ``num_classes``),
convolution by convolution, independently of the program's own
``rnb_tpu/models/r2p1d/flops.py`` and of ``families/r2p1d.py``'s total:
operations at 2 a multiply-add, and the bytes each convolution has to
move at bfloat16 (its input read once, its output written once, the
residual sum's second operand, the weights once a dispatch; batch norm
and ReLU ride on the convolution's pass). The parts add up to the
family file's ``flops_per_row`` exactly (a test holds them to it).

It stands beside the family file and not in it because the PR that
brought it (37, ``tracing``) could not edit a file the benchmark had: a
``benchmark`` PR folds it into ``families/r2p1d.py`` as a
``mechanism_work``.
"""

from __future__ import annotations

from typing import Dict

BF16 = 2


def _out(extent: int, kernel: int, stride: int) -> int:
    return (extent + 2 * (kernel // 2) - kernel) // stride + 1


class Work:
    """Operations and activation bytes a clip, weight bytes a dispatch."""

    __slots__ = ("flops", "activation_bytes", "weight_bytes")

    def __init__(self):
        self.flops = self.activation_bytes = self.weight_bytes = 0

    def conv(self, dims, c_in: int, c_out: int, kernel, stride):
        """One convolution over ``dims`` (t, h, w) -> its output dims."""
        out = tuple(_out(n, k, s) for n, k, s in zip(dims, kernel, stride))
        taps = kernel[0] * kernel[1] * kernel[2] * c_in
        points = out[0] * out[1] * out[2]
        self.flops += 2 * points * c_out * taps
        self.activation_bytes += BF16 * (
            dims[0] * dims[1] * dims[2] * c_in + points * c_out)
        self.weight_bytes += BF16 * taps * c_out
        return out

    def factored(self, dims, c_in: int, c_out: int, kt: int, kd: int,
                 st: int, sd: int):
        """A (2+1)D pair: (1, d, d) to the parameter-matched width M_i
        (Tran et al., section 3.5), then (t, 1, 1)."""
        mid = max(1, (kt * kd * kd * c_in * c_out)
                  // (kd * kd * c_in + kt * c_out))
        dims = self.conv(dims, c_in, mid, (1, kd, kd), (1, sd, sd))
        return self.conv(dims, mid, c_out, (kt, 1, 1), (st, 1, 1))


def stage_work(model: dict) -> Dict[str, Work]:
    """{"stem", "stage2" ... "stage5", "head": Work} of one clip of
    ``consecutive_frames`` x ``frame_hw`` x ``frame_hw`` through
    ``layer_sizes`` blocks of ``stage_widths`` channels (stages 3 to 5
    open with a strided block and a 1x1x1 projection on the shortcut)
    and a linear classifier."""
    dims = (int(model["consecutive_frames"]),) + (int(model["frame_hw"]),) * 2
    parts = {"stem": Work()}
    dims = parts["stem"].factored(dims, 3, 64, 3, 7, 1, 2)
    c = 64
    for n, (blocks, c_out) in enumerate(zip(model["layer_sizes"],
                                            model["stage_widths"])):
        work = parts["stage%d" % (n + 2)] = Work()
        for block in range(blocks):
            stride = 2 if n > 0 and block == 0 else 1
            if stride == 2:
                work.conv(dims, c, c_out, (1, 1, 1), (2, 2, 2))
            new = work.factored(dims, c, c_out, 3, 3, stride, stride)
            work.factored(new, c_out, c_out, 3, 3, 1, 1)
            # the sum reads the shortcut's side once more
            work.activation_bytes += BF16 * new[0] * new[1] * new[2] * c_out
            dims, c = new, c_out
    head = parts["head"] = Work()
    head.flops = 2 * c * int(model["num_classes"])
    head.activation_bytes = BF16 * (dims[0] * dims[1] * dims[2] * c
                                    + int(model["num_classes"]))
    head.weight_bytes = BF16 * c * int(model["num_classes"])
    return parts
