"""The benchmark's videos: made once per checkout from a fixed seed.

A configuration's ``dataset`` block says what its deployment decodes
(container, geometry, length). The files are written once into an
ignored directory of the checkout (``data/benchmarks/<key>``) and
reused by every later run there; a marker file written last makes a
half-written set regenerate.

The program's sampler seeds on a file's absolute path, so which files
are "long" (as many clips as fit) and which "short" (one clip) would
follow where the checkout lies. The mix is pinned instead: beside each
file the generator places links under names chosen so that the sampler
— asked, not re-implemented — draws one clip for one name and the
long count for the other. :func:`prepare` returns both lists and the
clip count of every path.
"""

from __future__ import annotations

import json
import os
import shutil
from typing import Dict, List, Tuple

import numpy as np

#: bump when the frame synthesis changes: it is part of the key
GENERATOR_VERSION = 1


def synth_frames(num_frames: int, height: int, width: int,
                 seed) -> np.ndarray:
    """(N, H, W, 3) u8: three drifting sinusoidal gradients and a fixed
    low noise floor that drifts with them. Smooth enough that a q60
    JPEG of a 112x112 frame stays inside the dct path's default
    coefficient budget, busy enough that decode does real work."""
    rng = np.random.default_rng(seed)
    table = (127.5 * (1.0 + np.sin(
        2 * np.pi * np.arange(1024) / 1024.0))).astype(np.int16)
    yy, xx = np.mgrid[0:height, 0:width]
    base = ((yy / height + xx / width) * 1024).astype(np.int64)
    noise = rng.integers(0, 16, (height, width, 3), dtype=np.int16)
    frames = np.empty((num_frames, height, width, 3), np.uint8)
    phase = rng.integers(0, 1024, 3)
    speed = rng.integers(16, 66, 3)
    for t in range(num_frames):
        for c in range(3):
            wave = table[(base + phase[c] + speed[c] * t) % 1024]
            frames[t, ..., c] = np.minimum(
                wave + np.roll(noise[..., c], t, axis=1), 255)
    return frames


def dataset_key(spec: dict) -> str:
    return "%s-%dx%d-%df-%dv-q%s-s%d-g%d" % (
        spec["format"], spec["size"][0], spec["size"][1],
        spec["frames"], spec["labels"] * spec["videos_per_label"],
        spec.get("quality", "na"), spec.get("seed", 0),
        GENERATOR_VERSION)


def ensure_files(spec: dict, data_base: str) -> str:
    """Write the spec's files under ``data_base`` unless a complete
    set is there; -> the dataset root (a root/label/video tree)."""
    # the container writers are the program's input formats
    from rnb_tpu.decode import write_mjpeg, write_y4m
    root = os.path.join(data_base, dataset_key(spec))
    marker = os.path.join(root, "COMPLETE.json")
    if os.path.exists(marker):
        return root
    shutil.rmtree(root, ignore_errors=True)
    height, width = spec["size"]
    for li in range(spec["labels"]):
        label_dir = os.path.join(root, "label%03d" % li)
        os.makedirs(label_dir)
        for vi in range(spec["videos_per_label"]):
            frames = synth_frames(spec["frames"], height, width,
                                  [spec.get("seed", 0), li, vi])
            if spec["format"] == "mjpeg":
                write_mjpeg(os.path.join(label_dir, "video%04d.mjpg" % vi),
                            frames, quality=spec["quality"])
            elif spec["format"] == "y4m":
                write_y4m(os.path.join(label_dir, "video%04d.y4m" % vi),
                          frames, colorspace=spec.get("colorspace", "420"))
            else:
                raise ValueError("dataset format %r" % (spec["format"],))
    with open(marker, "w") as f:
        json.dump(spec, f)
    return root


def _base_files(root: str) -> List[str]:
    out = []
    for label in sorted(os.listdir(root)):
        label_dir = os.path.join(root, label)
        if os.path.isdir(label_dir) and label.startswith("label"):
            out.extend(os.path.join(label_dir, v)
                       for v in sorted(os.listdir(label_dir))
                       if v.startswith("video"))
    return out


def prepare(spec: dict, data_base: str, sampler,
            max_clips: int) -> Tuple[List[str], List[str], Dict[str, int]]:
    """-> (short paths, long paths, {path: clips}) for this checkout.

    ``sampler`` is the program's own clip sampler, built as the
    configuration's loader builds it. For each base file two links
    ``pick/short-<i>-<k>.<ext>`` and ``pick/long-<i>-<k>.<ext>`` are
    made, with the smallest ``k`` for which the sampler draws 1 clip,
    or more than one, for that absolute path."""
    root = ensure_files(spec, data_base)
    frames = int(spec["frames"])
    pick = os.path.join(root, "pick")
    os.makedirs(pick, exist_ok=True)
    shorts, longs, clips = [], [], {}
    for i, base in enumerate(_base_files(root)):
        ext = os.path.splitext(base)[1]
        for kind, out in (("short", shorts), ("long", longs)):
            for k in range(100000):
                path = os.path.join(pick, "%s-%d-%d%s" % (kind, i, k, ext))
                n = min(len(sampler.sample(frames, video_id=path)),
                        max_clips)
                if (n == 1) == (kind == "short"):
                    break
            else:
                raise RuntimeError("no %s name found for %s"
                                   % (kind, base))
            if not os.path.exists(path):
                os.symlink(os.path.relpath(base, pick), path)
            out.append(path)
            clips[path] = n
    return shorts, longs, clips
