"""Single-caller decode micro-benchmark for the native backend.

Measures raw frames/s of the C++ decoder (native/decode.cpp) outside
the pipeline — the number to quote when attributing a cell's
throughput to the host codec (the role NVDEC benchmarks filled
for the reference's NVVL loader, reference README.md:42-110). Decodes
every video in a dataset tree sequentially on the calling thread (no
pool fan-out) so the figure is per-core codec speed, not concurrency.

Besides frames/s, each run reports ``bytes_per_frame`` — the
host->device wire cost of one decoded frame in the chosen pixel path,
measured from the decoder's actual output buffer (rgb: H*W*3 u8;
yuv420: H*W*3/2 packed planes; dct: the packed int16 coefficient rows
of rnb_tpu/ops/dct.py) — so the wire-bandwidth claim each pixel path
makes is a measured column of this benchmark, not prose. ``--pixfmt
all`` prints one JSON line per path plus a summary line with the byte
ratios.

Clip plan: each video is decoded in whole non-overlapping clips of
``--consecutive-frames`` frames — every frame of every *whole* clip is
decoded exactly once; the tail frames past the last whole clip are
dropped, and a video shorter than one clip contributes no frames at
all. A dataset where every video is that short would therefore measure
nothing; the script exits non-zero in that case instead of printing a
misleading ``{"frames_per_sec": 0.0}``.

Note the dct path needs MJPEG sources at exactly the output geometry
(112x112 by default, divisible by 16): coefficients cannot be resized
on the host, which is the point of the path.

Usage::

    python scripts/decode_bench.py data/bench_mjpeg [--pixfmt dct]
        [--repeats 3]
    python scripts/decode_bench.py data/bench_mjpeg --pixfmt all
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from rnb_tpu.decode import DEFAULT_HEIGHT, DEFAULT_WIDTH  # noqa: E402
from rnb_tpu.decode.native import NativeY4MDecoder  # noqa: E402
from rnb_tpu.video_path_provider import (  # noqa: E402
    VIDEO_EXTENSIONS, scan_video_tree)

def dataset_videos(root: str):
    vids = scan_video_tree(root)
    if not vids:
        raise SystemExit("no %s videos under %s"
                         % (VIDEO_EXTENSIONS, root))
    return vids


def run_one(dec, plans, total_frames: int, pixfmt: str, repeats: int,
            dataset: str) -> dict:
    cf_decoders = {
        "rgb": dec.decode_clips,
        "yuv420": dec.decode_clips_yuv,
        "dct": functools.partial(dec.decode_clips_dct,
                                 width=DEFAULT_WIDTH,
                                 height=DEFAULT_HEIGHT),
    }
    decode = cf_decoders[pixfmt]
    # bytes_per_frame is MEASURED from the decoder's actual output
    # buffer (one untimed warm decode), so the column reports what a
    # custom dct budget / non-default geometry really ships
    v0, starts0, cf0 = plans[0]
    out0 = decode(v0, starts0, cf0)
    bytes_per_frame = out0.nbytes // (len(starts0) * cf0)
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        for v, starts, cf in plans:
            decode(v, starts, cf)
        best = min(best, time.perf_counter() - t0)
    return {
        "frames_per_sec": round(total_frames / best, 1),
        "videos": len(plans), "frames": total_frames,
        "wall_s": round(best, 3), "pixfmt": pixfmt,
        "bytes_per_frame": int(bytes_per_frame),
        "dataset": dataset}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("dataset")
    ap.add_argument("--pixfmt", choices=("rgb", "yuv420", "dct", "all"),
                    default="yuv420")
    ap.add_argument("--repeats", type=int, default=3,
                    help="best-of-N passes over the dataset")
    ap.add_argument("--consecutive-frames", type=int, default=8)
    args = ap.parse_args()

    dec = NativeY4MDecoder(use_pool=False)  # single-caller by design
    videos = dataset_videos(args.dataset)
    cf = args.consecutive_frames
    plans = []  # (video, clip_starts) decoding every frame exactly once
    total_frames = 0
    for v in videos:
        n = dec.num_frames(v)
        starts = list(range(0, n - cf + 1, cf))
        plans.append((v, starts, cf))
        total_frames += len(starts) * cf
    if total_frames == 0:
        # mirrors the no-videos guard: an all-short-video dataset
        # (every video < --consecutive-frames) decodes nothing, and a
        # 0.0 frames/s line with rc 0 would read as a measurement
        raise SystemExit(
            "no decodable clips: every video under %s is shorter than "
            "--consecutive-frames=%d" % (args.dataset, cf))

    pixfmts = (("rgb", "yuv420", "dct") if args.pixfmt == "all"
               else (args.pixfmt,))
    rows = []
    for pixfmt in pixfmts:
        row = run_one(dec, plans, total_frames, pixfmt, args.repeats,
                      args.dataset)
        rows.append(row)
        print(json.dumps(row))
    if len(rows) > 1:
        by = {r["pixfmt"]: r for r in rows}
        print(json.dumps({
            "bytes_per_frame": {k: r["bytes_per_frame"]
                                for k, r in by.items()},
            "dct_vs_yuv420_bytes": round(
                by["dct"]["bytes_per_frame"]
                / by["yuv420"]["bytes_per_frame"], 4),
            "yuv420_vs_rgb_bytes": round(
                by["yuv420"]["bytes_per_frame"]
                / by["rgb"]["bytes_per_frame"], 4)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
