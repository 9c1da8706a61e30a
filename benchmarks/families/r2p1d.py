"""The R(2+1)D family: everything the harness has to know of a 3-D
convolutional video classifier, behind the contract that
``benchmarks/run.py`` calls (:func:`build`, :func:`prepare_inputs`,
:func:`make_weights`, :func:`check_outputs`, :func:`flops_per_row`,
:func:`wire_bytes_per_row`). A configuration's file names it:
``"family": "r2p1d"``. The plain reference is
``benchmarks/references/r2p1d.py``.

**The videos: made once per checkout from a fixed seed.**
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

**The work of a row.** The FLOP count of one clip through R(2+1)D is
computed here from the published structure (Tran et al., CVPR 2018):
each 3-D convolution is a spatial (1,d,d) convolution to the
parameter-matched width M_i, then a temporal (t,1,1) one; 2 FLOPs per
multiply-add. It is the count the algorithm needs, independent of how
the program schedules it, and a test holds it equal to the program's
own ``rnb_tpu.models.r2p1d.flops.range_flops_per_clip``.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
from typing import Dict, List, Sequence, Tuple

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


# -- the contract benchmarks/run.py calls --------------------------------


def build(repo: str) -> None:
    """Children that never touch JAX, before it is imported: the native
    build (a no-op once built)."""
    subprocess.run(["make", "-C", os.path.join(repo, "native")],
                   check=True, stdout=subprocess.DEVNULL)


def prepare_inputs(config: dict, data_base: str) -> dict:
    """The request files of this checkout: ``short_files`` and
    ``long_files``, the rows of each (``rows_of``), the ``data_root``
    the program is pointed at, and the ``sample`` that
    :func:`check_outputs` uses (a long video and its clip starts)."""
    from rnb_tpu.decode.native import load_native
    from rnb_tpu.models.r2p1d.sampler import R2P1DSampler
    if load_native() is None:
        raise RuntimeError("native/build/librnb_decode.so does not load")
    loader = config["pipeline_config"]["pipeline"][0]
    sampler = R2P1DSampler(
        consecutive_frames=int(loader["consecutive_frames"]))
    shorts, longs, clips_of = prepare(config["dataset"], data_base, sampler,
                                      int(loader["max_clips"]))
    sample = longs[0]
    return {"short_files": shorts, "long_files": longs, "rows_of": clips_of,
            "data_root": os.path.dirname(os.path.dirname(shorts[0])),
            "sample": (sample, sampler.sample(
                int(config["dataset"]["frames"]), video_id=sample))}


def make_weights(config: dict, seed: int, ckpt_base: str):
    """-> (the checkpoint the program loads, the variable tree the
    reference reads): the program's own initialiser from ``seed``,
    saved as msgpack."""
    from rnb_tpu.models.r2p1d import checkpoint
    model = config["model"]
    variables = checkpoint.init_variables(
        seed=seed % 2 ** 31, layer_sizes=tuple(model["layer_sizes"]),
        num_classes=model["num_classes"])
    ckpt_path = ckpt_base + ".msgpack"
    checkpoint.save_checkpoint(ckpt_path, variables)
    return ckpt_path, variables


def check_outputs(config: dict, pipeline: dict, weights, ckpt_path: str,
                  seed: int, inputs: dict, devices, result) -> dict:
    """The serving applier of the run (same jitted function, same
    device weights) against the float32 reference on a seeded sample,
    outside the window: at the smallest warmed bucket and at the
    largest, the rows most of a backlog's dispatches ship. The compiler
    chooses the layouts around the ingest kernels by the row count, so
    what holds at 8 rows is not shown at 48 (PERF.md section 7 g).
    Both programs are warmed; nothing compiles. The verdict is the
    worse bucket's, with every bucket's numbers beside it; the
    reference runs once."""
    import jax
    import numpy as np

    from benchmarks.references import compare, r2p1d as reference
    from rnb_tpu.models.r2p1d import model as stage
    sample_path, clips_starts = inputs["sample"]
    step = pipeline["pipeline"][config["weights_steps"][0]]
    sizes = tuple(step["layer_sizes"])
    frames = int(step["consecutive_frames"])
    pixel_path = step["pixel_path"]
    hw = stage.FRAME_HW
    device = devices[0]
    apply = stage._shared_apply(step["start_index"], step["end_index"],
                                config["model"]["num_classes"], sizes,
                                pixel_path=pixel_path)
    params = stage._shared_params(step["start_index"], step["end_index"],
                                  config["model"]["num_classes"], sizes,
                                  ckpt_path, device)
    buckets = sorted({int(min(step["row_buckets"])),
                      int(max(step["row_buckets"]))})
    checked = min(2, buckets[0])
    # one input at the largest bucket, its first rows the smaller one's
    # (the generator fills row by row), so every bucket is held to the
    # same reference rows
    rng = np.random.default_rng([seed % 2 ** 63, 7])
    if pixel_path == "yuv420":
        wire = rng.integers(0, 256, (buckets[-1], frames, hw * hw * 3 // 2),
                            dtype=np.uint8)
        ref_in = reference.normalize_yuv420(wire[:checked], hw, hw)
    elif pixel_path == "dct":
        # real files: the program's decoder makes the coefficient rows,
        # its float64 numpy oracle the reference's pixels (the one part
        # of the reference that is the program's own: PERF.md)
        from rnb_tpu.decode import get_decoder
        from rnb_tpu.ops import dct
        decoded = get_decoder(sample_path).decode_clips_dct(
            sample_path, list(clips_starts)[:checked], frames, width=hw,
            height=hw, coeffs=dct.default_dct_coeffs(hw, hw))
        wire = np.zeros((buckets[-1],) + tuple(decoded.shape[1:]),
                        decoded.dtype)
        wire[:checked] = decoded[:checked]
        ref_in = reference.normalize_rgb_u8(
            dct.dct_rows_to_rgb_numpy(wire[:checked], hw, hw))
    else:
        raise ValueError("no reference ingest for pixel_path %r"
                         % (pixel_path,))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(
            lambda v, x: reference.forward(v, x, sizes))(weights, ref_in))
    by_rows = {rows: compare(np.asarray(
        apply(params, jax.device_put(wire[:rows], device)),
        np.float32)[:checked], ref) for rows in buckets}
    worst = max(buckets, key=lambda rows: (
        not by_rows[rows]["ok"], by_rows[rows].get("share_of_spread") or 0))
    return dict(by_rows[worst], rows=worst,
                by_rows={str(rows): by_rows[rows] for rows in buckets})


def check_config(config: dict) -> List[str]:
    """What has to hold between the parts of one of this family's
    configuration files, beyond what the program's own parser and lint
    check: -> the problems, none for a sound file."""
    model = config["model"]
    runner = config["pipeline_config"]["pipeline"][-1]
    problems = []
    for key, published in (("layer_sizes", [3, 4, 6, 3]),
                           ("consecutive_frames", 32)):
        if not runner[key] == model[key] == published:
            problems.append("%s: the stage's %r, the model's %r, the "
                            "paper's %r" % (key, runner[key], model[key],
                                            published))
    return problems


def project_memory(config: dict, sharding) -> dict:
    """Bytes the largest row bucket takes on the device of ``sharding``
    (a described chip: the real stage program, ingest included, is
    compiled and nothing runs): the program's ``temporaries`` and
    ``arguments`` (weights and one input batch) and the batches that
    may be ``waiting`` on the device, one a ring slot."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.r2p1d import model as stage
    from rnb_tpu.models.r2p1d.network import R2Plus1DClassifier
    pipeline = config["pipeline_config"]["pipeline"]
    loader, step = pipeline[0], pipeline[-1]
    sizes = tuple(step["layer_sizes"])
    rows, frames = max(step["row_buckets"]), step["consecutive_frames"]
    apply = stage._shared_apply(step["start_index"], step["end_index"],
                                config["model"]["num_classes"], sizes,
                                pixel_path=step["pixel_path"])
    shapes = jax.eval_shape(
        lambda k: R2Plus1DClassifier(layer_sizes=sizes).init(
            k, np.zeros((1, 2, 14, 14, 3), np.float32), train=False),
        jax.random.key(0))
    variables = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), shapes)
    shape = stage.R2P1DRunner.input_shape_for(
        start_index=step["start_index"], max_rows=rows,
        consecutive_frames=frames, pixel_path=step["pixel_path"])[0]
    dtype = getattr(jnp, stage.R2P1DRunner.input_dtype_for(
        start_index=step["start_index"], pixel_path=step["pixel_path"]))
    memory = apply.lower(variables, jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)).compile().memory_analysis()
    return {"rows": rows,
            "temporaries": memory.temp_size_in_bytes,
            "arguments": memory.argument_size_in_bytes,
            "waiting": loader["num_shared_tensors"] * int(np.prod(shape))
            * np.dtype(dtype).itemsize}


def flops_per_row(config: dict) -> int:
    model = config["model"]
    return flops_per_clip(model["layer_sizes"], model["consecutive_frames"],
                          model["frame_hw"], model["num_classes"])


def wire_bytes_per_row(config: dict, pipeline: dict) -> int:
    """Bytes of one row as the loader ships it to the network stage."""
    import numpy as np

    from rnb_tpu.models.r2p1d.model import R2P1DRunner
    loader = pipeline["pipeline"][0]
    return int(np.prod(R2P1DRunner.input_shape_for(
        start_index=1, max_rows=1,
        consecutive_frames=int(loader["consecutive_frames"]),
        pixel_path=loader["pixel_path"])[0])) * np.dtype(
            R2P1DRunner.input_dtype_for(
                start_index=1, pixel_path=loader["pixel_path"])).itemsize


# -- the operation count --------------------------------------------------


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


def flops_per_clip(layer_sizes: Sequence[int], frames: int,
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
