"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

Drives the serving path once, through the entry points a user would
call (``run_benchmark`` over shipped configs), at the full width of
the one model the repo supports — R(2+1)D-18, 8-frame 112x112 clips,
400 classes, random weights from ``checkpoint.ensure_checkpoint`` — on
real files decoded by the native library this run builds. It checks
what comes out by the repo's own means (Pallas kernels against their
jnp twins at the shipped shapes, the serving applier's logits against
a float32 reference, termination flag 0, every request completed, no
compilation inside a measured window, ``parse_utils --check``), and
prints as its last line of standard output one JSON object with
exactly these keys, the device as JAX reports it::

    {"ok": true, "device": {"platform": "tpu", "kind": "...",
     "count": 1}}

The line before it is the summary (also ``summary.json``): versions,
compile-cache entries, every phase's readings, ``"claim": null``.
Every number it prints is a smoke reading, not a benchmark: one run,
short windows, no repeats. Any phase that fails raises, so the exit
code is non-zero and no result line is printed. It needs a TPU: JAX
falls back to the CPU when no accelerator comes up, and this script
stops there, naming the platform it found (``--platform cpu`` is the
builder's dry run of the control flow; its result line says "cpu").

One process owns the chip. The only children are ``make`` and the
dataset generator, neither of which opens an accelerator.

``--chips 4`` is the four-chip host's run: the flagship with its
network stage replicated over devices 0-3, the ring collectives with
the Pallas remote-copy kernel against their ``ppermute`` twins and the
weight-sharded ``rnb-shard-d2`` arm.

What it writes goes under one directory (``--out``, default
``chiprun_out/chip_smoke`` beside this file): job logs and
``summary.json``. The dataset (``data/``), the weights
(``checkpoints/``), the native build and the compile cache are
run-time products listed in ``.gitignore``.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

#: the procedural y4m population the benchmark measures (4:2:0, 128 source
#: frames so the sampler can place 15 clips, 192x256 so decode+resize
#: does real work) and a 112x112 MJPEG set for the dct pixel path, which
#: ships coefficients at source geometry. Quality 60: over all 16 clips
#: of all 12 videos the busiest frame holds 1679 nonzero coefficients
#: of the default wire budget's 2205; at q75 six clips exceed it, and
#: which clips a run samples depends on the dataset's path.
Y4M_SPEC = ("--labels", "4", "--videos-per-label", "11", "--frames", "128",
            "--size", "192x256", "--colorspace", "420")
MJPEG_SPEC = ("--labels", "2", "--videos-per-label", "6", "--frames", "128",
              "--size", "112x112", "--format", "mjpeg", "--quality", "60")

#: (phase, config, videos, mean_interval_ms, dataset) — bulk windows of
#: several seconds at the flagship's rate; the open-loop run offers 500
#: requests/s (mean interval 2 ms, the CLI's integer granularity)
ONE_CHIP_RUNS = (
    ("flagship-bulk", "rnb-fused-yuv-big.json", 6000, 0, "y4m"),
    ("flagship-open", "rnb-fused-yuv-big.json", 3000, 2, "y4m"),
    ("ragged", "rnb-fused-yuv-ragged.json", 600, 0, "y4m"),
    ("paged-zipf", "rnb-fused-yuv-paged-zipf.json", 600, 0, "y4m"),
    ("dct-ragged", "rnb-fused-dct-ragged.json", 600, 0, "mjpeg"),
)
FOUR_CHIP_RUNS = (
    ("flagship-r4", None, 12000, 0, "y4m"),
    ("shard-d2", "rnb-shard-d2.json", 24, 0, "y4m"),
)


def say(msg: str) -> None:
    print("[chip_smoke] %s" % msg, flush=True)


def run_child(cmd) -> None:
    """A child that never touches JAX (make, the dataset generator)."""
    subprocess.run(cmd, check=True, stdout=subprocess.DEVNULL)


def build_native() -> None:
    """Build both native libraries from native/*.cpp in THIS run (-B:
    nothing inherited is trusted) and insist that they load."""
    run_child(["make", "-B", "-C", os.path.join(REPO, "native")])
    from rnb_tpu import profiler
    from rnb_tpu.decode.native import load_native
    if load_native() is None:
        raise RuntimeError("native/build/librnb_decode.so was built but "
                           "does not load")
    if profiler._xplane_lib() is None:
        raise RuntimeError("native/build/librnb_xplane.so was built but "
                           "does not load")


def make_dataset(name: str, spec) -> str:
    root = os.path.join(REPO, "data", "chip_smoke_" + name)
    shutil.rmtree(root, ignore_errors=True)
    run_child([sys.executable,
               os.path.join(REPO, "scripts", "make_dataset.py"),
               "--root", root, "--seed", "0", *spec])
    return root


def result_line(device: dict) -> str:
    """The last line of standard output: the chip check reads exactly
    these keys, so everything else goes on the summary line before."""
    return json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}})


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


# -- kernels against their twins --------------------------------------

def lowers_to_pallas(fn, *args) -> bool:
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def check_kernels(on_chip: bool) -> dict:
    """Each single-chip Pallas kernel at the shapes the shipped configs
    use, against its jnp twin. The byte-moving and elementwise kernels
    must match bit for bit, as the interpret-mode tests assert. The DCT
    kernel cannot on a TPU: its IDCT matmuls and XLA's sum in different
    orders, so planes that land within float rounding of a quantization
    boundary flip by one level, and BT.601 spreads one-level flips in
    Y, U and V over at most ceil(1 + 1.772) = 3 output levels. It is
    held to that bound, on at most 1% of the elements, against both its
    twin and the float64 numpy oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from rnb_tpu.ops import dct, pages, preprocess, ragged

    rng = np.random.RandomState(0)
    out = {}

    def record(name, fn, args, twin, exact=True, oracle=None, jitted=None):
        # ``jitted``: the entry dispatches through a jit of its own;
        # ``twin`` None: plain jnp with no kernel, held to its oracle
        if on_chip and twin is not None \
                and not lowers_to_pallas(jitted or fn, *args):
            raise AssertionError("%s reached its jnp twin on a TPU" % name)
        got = np.asarray(jax.block_until_ready(
            (fn if jitted else jax.jit(fn))(*args)), np.float32)
        if not np.isfinite(got).all():
            raise AssertionError("%s produced non-finite values" % name)
        refs = {}
        if twin is not None:
            refs["twin"] = np.asarray(jax.jit(twin)(*args), np.float32)
        if oracle is not None:
            refs["oracle"] = oracle
        level = 2.0 / 255.0
        verdicts = []
        for what, ref in refs.items():
            diff = np.abs(got - ref)
            if exact and what == "twin" and diff.max() > 0:
                raise AssertionError(
                    "%s differs from its %s on %d element(s)"
                    % (name, what, int((diff > 0).sum())))
            # whole output levels apart (the oracle is not rounded to
            # bf16, so a fraction of a level is not a difference)
            levels = np.rint(diff / level)
            share = float((levels > 0).mean())
            if levels.max() > 3 or share > 0.01:
                raise AssertionError(
                    "%s vs %s: up to %d levels apart, %.3f%% of elements "
                    "differ" % (name, what, levels.max(), 100 * share))
            out["%s.%s" % (name, what)] = {
                "bit_equal": bool(diff.max() == 0),
                "max_levels": int(levels.max()),
                "differing_share": round(share, 6)}
            verdicts.append(
                "%s:bit-equal" % what if diff.max() == 0 else
                "%s:<=%d levels on %.3f%%" % (what, levels.max(),
                                              100 * share))
        say("kernel %-28s %s" % (name, " ".join(verdicts)))

    def masked_normalize(pool, valid):
        return jnp.where(ragged._row_mask(pool, valid),
                         preprocess.normalize_u8(pool),
                         jnp.zeros((), jnp.bfloat16))

    for rows in (15, 48):  # the rgb loaders' row pools
        clips = jnp.asarray(rng.randint(0, 256, (rows, 8, 112, 112, 3),
                                        np.uint8))
        record("normalize_u8/%d" % rows, preprocess.normalize_u8,
               (clips,), None,
               oracle=(np.asarray(clips, np.float32) * 2.0 - 255.0)
               / np.float32(255.0))
        record("ragged_normalize_u8/%d" % rows,
               ragged.ragged_normalize_u8,
               (clips, np.int32(rows * 2 // 3)), masked_normalize)

    # the pager's clip arena: 4-row pages of packed yuv420 clips
    pool = jnp.asarray(rng.randint(0, 256, (15, 8, 18816), np.uint8))
    slab = jnp.asarray(rng.randint(0, 256, (16 * 4, 8, 18816), np.uint8))
    src = np.full((15,), -1, np.int32)
    src[[1, 3, 14]] = (5, 63, 0)
    record("gather_rows/15", pages.gather_rows, (pool, slab, src),
           pages.gather_rows_reference, jitted=pages._gather_jit())

    # (15, 8, elems) int16 coefficient rows at 112x112, q75-like spectra
    nb = dct.num_dct_blocks(112, 112)
    wire = np.zeros((15, 8, dct.dct_frame_elems(112, 112)), np.int16)
    for i in range(15):
        for f in range(8):
            zz = np.zeros((nb, 64), np.int16)
            zz[:, 0] = rng.randint(-900, 900, nb)
            for k in range(1, 6):
                zz[:, k] = rng.randint(-60, 60, nb) * (rng.rand(nb) < 0.5)
            wire[i, f] = dct.pack_frame_dct(zz, 112, 112)
    oracle = (dct.dct_rows_to_rgb_numpy(wire, 112, 112)
              .astype(np.float32) * 2.0 - 255.0) / 255.0

    def dct_twin(rows):
        return dct._dct_convert_jnp(*dct.unpack_dct_rows(rows, 112, 112),
                                    112, 112, jnp.bfloat16)

    def ragged_dct_twin(rows, valid):
        mask = jnp.arange(15).reshape((15, 1, 1, 1, 1)) < valid
        return jnp.where(mask, dct_twin(rows), jnp.zeros((), jnp.bfloat16))

    record("normalize_dct/15",
           lambda rows: dct.normalize_dct(rows, 112, 112),
           (jnp.asarray(wire),), dct_twin, exact=not on_chip,
           oracle=oracle)
    ragged_oracle = oracle.copy()
    ragged_oracle[9:] = 0.0
    record("ragged_normalize_dct/15",
           lambda rows, valid: dct.ragged_normalize_dct(rows, valid,
                                                        112, 112),
           (jnp.asarray(wire), np.int32(9)), ragged_dct_twin,
           exact=not on_chip, oracle=ragged_oracle)
    return out


# -- the serving applier against a float32 reference ------------------

def check_logits() -> dict:
    """The flagship network stage's own jitted applier (bf16, fused
    yuv420 ingest, the warmed 6-row bucket) on the default device,
    against the same network in float32 at highest matmul precision on
    the host CPU, same weights, seeded input. Logits, not classes:
    with random weights the largest logit changes on rounding.

    Tolerance: bf16 carries 8 significant bits through 18 conv layers
    with f32 accumulation; on the CPU backend the same bf16-vs-f32
    comparison lands near 1% of the logits' spread. 5% of the spread
    catches a wrong ingest, a wrong layout or lost weights (each moves
    logits by the spread itself) and would also catch an 8-bit
    integer path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from rnb_tpu.models.r2p1d import checkpoint as ckpt
    from rnb_tpu.models.r2p1d import model as stage
    from rnb_tpu.models.r2p1d.network import (KINETICS_CLASSES,
                                              R18_LAYER_SIZES,
                                              R2Plus1DClassifier)
    from rnb_tpu.ops.yuv import normalize_yuv420

    hw = stage.FRAME_HW
    planes = np.random.RandomState(7).randint(
        0, 256, (6, stage.CONSECUTIVE_FRAMES, hw * hw * 3 // 2), np.uint8)
    device = jax.devices()[0]
    apply = stage._shared_apply(1, 5, KINETICS_CLASSES,
                                tuple(R18_LAYER_SIZES),
                                pixel_path="yuv420")
    params = stage._shared_params(1, 5, KINETICS_CLASSES,
                                  tuple(R18_LAYER_SIZES), None, device)
    got = np.asarray(apply(params, jax.device_put(planes, device)),
                     np.float32)

    host = jax.devices("cpu")[0]
    reference = R2Plus1DClassifier(dtype=jnp.float32)
    with jax.default_device(host), \
            jax.default_matmul_precision("highest"):
        ref = np.asarray(reference.apply(
            ckpt.load_for_range(1, 5),
            normalize_yuv420(jnp.asarray(planes), hw, hw,
                             dtype=jnp.float32), train=False))
    if got.shape != (6, KINETICS_CLASSES) or not np.isfinite(got).all():
        raise AssertionError("logits: shape %r, finite %s"
                             % (got.shape, np.isfinite(got).all()))
    spread = float(ref.std())
    worst = float(np.abs(got - ref).max())
    if worst > 0.05 * spread:
        raise AssertionError(
            "serving logits are %.4f from the float32 reference, over "
            "5%% of its spread %.4f" % (worst, spread))
    return {"max_abs_diff": round(worst, 5), "ref_spread": round(spread, 5),
            "share_of_spread": round(worst / spread, 4)}


# -- serving runs ------------------------------------------------------

def four_chip_flagship(log_dir: str) -> str:
    """The flagship with its network step's queue group on devices
    0-3 — the reference's own replication, nothing else changed but
    the root ``handoff`` key, which only turns on the accounting of
    where each batch was re-homed. Derived into the run's directory:
    it is this run's arrangement, not a shipped config."""
    with open(os.path.join(REPO, "configs", "rnb-fused-yuv-big.json")) as f:
        config = json.load(f)
    config["_comment"] = ("derived by chip_smoke.py --chips 4 from "
                          "configs/rnb-fused-yuv-big.json")
    config["pipeline"][1]["queue_groups"][0]["devices"] = [0, 1, 2, 3]
    config["handoff"] = {"enabled": True, "mode": "device"}
    path = os.path.join(log_dir, "rnb-fused-yuv-big-r4.json")
    with open(path, "w") as f:
        json.dump(config, f, indent=2)
    return path


def final_instance_rows(log_dir: str) -> dict:
    """Completed requests per final-stage instance table."""
    rows = {}
    for name in sorted(os.listdir(log_dir)):
        if "-group" in name and name.endswith(".txt"):
            with open(os.path.join(log_dir, name)) as f:
                rows[name[:-4]] = max(0, sum(
                    1 for line in f
                    if line.strip() and not line.startswith("#")) - 1)
    return rows


def serve(phase: str, config_path: str, videos: int, interval_ms: int,
          data_root: str, log_base: str) -> dict:
    """One ``run_benchmark`` job held to the smoke's contract."""
    import parse_utils

    from rnb_tpu.benchmark import run_benchmark
    os.environ["RNB_TPU_DATA_ROOT"] = data_root
    t0 = time.monotonic()
    result = run_benchmark(config_path=config_path,
                           mean_interval_ms=interval_ms,
                           num_videos=videos, log_base=log_base,
                           print_progress=False, seed=0, job_id=phase)
    wall = time.monotonic() - t0
    steady_new = sum(sig.get("steady_new", 0)
                     for sig in result.compile_signatures.values())
    problems, _ = parse_utils.check_job_detail(result.log_dir)
    reading = {
        "videos_per_s": round(result.throughput_vps, 1),
        "window_s": round(result.total_time_s, 2),
        "p50_ms": result.p50_latency_ms and round(result.p50_latency_ms, 2),
        "p99_ms": result.p99_latency_ms and round(result.p99_latency_ms, 2),
        "completed": result.num_completed, "failed": result.num_failed,
        "shed": result.num_shed,
        "termination_flag": result.termination_flag,
        "steady_new": steady_new,
        "warmup_s": {k: round(v, 1) for k, v in result.warmup_s.items()},
        "wall_s": round(wall, 1),
        "check": "OK" if not problems else problems,
    }
    say("phase=%s %s (smoke, not a benchmark)"
        % (phase, " ".join("%s=%s" % kv for kv in reading.items())))
    if result.termination_flag != 0:
        raise AssertionError("%s: termination flag %d"
                             % (phase, result.termination_flag))
    # an open-loop client keeps sending until the target is counted,
    # so a request or two already in flight may complete beyond it
    enough = (result.num_completed >= videos if interval_ms
              else result.num_completed == videos)
    if not enough or result.num_failed or result.num_shed:
        raise AssertionError(
            "%s: %d completed, %d failed, %d shed of %d requests"
            % (phase, result.num_completed, result.num_failed,
               result.num_shed, videos))
    if steady_new:
        raise AssertionError("%s: %d compilation(s) inside the measured "
                             "window" % (phase, steady_new))
    if problems:
        raise AssertionError("%s: parse_utils --check: %s"
                             % (phase, problems))
    reading["log_dir"] = os.path.relpath(result.log_dir, REPO)
    reading["result"] = result
    return reading


# -- four chips --------------------------------------------------------

def check_ring_collectives(devices) -> dict:
    """ring_shift / ring_all_gather / ring_psum_scatter with the Pallas
    remote-copy kernel on a 4-device ring, against their ppermute
    twins. Pure movement must match bit for bit; the reduce-scatter
    adds in the same ring order in both bodies, on values exactly
    representable in f32, so it must too."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from rnb_tpu.ops import handoff_dma

    mesh = Mesh(np.array(devices[:4]), ("ring",))
    n = 4
    rng = np.random.RandomState(3)
    out = {}

    def both(name, fn, x, **kwargs):
        results = [np.asarray(jax.block_until_ready(
            fn(x, mesh, use_pallas=flag, **kwargs)))
            for flag in (True, False)]
        if not np.array_equal(*results):
            raise AssertionError("%s: the remote-copy kernel and the "
                                 "ppermute twin disagree" % name)
        out[name] = "bit-equal to the ppermute twin"
        say("ring %-22s pallas remote copy == ppermute twin" % name)
        return results[0]

    rows = rng.randint(-1000, 1000, (n * 8, 256)).astype(np.float32)
    sharded = jax.device_put(rows, NamedSharding(mesh, P("ring")))
    for shift in (1, 2):
        moved = both("ring_shift/%d" % shift, handoff_dma.ring_shift,
                     sharded, shift=shift)
        if not np.array_equal(moved, np.roll(rows, shift * 8, axis=0)):
            raise AssertionError("ring_shift/%d is not a roll by %d "
                                 "shards" % (shift, shift))
    cols = jax.device_put(rows, NamedSharding(mesh, P(None, "ring")))
    gathered = both("ring_all_gather", handoff_dma.ring_all_gather, cols)
    if not np.array_equal(gathered, rows):
        raise AssertionError("ring_all_gather is not the concatenation")
    stack = rng.randint(-1000, 1000, (n, 8, 512)).astype(np.float32)
    summed = both("ring_psum_scatter", handoff_dma.ring_psum_scatter,
                  jax.device_put(stack, NamedSharding(mesh, P("ring"))))
    if not np.array_equal(summed, stack.sum(axis=0)):
        raise AssertionError("ring_psum_scatter is not the sum")
    return out


# -- main --------------------------------------------------------------

def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                        help="'cpu' is the builder's dry run; it has to "
                             "be asked for by name")
    parser.add_argument("--out", default=None,
                        help="output directory (default chiprun_out/"
                             "chip_smoke[-4chip] beside this file)")
    parser.add_argument("--videos", type=int, default=None,
                        help="requests per serving phase (dry runs)")
    parser.add_argument("--only", default=None,
                        help="comma-separated phases to run (builder's "
                             "debugging; the summary lists them)")
    parser.add_argument("--deadline-s", type=float, default=1150.0,
                        help="dump every thread's stack and exit 1 if "
                             "the run is still going after this long")
    args = parser.parse_args(argv)
    faulthandler.dump_traceback_later(args.deadline_s, exit=True)
    t_start = time.monotonic()
    sys.path.insert(0, REPO)
    sys.path.insert(0, os.path.join(REPO, "scripts"))

    # the first JAX import of the process, and the device it found,
    # before any work
    import jax
    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jaxlib

    from rnb_tpu.devices import require_platform
    from rnb_tpu.models.r2p1d.flops import peak_tflops_for
    devices = require_platform(args.platform)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    peak = peak_tflops_for(device["kind"], device["platform"])
    if len(devices) < args.chips:
        raise SystemExit("chip_smoke: --chips %d needs %d %s devices, "
                         "found %d" % (args.chips, args.chips,
                                       args.platform, len(devices)))
    on_chip = device["platform"] == "tpu"
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    say("jax=%s jaxlib=%s libtpu=%s platform=%s device_kind=%s "
        "device_count=%d peak_tflops=%s"
        % (jax.__version__, jaxlib.__version__, libtpu, device["platform"],
           device["kind"], device["count"], peak))

    out_dir = os.path.abspath(args.out or os.path.join(
        REPO, "chiprun_out",
        "chip_smoke-4chip" if args.chips == 4 else "chip_smoke"))
    shutil.rmtree(out_dir, ignore_errors=True)
    log_base = os.path.join(out_dir, "logs")
    os.makedirs(log_base)

    build_native()
    say("decode_backend=native-y4m (native/build made in this run)")

    from rnb_tpu.benchmark import enable_compilation_cache
    from rnb_tpu.models.r2p1d import checkpoint
    cache_dir = enable_compilation_cache()
    entries_before = cache_entries(cache_dir)
    say("compile_cache_dir=%s entries_before=%d"
        % (cache_dir, entries_before))
    had_weights = os.path.exists(checkpoint.DEFAULT_CKPT_PATH)
    checkpoint.ensure_checkpoint(seed=0)
    say("weights=%s (%s)" % (os.path.relpath(checkpoint.DEFAULT_CKPT_PATH,
                                             REPO),
                             "found on disk" if had_weights
                             else "made in this run from seed 0"))

    runs = FOUR_CHIP_RUNS if args.chips == 4 else ONE_CHIP_RUNS
    plan = (["kernels", "logits"] if args.chips == 1 else []) \
        + [run[0] for run in runs] + (["ring"] if args.chips == 4 else [])
    if args.only:
        chosen = args.only.split(",")
        unknown = sorted(set(chosen) - set(plan))
        if unknown:
            raise SystemExit("chip_smoke: unknown phase(s) %s; this run "
                             "has %s" % (unknown, plan))
        plan = [phase for phase in plan if phase in chosen]
    data_roots = {}
    for kind, spec in (("y4m", Y4M_SPEC), ("mjpeg", MJPEG_SPEC)):
        if any(run[0] in plan and run[4] == kind for run in runs):
            data_roots[kind] = make_dataset(kind, spec)
    say("datasets=%s (scripts/make_dataset.py, seed 0); phases=%s"
        % (sorted(data_roots), plan))

    phases = {}
    if "kernels" in plan:
        phases["kernels"] = check_kernels(on_chip)
    if "logits" in plan:
        phases["logits"] = check_logits()
        say("logits vs float32 reference: %s" % phases["logits"])
    say("checks done at %.0fs" % (time.monotonic() - t_start))
    for phase, config_name, videos, interval_ms, kind in runs:
        if phase not in plan:
            continue
        if config_name is None:
            config_path = four_chip_flagship(out_dir)
        else:
            config_path = os.path.join(REPO, "configs", config_name)
        reading = serve(phase, config_path, args.videos or videos,
                        interval_ms, data_roots[kind], log_base)
        result = reading.pop("result")
        if phase == "flagship-r4":
            split = final_instance_rows(result.log_dir)
            reading["completed_by_instance"] = split
            # every emitted row is 8 packed 4:2:0 frames of 112x112
            put_bytes = result.total_rows * 8 * 18816
            reading["input_bytes"] = {
                "put_on_loader_device": put_bytes,
                "rehomed_device_to_device": result.handoff_d2d_bytes,
                "through_host": result.handoff_host_bytes}
            say("flagship-r4 split=%s; all %d input bytes are put on the "
                "loader's device (%s:0) first; the consuming replicas "
                "then re-homed %d of them device to device and %d "
                "through the host"
                % (split, put_bytes, device["platform"],
                   result.handoff_d2d_bytes, result.handoff_host_bytes))
            want = {"%s%d-group0-%d" % (device["platform"], i, i)
                    for i in range(4)}
            if set(split) != want or min(split.values()) < 1:
                raise AssertionError(
                    "flagship-r4: expected completed requests on each "
                    "of %s, got %s" % (sorted(want), split))
        phases[phase] = reading
    if "ring" in plan:
        if not on_chip:
            raise SystemExit("chip_smoke: the remote-copy kernel exists "
                             "on TPUs only; drop 'ring' from a cpu dry run")
        phases["ring"] = check_ring_collectives(devices)

    entries_after = cache_entries(cache_dir)
    summary = {
        "ok": True,
        "device": device,
        "chips": args.chips,
        "phases_run": plan,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
        "decode_backend": "native-y4m",
        "compile_cache": {"dir": cache_dir, "entries_before": entries_before,
                          "entries_after": entries_after},
        "wall_s": round(time.monotonic() - t_start, 1),
        "smoke_not_a_benchmark": phases,
        "claim": None,
    }
    say("compile_cache entries_before=%d entries_after=%d wall_s=%.1f"
        % (entries_before, entries_after, summary["wall_s"]))
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    faulthandler.cancel_dump_traceback_later()
    say("summary %s" % json.dumps(summary))
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
