"""Headline benchmark: videos/sec through the flagship pipeline.

Reproduces the reference's benchmark methodology (SURVEY.md §6) on this
framework, driven in bulk (max-throughput) mode against the baseline
from the reference's only published number (11.3 videos/s on one GPU
over config/r2p1d-whole.json, reference README.md:176-178). The default
topology here is ``configs/rnb-fused-yuv-big.json`` — the reference's
Replicate & Batch idea collapsed into the loader: R2P1DFusingLoader
submits every request to the decode pool on receipt, harvests
completed decodes and ships one fused device batch straight to the
network stage, whose jit opens with the yuv420 ingest (packed 4:2:0
planes -> chroma upsample -> BT.601 -> normalize, rnb_tpu/ops/yuv.py).
Batching without the extra host stage that made the standalone Batcher
topology host-bound (rnb-1chip measured 481 vs 874-909 fused in round
4); the 2-stage ``r2p1d-whole-yuv`` and the reference-shaped
``rnb-1chip`` remain measured side-by-side in scripts/bench_matrix.py.
The ``-big`` variant (fuse 20 / 48-row cap, buckets [6,15,24,36,48])
amortizes the per-dispatch cost over wider fused batches; adaptive
emission still sends small batches the moment the pipeline idles.
Whether the 48-row cap beats the 15-row one on a locally attached
chip is not measured (ROADMAP S3).

**Real decode by default.** The reference's number includes real video
decode through NVVL (reference models/r2p1d/model.py:140-151), so this
bench decodes real files too: it generates (once, cached under
``data/bench_y4m``) a y4m dataset via scripts/make_dataset.py and runs
it through the native C++ decode pool. ``RNB_BENCH_DATASET=mjpeg``
switches to compressed MJPEG input (baseline-JPEG Huffman+IDCT per
frame in native/decode.cpp — real codec work, the role NVDEC filled
for the reference); ``RNB_BENCH_DATASET=synth`` restores the
synthetic-id mode for apples-to-apples comparison with rounds ≤3; the
emitted ``decode_backend`` key states which path was measured.

Prints exactly ONE JSON line with throughput plus the evidence keys the
perf claim needs to be auditable:
  {"metric": "videos_per_sec", "value": N, "unit": "videos/s",
   "vs_baseline": N / 11.3, "platform": "tpu", "decode_backend": "...",
   "p50_ms": N, "p99_ms": N, "clips_per_sec": N,
   "gflops_per_clip": 42.14, "tflops": N, "mfu": N, ...}
and on unrecoverable failure a structured error line instead:
  {"metric": "videos_per_sec", "value": null, "unit": "videos/s",
   "vs_baseline": null, "error": "..."}

``vs_baseline`` is only reported when the measured platform is a TPU
— the reference number is a GPU-hardware number and comparing a
host-CPU run against it would be meaningless. ``mfu`` is analytic
conv+dense FLOPs (rnb_tpu/models/r2p1d/flops.py, cross-checked against
XLA cost_analysis in tests) divided by the device's spec-sheet bf16
peak; it is null on the CPU, and a TPU whose ``device_kind`` is not in
the peaks table is an error.

One process, one chip: JAX initializes once, here, and the run insists
on a TPU. JAX falls back to the CPU when no accelerator comes up, so a
platform other than ``tpu`` is the structured error line and exit code
1 — unless the CPU was asked for by name (``RNB_BENCH_PLATFORM=cpu``).
On a TPU, decoding real files without the native library
(``make -C native``) is an error too, not a silent numpy run.

Env knobs: RNB_BENCH_VIDEOS (default 10000), RNB_BENCH_CONFIG,
RNB_BENCH_MEAN_INTERVAL_MS (default 0 = bulk), RNB_BENCH_DATASET
(y4m|mjpeg|synth, default y4m), RNB_TPU_DATA_ROOT (use an existing
dataset instead of generating), RNB_BENCH_PLATFORM ("cpu" for a smoke
run on the host; anything else must be the platform JAX finds).
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import subprocess
import sys

#: reference README.md:176-178 — 500 videos / 44.249694 s on one GPU
BASELINE_VIDEOS_PER_SEC = 500.0 / 44.249694

#: the real stdout, captured before any redirect_stdout so the one-line
#: JSON contract holds whatever the harness prints
_REAL_STDOUT = sys.stdout


def _emit(payload: dict) -> None:
    _REAL_STDOUT.write(json.dumps(payload) + "\n")
    _REAL_STDOUT.flush()


def _emit_error(msg: str) -> int:
    _emit({
        "metric": "videos_per_sec",
        "value": None,
        "unit": "videos/s",
        "vs_baseline": None,
        "error": msg[:500],
    })
    return 1


def _dataset_spec():
    """Generated-dataset geometry (env-overridable for smoke tests):
    128 source frames so the sampler can place 15 non-overlapping
    8-frame clips (15*8=120 <= 128 keeps the reference's skewed [1,15]
    clip population intact), 192x256 source pixels so decode+resize
    does real work per frame. 4 labels x 11 videos is chosen because
    the per-id deterministic sampler locks each file's clip count to
    its path hash: this population lands at 4/44 large videos (9.1%)
    and 2.27 clips/video on average — matching the [1,15]@[10,1]
    weights the reference's sampler draws (a smaller set can skew to
    ~3% large and flatter the measured throughput). The share holds for
    the default data/bench_y4m root — ids are path-hashed, so custom
    RNB_TPU_DATA_ROOT datasets carry their own (still deterministic)
    mix."""
    e = os.environ.get
    return ("--labels", e("RNB_BENCH_DATASET_LABELS", "4"),
            "--videos-per-label", e("RNB_BENCH_DATASET_VPL", "11"),
            "--frames", e("RNB_BENCH_DATASET_FRAMES", "128"),
            "--size", e("RNB_BENCH_DATASET_SIZE", "192x256"),
            # 4:2:0 like real video — and decode is read-bandwidth
            # bound once the colourspace math runs on device, so the
            # stand-in for codec output should not double the bytes
            "--colorspace", e("RNB_BENCH_DATASET_COLORSPACE", "420"))


def _count_videos(root: str, exts=(".y4m",)) -> int:
    """Count videos using EXACTLY the pipeline iterator's scan rule
    (root/<label>/*<ext>, one level — R2P1DVideoPathIterator): a dataset
    this count accepts is a dataset the measured run actually consumes,
    so decode_backend can never claim real decode over a layout the
    iterator would silently skip (falling back to synth:// ids)."""
    if not os.path.isdir(root):
        return 0
    total = 0
    for label in os.listdir(root):
        label_dir = os.path.join(root, label)
        if os.path.isdir(label_dir):
            total += sum(1 for v in os.listdir(label_dir)
                         if v.endswith(tuple(exts)))
    return total


def _ensure_dataset(repo_dir: str):
    """Prepare the decode workload; -> (decode_backend, dataset_root).

    y4m mode (default): reuse RNB_TPU_DATA_ROOT if it already holds
    videos, else generate the procedural y4m tree once under
    data/bench_y4m; exports RNB_TPU_DATA_ROOT so the pipeline's path
    iterator and decode warm-up find it. synth mode: clears the root so
    the loader falls back to synth:// ids (rounds <=3 behavior).
    """
    mode = os.environ.get("RNB_BENCH_DATASET", "y4m")
    if mode == "synth":
        os.environ.pop("RNB_TPU_DATA_ROOT", None)
        return "synthetic", None
    if mode not in ("y4m", "mjpeg"):
        raise ValueError("RNB_BENCH_DATASET must be y4m, mjpeg or "
                         "synth, got %r" % mode)
    exts = (".y4m",) if mode == "y4m" else (".mjpg", ".mjpeg")
    user_root = os.environ.get("RNB_TPU_DATA_ROOT")
    root = user_root or os.path.join(repo_dir, "data", "bench_" + mode)
    spec = list(_dataset_spec())
    if mode == "mjpeg":
        # real codec work per frame: baseline-JPEG entropy decode +
        # IDCT (native/decode.cpp), the role NVDEC filled for the
        # reference (README.md:42-110)
        spec += ["--format", "mjpeg", "--quality",
                 os.environ.get("RNB_BENCH_MJPEG_QUALITY", "90")]
    spec_path = os.path.join(root, "DATASET_SPEC.json")
    spec_stale = False
    if not user_root and _count_videos(root, exts) > 0:
        # the generated cache is keyed by its spec: a geometry change
        # (e.g. the round-4 clip-mix fix) must regenerate, or the run
        # silently measures the old population while the evidence
        # describes the new one. User-supplied roots are never touched.
        try:
            with open(spec_path) as f:
                spec_stale = json.load(f) != spec
        except (OSError, ValueError):
            spec_stale = True
    if _count_videos(root, exts) == 0 or spec_stale:
        if spec_stale:
            import shutil
            sys.stderr.write("bench: regenerating %s (spec changed)\n"
                             % root)
            shutil.rmtree(root, ignore_errors=True)
        else:
            sys.stderr.write("bench: generating %s dataset under %s\n"
                             % (mode, root))
        subprocess.run(
            [sys.executable,
             os.path.join(repo_dir, "scripts", "make_dataset.py"),
             "--root", root, *spec],
            check=True, stdout=subprocess.DEVNULL)
        if _count_videos(root, exts) == 0:
            raise RuntimeError(
                "dataset generation produced no root/label/* videos "
                "under %s" % root)
        if not user_root:
            with open(spec_path, "w") as f:
                json.dump(spec, f)
    # the iterator consumes EVERY supported extension, so a root mixing
    # formats would measure a different population than decode_backend
    # claims — fail loud instead of publishing false evidence
    other_exts = (".mjpg", ".mjpeg") if mode == "y4m" else (".y4m",)
    n_other = _count_videos(root, other_exts)
    if n_other:
        raise RuntimeError(
            "dataset root %s holds %d %s video(s) alongside the %s "
            "dataset — the pipeline iterator would consume both and "
            "the decode_backend evidence key would lie; use a "
            "single-format root" % (root, n_other, other_exts, mode))
    os.environ["RNB_TPU_DATA_ROOT"] = root
    from rnb_tpu.decode.native import native_available
    native = native_available()
    if mode == "mjpeg":
        backend = "native-mjpeg" if native else "pil-mjpeg"
    else:
        backend = "native-y4m" if native else "numpy-y4m"
    return backend, root


def _config_stage_views(config: dict):
    """Shared with the devobs plane (rnb_tpu.devobs) — one merged-view
    rule so the published evidence and the runtime Compute:/Memory:
    accounting can never disagree on what a stage was configured as."""
    from rnb_tpu.devobs import config_stage_views
    return config_stage_views(config)


def _flops_per_clip_for_config(config: dict) -> float:
    """Analytic conv+dense FLOPs one clip costs across every network
    stage — delegated to rnb_tpu.devobs.flops_per_clip_for_config, the
    SAME config walk the device observability plane cross-foots its
    runtime ``compute_profile()`` seam against (``make devobs``), so
    the evidence line's gflops_per_clip and the Compute: log-meta line
    share one definition."""
    from rnb_tpu.devobs import flops_per_clip_for_config
    return flops_per_clip_for_config(config)


def _latency_semantics(config: dict) -> str:
    """\"completion\" when every stage blocks before stamping
    inference_finish; \"dispatch\" when any stage publishes async
    (async_dispatch step flag, or a mesh stage with sync_preds false) —
    the emitted p50/p99 then measure dispatch, and the evidence line
    must say so."""
    for step, views in _config_stage_views(config):
        for view in views:
            if view.get("async_dispatch"):
                return "dispatch"
            if (view.get("model", step.get("model", ""))
                    .endswith(".R2P1DMeshRunner")
                    and view.get("sync_preds") is False):
                return "dispatch"
    return "completion"


def _devices_used(config: dict) -> int:
    """Distinct accelerator devices the topology touches — delegated
    to rnb_tpu.devobs.devices_used, the same MFU denominator rule the
    Compute: log-meta line applies, so the two cross-foot by
    construction."""
    from rnb_tpu.devobs import devices_used
    return devices_used(config)


def main() -> int:
    repo_dir = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, repo_dir)

    wanted = os.environ.get("RNB_BENCH_PLATFORM", "tpu")
    import jax
    if wanted == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from rnb_tpu.decode.native import require_native
    from rnb_tpu.devices import require_platform
    try:
        require_platform(wanted)
        require_native(wanted)
    except RuntimeError as e:
        # the wrong platform (DeviceResolutionError), one named in
        # JAX_PLATFORMS that failed to initialize, or a TPU without
        # the native decoder: no chip is an answer, not something to
        # wait out, and a numpy decode is not what a TPU run measures
        return _emit_error("%s: %s" % (type(e).__name__, e))

    try:
        decode_backend, dataset_root = _ensure_dataset(repo_dir)
    except Exception as e:  # noqa: BLE001 — one-line contract
        return _emit_error("dataset preparation failed: %s: %s"
                           % (type(e).__name__, e))

    num_videos = int(os.environ.get("RNB_BENCH_VIDEOS", "10000"))
    config = os.environ.get(
        "RNB_BENCH_CONFIG",
        os.path.join(repo_dir, "configs", "rnb-fused-yuv-big.json"))
    mean_interval = int(os.environ.get("RNB_BENCH_MEAN_INTERVAL_MS", "0"))

    # everything the harness prints stays out of the one-line contract
    captured_err = io.StringIO()
    try:
        with contextlib.redirect_stdout(io.StringIO()), \
                contextlib.redirect_stderr(captured_err):
            line, termination_flag = measure(
                config, num_videos, mean_interval,
                decode_backend, dataset_root,
                log_base=os.environ.get("RNB_BENCH_LOG_BASE", "logs"))
    except Exception as e:  # noqa: BLE001 — one-line contract on any failure
        sys.stderr.write(captured_err.getvalue())
        return _emit_error("%s: %s" % (type(e).__name__, e))
    _emit(line)
    if termination_flag != 0:
        sys.stderr.write(captured_err.getvalue())
        sys.stderr.write("bench: abnormal termination flag %d\n"
                         % termination_flag)
        return 1
    return 0


def measure(config: str, num_videos: int, mean_interval: int,
            decode_backend: str, dataset_root, log_base: str = "logs",
            seed: int = 0):
    """Run one benchmark job; -> (evidence line dict, termination flag).

    Shared by the headline bench (one line to stdout) and
    scripts/bench_matrix.py (one row per config in the matrix artifact).
    """
    repo_dir = os.path.dirname(os.path.abspath(__file__))
    with open(config) as f:
        config_dict = json.load(f)
    from rnb_tpu.benchmark import run_benchmark
    result = run_benchmark(
        config_path=config,
        mean_interval_ms=mean_interval,
        num_videos=num_videos,
        log_base=log_base,
        print_progress=False,
        seed=seed,
    )

    # record what was actually measured: the live backend
    import jax
    devs = jax.devices()
    measured_platform = devs[0].platform
    line = {
        "metric": "videos_per_sec",
        "value": round(result.throughput_vps, 3),
        "unit": "videos/s",
        "vs_baseline": None,
        "platform": measured_platform,
        "device_kind": devs[0].device_kind,
        "num_devices": len(devs),
        "devices_used": _devices_used(config_dict),
        "num_videos": num_videos,
        "mean_interval_ms": mean_interval,
        "config": os.path.relpath(config, repo_dir),
        "decode_backend": decode_backend,
        "dataset": (os.path.relpath(dataset_root, repo_dir)
                    if dataset_root else None),
        "measured_window_s": round(result.total_time_s, 3),
        "p50_ms": (round(result.p50_latency_ms, 3)
                   if result.p50_latency_ms is not None else None),
        "p99_ms": (round(result.p99_latency_ms, 3)
                   if result.p99_latency_ms is not None else None),
        "latency_semantics": _latency_semantics(config_dict),
        # host-core saturation over the measured window (1-core host:
        # ~1.0 means the host is the ceiling) — the quantitative leg
        # of any host-bound claim
        "host_cpu_frac": (round(result.host_cpu_s / result.total_time_s,
                                3)
                          if result.total_time_s > 0 else None),
    }
    # device-utilization evidence: analytic conv+dense FLOPs (see
    # rnb_tpu/models/r2p1d/flops.py) x measured clip rate vs spec peak
    from rnb_tpu.models.r2p1d.flops import peak_tflops_for
    flops_per_clip = _flops_per_clip_for_config(config_dict)
    clips_per_sec = (result.clips_completed / result.total_time_s
                     if result.total_time_s > 0 else 0.0)
    line["clips_per_sec"] = round(clips_per_sec, 3)
    line["gflops_per_clip"] = round(flops_per_clip / 1e9, 3)
    tflops = clips_per_sec * flops_per_clip / 1e12
    line["tflops"] = round(tflops, 3)
    peak = peak_tflops_for(devs[0].device_kind, measured_platform)
    line["peak_tflops_per_device"] = peak
    line["mfu"] = (round(tflops / (peak * line["devices_used"]), 4)
                   if peak else None)
    if result.compute_stages:
        # devobs-enabled runs surface the runtime compute plane's own
        # figures next to the analytic ones — the `make devobs` gate
        # holds them equal to the digit (tflops_milli vs
        # round(tflops, 3); mfu_e4 vs round(mfu, 4); -1 = no peak)
        line["compute_tflops_milli"] = result.compute_tflops_milli
        line["compute_mfu_e4"] = result.compute_mfu_e4
    if measured_platform == "tpu":
        line["vs_baseline"] = round(
            result.throughput_vps / BASELINE_VIDEOS_PER_SEC, 3)
    else:
        # the baseline is a GPU-hardware number; comparing a host run
        # against it would publish a meaningless ratio
        line["note"] = ("vs_baseline omitted: measured platform is %r, "
                        "not a TPU" % measured_platform)
    return line, result.termination_flag


if __name__ == "__main__":
    sys.exit(main())
