"""bench.py driver contract: exactly one JSON line on stdout.

The round driver runs ``python bench.py`` and records the single JSON
line; this test pins the schema (metric/value/unit/vs_baseline) and the
exit code using the reduced-geometry config via env overrides.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_prints_one_json_line(tmp_path):
    env = dict(os.environ)
    env.update({
        "RNB_BENCH_VIDEOS": "6",
        "RNB_BENCH_CONFIG": os.path.join(REPO, "configs",
                                         "r2p1d-tiny.json"),
        "RNB_BENCH_LOG_BASE": str(tmp_path / "logs"),
        "RNB_BENCH_PLATFORM": "cpu",
        "RNB_BENCH_DATASET": "synth",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, "stdout must be exactly one line: %r" % lines
    payload = json.loads(lines[0])
    # the driver contract plus the round-4 evidence keys (p50/p99, clip
    # rate, analytic FLOPs, MFU, decode backend)
    assert set(payload) >= {"metric", "value", "unit", "vs_baseline",
                            "platform", "num_devices", "num_videos",
                            "config", "note", "decode_backend", "p50_ms",
                            "p99_ms", "clips_per_sec", "gflops_per_clip",
                            "tflops", "mfu", "measured_window_s",
                            "device_kind", "devices_used"}
    assert payload["metric"] == "videos_per_sec"
    assert payload["unit"] == "videos/s"
    assert payload["value"] > 0
    # the baseline ratio is only published for real-TPU measurements;
    # this forced-CPU run must refuse the comparison and say why
    assert payload["platform"] == "cpu"
    assert payload["vs_baseline"] is None
    assert "not a TPU" in payload["note"]
    assert payload["num_devices"] >= 1
    assert payload["num_videos"] == 6
    assert payload["config"].endswith("r2p1d-tiny.json")
    assert payload["decode_backend"] == "synthetic"
    assert payload["mfu"] is None  # no spec peak for the CPU backend


def test_bench_y4m_mode_uses_real_decode(tmp_path):
    """Default dataset mode decodes real files: a fresh dataset root is
    populated once and the emitted line says which backend ran."""
    env = dict(os.environ)
    env.update({
        "RNB_BENCH_VIDEOS": "6",
        "RNB_BENCH_CONFIG": os.path.join(REPO, "configs",
                                         "r2p1d-tiny.json"),
        "RNB_BENCH_LOG_BASE": str(tmp_path / "logs"),
        "RNB_BENCH_PLATFORM": "cpu",
        "RNB_TPU_DATA_ROOT": str(tmp_path / "data"),
        "RNB_BENCH_DATASET_LABELS": "2",
        "RNB_BENCH_DATASET_VPL": "4",
        "RNB_BENCH_DATASET_FRAMES": "24",
        "RNB_BENCH_DATASET_SIZE": "64x64",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    payload = json.loads(proc.stdout.strip())
    assert payload["decode_backend"] in ("native-y4m", "numpy-y4m")
    assert payload["value"] > 0
    # the dataset generator ran against the requested root
    found = []
    for _dir, _sub, files in os.walk(str(tmp_path / "data")):
        found += [f for f in files if f.endswith(".y4m")]
    assert len(found) >= 8
