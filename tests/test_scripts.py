"""Offline log-analysis scripts parse what the runtime actually writes.

The reference shipped a parser stale against its own log schema
(SURVEY.md §2.1 #15); these tests pin ours to the real writers by
round-tripping through TimeCardSummary.save_full_report and the
log-meta format emitted by rnb_tpu/benchmark.py.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))

from parse_utils import (decompose_latency, get_data,  # noqa: E402
                         get_data_from_all_logs, parse_meta,
                         parse_timing_table)
from rnb_tpu.telemetry import TimeCard, TimeCardSummary, logname  # noqa: E402


def _make_job(log_base, job_id, num_requests=5, mi=90):
    """Write a job dir through the real telemetry writers."""
    keys = ["enqueue_filename", "runner0_start", "inference0_start",
            "inference0_finish", "runner1_start", "inference1_start",
            "inference1_finish"]
    summary = TimeCardSummary()
    t = 1000.0
    for req in range(num_requests):
        tc = TimeCard(req)
        for k_idx, key in enumerate(keys):
            tc.timings[key] = t + req * 10.0 + k_idx * 0.5
        tc.add_device("tpu0")
        tc.add_device("tpu1")
        summary.register(tc)
    path = logname(job_id, "tpu1", 0, 0, base=log_base)
    with open(path, "w") as f:
        summary.save_full_report(f)
    with open(os.path.join(log_base, job_id, "log-meta.txt"), "w") as f:
        f.write("Args: Namespace(mean_interval_ms=%d, batch_size=1, "
                "videos=%d, queue_size=500, "
                "config_file_path='configs/r2p1d-whole.json')\n"
                % (mi, num_requests))
        f.write("%f %f\n" % (t, t + 50.0))
        f.write("Termination flag: 0\n")
    return path


def test_parse_meta_roundtrip(tmp_path):
    _make_job(str(tmp_path), "job-a", num_requests=5, mi=90)
    meta = parse_meta(str(tmp_path / "job-a"))
    assert meta["mean_interval_ms"] == 90
    assert meta["videos"] == 5
    assert meta["config_file_path"] == "configs/r2p1d-whole.json"
    assert meta["termination_flag"] == 0
    assert meta["wall_time_s"] == pytest.approx(50.0)
    assert meta["throughput_vps"] == pytest.approx(0.1)


def test_parse_timing_table_types_and_identity(tmp_path):
    path = _make_job(str(tmp_path), "job-a")
    df = parse_timing_table(path)
    assert len(df) == 5
    assert df["enqueue_filename"].dtype == float
    assert df["device0"].iloc[0] == "tpu0"
    assert df["final_device"].iloc[0] == "tpu1"
    assert df["final_group"].iloc[0] == 0
    assert df["final_instance"].iloc[0] == 0


def test_get_data_from_all_logs_two_jobs(tmp_path):
    _make_job(str(tmp_path), "job-a", num_requests=5, mi=90)
    _make_job(str(tmp_path), "job-b", num_requests=3, mi=0)
    jobs, requests = get_data_from_all_logs(str(tmp_path))
    assert set(jobs["job_id"]) == {"job-a", "job-b"}
    assert len(requests) == 8
    assert set(requests["mean_interval_ms"]) == {90, 0}


def test_decompose_latency_standard_schema(tmp_path):
    path = _make_job(str(tmp_path), "job-a")
    df = decompose_latency(parse_timing_table(path))
    # every adjacent gap in the synthetic cards is exactly 0.5 s = 500 ms
    for col in ("filename_queue_wait", "decode", "frame_queue_wait",
                "device_comm", "neural_net"):
        assert df[col].iloc[0] == pytest.approx(500.0), col


def test_dispatch_batch_sizes(tmp_path):
    """Requests sharing an inference-finish stamp = one fused dispatch;
    the distribution recovers fused batch sizes from the logs."""
    from parse_utils import dispatch_batch_sizes, parse_timing_table
    keys = ["enqueue_filename", "runner0_start", "inference0_start",
            "inference0_finish"]
    summary = TimeCardSummary()
    t = 500.0
    # two fused dispatches of 3, one single: stamps shared per dispatch
    for dispatch, size in enumerate((3, 3, 1)):
        finish = t + dispatch
        for _ in range(size):
            tc = TimeCard(0)
            for k_idx, key in enumerate(keys[:-1]):
                tc.timings[key] = finish - 0.1 * (len(keys) - k_idx)
            tc.timings["inference0_finish"] = finish
            tc.add_device("tpu0")
            summary.register(tc)
    path = logname("job-f", "tpu0", 0, 0, base=str(tmp_path))
    with open(path, "w") as f:
        summary.save_full_report(f)
    sizes = dispatch_batch_sizes(parse_timing_table(path))
    assert sizes.to_dict() == {1: 1, 3: 2}

    df = parse_timing_table(path)
    # explicit missing/empty step must raise, not return empty
    with pytest.raises(ValueError):
        dispatch_batch_sizes(df, step=7)
    # a segment job's deeper steps carry suffixed merged keys; the
    # default must refuse rather than mislabel a pre-fork stage
    df["inference1_finish-0"] = df["inference0_finish"] + 1.0
    assert dispatch_batch_sizes(df).empty
    # but an explicit plain step still works
    assert dispatch_batch_sizes(df, step=0).to_dict() == {1: 1, 3: 2}


def test_latency_summary_cli(tmp_path, capsys):
    _make_job(str(tmp_path), "job-a")
    import latency_summary
    out_png = str(tmp_path / "latency.png")
    rc = latency_summary.main(["--log-base", str(tmp_path),
                               "--out", out_png])
    assert rc == 0
    assert os.path.exists(out_png)
    captured = capsys.readouterr()
    assert "job-a" in captured.out


def test_latency_summary_mixed_schemas(tmp_path, capsys):
    """Jobs with different pipeline depths (different timing columns)
    must each report a finite total — the union-of-schemas NaN padding
    for columns a job lacks must not poison its sum."""
    _make_job(str(tmp_path), "job-2stage", num_requests=4, mi=0)
    # a deeper job with an extra stage's columns
    keys = ["enqueue_filename", "runner0_start", "inference0_start",
            "inference0_finish", "runner1_start", "inference1_start",
            "inference1_finish", "runner2_start", "inference2_start",
            "inference2_finish"]
    summary = TimeCardSummary()
    for req in range(3):
        tc = TimeCard(req)
        for k_idx, key in enumerate(keys):
            tc.timings[key] = 2000.0 + req * 10.0 + k_idx * 0.5
        tc.add_device("tpu0")
        tc.add_device("tpu1")
        tc.add_device("tpu2")
        summary.register(tc)
    path = logname("job-3stage", "tpu2", 0, 0, base=str(tmp_path))
    with open(path, "w") as f:
        summary.save_full_report(f)
    with open(os.path.join(str(tmp_path), "job-3stage",
                           "log-meta.txt"), "w") as f:
        f.write("Args: Namespace(mean_interval_ms=0, batch_size=1, "
                "videos=3, queue_size=500, "
                "config_file_path='configs/rnb.json')\n")
        f.write("2000.0 2050.0\nTermination flag: 0\n")

    import latency_summary
    rc = latency_summary.main(["--log-base", str(tmp_path)])
    assert rc == 0
    out = capsys.readouterr().out
    for line in out.splitlines():
        if "end-to-end mean latency" in line:
            assert "nan" not in line.lower(), line


def test_latency_summary_cli_empty(tmp_path):
    import latency_summary
    assert latency_summary.main(["--log-base", str(tmp_path)]) == 1


def test_decode_bench_smoke(tmp_path):
    """scripts/decode_bench.py: decodes a tiny dataset tree with the
    native backend and reports a frame count matching every frame
    decoded exactly once."""
    import json as _json
    import subprocess as _sp

    import numpy as np

    from rnb_tpu.decode import write_mjpeg, write_y4m
    from rnb_tpu.decode.native import native_available
    if not native_available():
        pytest.skip("native decode library not built")

    rng = np.random.default_rng(7)
    frames = rng.integers(0, 255, size=(17, 32, 48, 3), dtype=np.uint8)
    label = tmp_path / "label000"
    label.mkdir()
    write_mjpeg(str(label / "a.mjpg"), frames)
    write_y4m(str(label / "b.y4m"), frames)
    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "decode_bench.py")
    proc = _sp.run([sys.executable, script, str(tmp_path),
                    "--repeats", "1"], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    row = _json.loads(proc.stdout.strip().splitlines()[-1])
    # 17 frames, 8-frame clips -> 2 whole clips = 16 frames per video
    assert row["videos"] == 2
    assert row["frames"] == 32
    assert row["frames_per_sec"] > 0
    # an empty tree must fail loudly, not report 0-frame success
    empty = tmp_path / "empty"
    empty.mkdir()
    assert _sp.run([sys.executable, script, str(empty)],
                   capture_output=True).returncode != 0


def test_bench_diff_rules(tmp_path):
    """bench_diff's per-cell rules: ok->failed, throughput below the
    tolerance floor, and a vanished row are regressions; new rows and
    improvements are not."""
    import bench_diff

    baseline = {
        "a.json": {"videos_per_sec": 1.0, "ok": True,
                   "termination_flag": 0},
        "b.json": {"videos_per_sec": 1.0, "ok": True,
                   "termination_flag": 0},
        "c.json": {"videos_per_sec": 1.0, "ok": True,
                   "termination_flag": 0},
        "gone.json": {"videos_per_sec": 1.0, "ok": True,
                      "termination_flag": 0},
    }
    current = {
        "a.json": {"videos_per_sec": 0.6, "ok": True,
                   "termination_flag": 0},   # below the 30% floor
        "b.json": {"videos_per_sec": 2.0, "ok": True,
                   "termination_flag": 0},   # improvement: fine
        "c.json": {"videos_per_sec": 1.0, "ok": False,
                   "termination_flag": 3},   # was ok, now failed
        "new.json": {"videos_per_sec": 0.1, "ok": True,
                     "termination_flag": 0},  # new row: fine
    }
    lines, regressions = bench_diff.diff(baseline, current, 0.30)
    assert regressions == 3
    text = "\n".join(lines)
    assert "REGRESSION a.json" in text.replace("   ", " ") \
        or "a.json" in text
    assert sum(1 for line in lines if "REGRESSION" in line) == 2
    assert sum(1 for line in lines if "MISSING" in line) == 1
    assert sum(1 for line in lines if "NEW" in line) == 1
    # within tolerance: no regression
    lines, regressions = bench_diff.diff(
        baseline, dict(current, **{
            "a.json": {"videos_per_sec": 0.75, "ok": True,
                       "termination_flag": 0},
            "c.json": baseline["c.json"],
            "gone.json": baseline["gone.json"]}), 0.30)
    assert regressions == 0


def test_bench_diff_committed_artifacts_are_green():
    """The committed matrix must clear the committed floor — the
    `make benchdiff` contract a fresh checkout starts from."""
    import bench_diff
    assert bench_diff.main([]) == 0


def test_bench_diff_cli_detects_regression(tmp_path):
    import json as _json

    import bench_diff
    base = {"configs": [{"config": "x.json", "videos_per_sec": 1.0,
                         "ok": True, "termination_flag": 0}]}
    cur = {"configs": [{"config": "x.json", "videos_per_sec": 0.1,
                        "ok": True, "termination_flag": 0}]}
    bpath, cpath = tmp_path / "base.json", tmp_path / "cur.json"
    bpath.write_text(_json.dumps(base))
    cpath.write_text(_json.dumps(cur))
    assert bench_diff.main(["--baseline", str(bpath),
                            "--current", str(cpath)]) == 1
    assert bench_diff.main(["--baseline", str(bpath),
                            "--current", str(cpath),
                            "--tolerance", "0.95"]) == 0
    assert bench_diff.main(["--baseline", str(tmp_path / "nope.json"),
                            "--current", str(cpath)]) == 2
