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


def test_bench_matrix_unparseable_cell_is_contained(monkeypatch,
                                                    tmp_path):
    """A cell whose bench.py prints garbage costs that cell only."""
    import importlib
    import subprocess as _sp

    bench_matrix = importlib.import_module("bench_matrix")

    class FakeProc:
        returncode = 0
        stdout = "not json at all\n"
        stderr = ""

    monkeypatch.setattr(_sp, "run", lambda *a, **k: FakeProc())
    row = bench_matrix.run_cell("configs/x.json", 0, 4)
    assert "unparseable" in row["error"]


def test_device_busy_union_and_filter(tmp_path):
    import device_busy

    trace = tmp_path / "xprof-ops.txt"
    trace.write_text(
        "0 100 fusion.1\n"
        "50 150 convolution.2\n"          # overlaps fusion.1
        "300 400 copy.3\n"
        "0 1000 $threading.py:323 wait\n"  # host row: filtered out
        "0 900 Thread #7\n")
    planes = device_busy.load_intervals(str(trace))
    # legacy 3-column format: everything lands under one plane
    assert set(planes) == {"(all)"}
    ivals = planes["(all)"]
    assert len(ivals) == 3
    # union: [0,150) + [300,400) = 250 ns busy; the span denominator
    # comes from the UNFILTERED trace (the host row spans [0,1000)) so
    # device idle at the window's edges is not hidden
    stats = device_busy.summarize(ivals, span_bounds=(0, 1000))
    assert stats["busy_ms"] == 250 / 1e6
    assert stats["span_ms"] == 1000 / 1e6
    assert abs(stats["busy_fraction"] - 0.25) < 1e-9
    # host rows kept on demand
    all_planes = device_busy.load_intervals(str(trace),
                                            device_only=False)
    assert len(all_planes["(all)"]) == 5
    assert device_busy.main([str(trace)]) == 0


def test_device_busy_groups_planes(tmp_path, capsys):
    """4-column traces: busy fractions are computed per plane — XLine
    clock bases differ across planes, so a cross-plane union would
    conflate clocks (a 6 s capture once reported a 54 s 'span')."""
    import device_busy

    trace = tmp_path / "xprof-ops.txt"
    trace.write_text(
        "# t0_ns t1_ns plane op_name\n"
        "0 100 /device:TPU:0 fusion.1\n"
        "50 150 /device:TPU:0 convolution.2\n"
        "1000000 1000400 /host:CPU jit_apply(42)\n"  # other clock base
        "0 1000 /host:CPU $threading.py:1 wait\n")
    planes = device_busy.load_intervals(str(trace))
    assert set(planes) == {"/device:TPU:0", "/host:CPU"}
    # per-plane union, never merged across planes
    dev = device_busy.summarize(planes["/device:TPU:0"],
                                span_bounds=(0, 150))
    assert dev["busy_ms"] == 150 / 1e6
    assert abs(dev["busy_fraction"] - 1.0) < 1e-9
    # default report: named /device: planes ARE the device ops — host
    # planes are excluded wholesale (the jit_apply row is a host-side
    # dispatch span even though its name passes the legacy heuristic)
    assert device_busy.main([str(trace)]) == 0
    out = capsys.readouterr().out
    assert "/device:TPU:0" in out and "/host:CPU" not in out
    assert device_busy.main([str(trace), "--include-host"]) == 0
    out = capsys.readouterr().out
    assert "/device:TPU:0" in out and "/host:CPU" in out


def test_device_busy_window_mapping(tmp_path, capsys):
    """The measured-window cross-check: host-epoch window from the
    header is mapped onto the device timeline by anchoring flush_epoch
    to the plane's max t1, and busy is reported within that window
    only (the remote capture contains the whole device session, so the
    full-span fraction under-reports steady-state utilization)."""
    import device_busy

    trace = tmp_path / "xprof-ops.txt"
    # device timeline: ops at [0,1e9), [2e9,3e9), [9e9,10e9).
    # flush at epoch 110.0 anchors device t=10e9; window epoch
    # [101.0, 110.0] -> device [1e9, 10e9): clips the first op out
    # entirely except nothing (op1 ends at 1e9), keeps [2e9,3e9) and
    # [9e9,10e9) -> busy 2e9 of a 9e9 window.
    trace.write_text(
        "# t0_ns t1_ns plane op_name\n"
        "# window_epoch 101.0 110.0 flush_epoch 110.0\n"
        "0 1000000000 /device:TPU:0 fusion.1\n"
        "2000000000 3000000000 /device:TPU:0 fusion.2\n"
        "9000000000 10000000000 /device:TPU:0 fusion.3\n")
    assert device_busy.load_window(str(trace)) == (101.0, 110.0, 110.0)
    planes = device_busy.load_intervals(str(trace))
    clipped, (w0, w1) = device_busy.clip_to_window(
        planes["/device:TPU:0"], (101.0, 110.0, 110.0),
        anchor_t1_ns=10_000_000_000)
    assert (w0, w1) == (1_000_000_000, 10_000_000_000)
    assert [(t0, t1) for t0, t1, _ in clipped] == [
        (2_000_000_000, 3_000_000_000),
        (9_000_000_000, 10_000_000_000)]
    assert device_busy.main([str(trace)]) == 0
    out = capsys.readouterr().out
    assert "measured window" in out
    # 2e9 busy / 9e9 window = 22.2%
    assert "(22.2% of window)" in out


def test_device_busy_no_window_header_is_fine(tmp_path, capsys):
    import device_busy

    trace = tmp_path / "xprof-ops.txt"
    trace.write_text("# t0_ns t1_ns plane op_name\n"
                     "0 100 /device:TPU:0 fusion.1\n")
    assert device_busy.load_window(str(trace)) is None
    assert device_busy.main([str(trace)]) == 0
    assert "measured window" not in capsys.readouterr().out


def test_device_busy_marker_window(tmp_path, capsys):
    """Marker-delimited window: busy is computed between the first
    marker's end and the last marker's start, markers excluded."""
    import device_busy

    trace = tmp_path / "xprof-ops.txt"
    trace.write_text(
        "# t0_ns t1_ns plane op_name\n"
        "# window_epoch 1.0 2.0 flush_epoch 2.0\n"  # marker wins over this
        "0 100 /device:TPU:0 jit_rnb_window_marker(1)\n"
        "500 600 /device:TPU:0 fusion.pre\n"        # before... no: inside
        "1000 3000 /device:TPU:0 fusion.in\n"
        "9000 9100 /device:TPU:0 jit_rnb_window_marker(2)\n"
        "9500 9900 /device:TPU:0 fusion.post\n")
    planes = device_busy.load_intervals(str(trace))
    assert device_busy.marker_window(planes["/device:TPU:0"]) == (100,
                                                                  9000)
    assert device_busy.main([str(trace)]) == 0
    out = capsys.readouterr().out
    # window [100, 9000): fusion.pre (100) + fusion.in (2000) busy of
    # 8900 -> 23.6%; fusion.post lies outside and is excluded
    assert "marker-delimited window (23.6%" in out


def test_device_busy_headerless_four_col_sniffed(tmp_path, capsys):
    """A 4-column file whose header line was stripped must still be
    parsed per-plane (sniffed from the first data row), not folded
    into '(all)' with the plane token glued onto the op name."""
    import device_busy

    trace = tmp_path / "xprof-ops.txt"
    trace.write_text("0 100 /device:TPU:0 fusion.1\n"
                     "50 150 /host:CPU cpu_thing\n")
    planes = device_busy.load_intervals(str(trace), device_only=False)
    assert set(planes) == {"/device:TPU:0", "/host:CPU"}
    assert planes["/device:TPU:0"] == [(0, 100, "fusion.1")]
    assert device_busy.main([str(trace)]) == 0
    capsys.readouterr()
    # a retained window_epoch comment must not defeat the sniff: the
    # format decision comes from the first DATA row
    trace.write_text("# window_epoch 100.0 102.0 flush_epoch 102.0\n"
                     "0 100 /device:TPU:0 fusion.1\n")
    planes = device_busy.load_intervals(str(trace), device_only=False)
    assert set(planes) == {"/device:TPU:0"}


def test_decode_bench_smoke(tmp_path):
    """scripts/decode_bench.py: decodes a tiny dataset tree with the
    native backend and reports a frame count matching every frame
    decoded exactly once (the micro-benchmark behind the frames/s
    rates quoted in MATRIX.md)."""
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


def test_device_busy_job_dir_reads_ledger_and_captures(tmp_path,
                                                       capsys):
    """Job-dir mode: the devobs ledger lines print first, every
    capture artifact is analyzed, and an idle capture is a report,
    not an error."""
    import device_busy

    job = tmp_path / "job"
    job.mkdir()
    (job / "log-meta.txt").write_text(
        "Args: Namespace()\n"
        "Compute: stages=1 dispatches=2 rows=3 flops_total=30 "
        "window_us=1000 tflops_milli=0 mfu_e4=-1 captures=1\n"
        "Memory: owners=1 devices=1 total_bytes=16 peak_bytes=16 "
        "watermark_bytes=0 watermark_hits=0 live_bytes=0 "
        "reconciled=0\n")
    (job / "devobs-capture-0.txt").write_text(
        "# t0_ns t1_ns plane op_name\n"
        "# window_epoch 0.0 1.0 flush_epoch 1.0\n"
        "# trigger window ops_total 1 ops_written 1\n"
        "100 200 /device:TPU:0 fusion.1\n")
    assert device_busy.main([str(job)]) == 0
    out = capsys.readouterr().out
    assert "Compute: stages=1" in out
    assert "Memory: owners=1" in out
    assert "devobs-capture-0.txt" in out
    # an idle (empty) capture must not fail the report
    (job / "devobs-capture-1.txt").write_text(
        "# t0_ns t1_ns plane op_name\n"
        "# trigger forced ops_total 0 ops_written 0\n")
    assert device_busy.main([str(job)]) == 0
    # a dir with neither ledger nor artifacts is an error
    empty = tmp_path / "empty"
    empty.mkdir()
    assert device_busy.main([str(empty)]) == 1
