"""End-to-end pipeline runs on the 8-virtual-device CPU backend.

Integration coverage the reference never had (SURVEY.md §4): full
client -> stages -> logs jobs, replication, segmentation + aggregation,
overflow abort semantics, and crash containment.
"""

import json
import os

import pytest

from rnb_tpu.benchmark import run_benchmark
from rnb_tpu.control import TerminationFlag


def _write_config(tmp_path, cfg, name="pipeline.json"):
    path = os.path.join(str(tmp_path), name)
    with open(path, "w") as f:
        json.dump(cfg, f)
    return path


def _two_step(devices_a=(0,), devices_b=(1,)):
    return {
        "video_path_iterator": "tests.pipeline_helpers.CountingPathIterator",
        "pipeline": [
            {"model": "tests.pipeline_helpers.TinyLoader",
             "queue_groups": [
                 {"devices": list(devices_a), "out_queues": [0]}],
             "num_shared_tensors": 4},
            {"model": "tests.pipeline_helpers.TinySink",
             "queue_groups": [{"devices": list(devices_b), "in_queue": 0}]},
        ],
    }


def test_bulk_end_to_end(tmp_path):
    cfg = _write_config(tmp_path, _two_step())
    res = run_benchmark(cfg, mean_interval_ms=0, num_videos=25,
                        queue_size=50, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    assert res.throughput_vps > 0
    # log artifacts: meta, config copy, one report per final instance
    files = os.listdir(res.log_dir)
    assert "log-meta.txt" in files
    assert "pipeline.json" in files
    reports = [f for f in files if "group" in f]
    assert len(reports) == 1
    # 25 videos > NUM_SUMMARY_SKIPS: latency percentiles must be present
    assert res.p50_latency_ms is not None
    assert res.p99_latency_ms >= res.p50_latency_ms > 0
    with open(os.path.join(res.log_dir, reports[0])) as f:
        lines = f.read().strip().split("\n")
    header = lines[0].split()
    assert header == ["enqueue_filename", "runner0_start",
                      "inference0_start", "inference0_finish",
                      "runner1_start", "inference1_start",
                      "inference1_finish", "device0", "device1"]
    # >= target rows recorded (some extra in-flight items may complete)
    assert len(lines) - 1 >= 25
    # timestamps monotonically increase along each row's event sequence
    row = list(map(float, lines[1].split()[:7]))
    assert row == sorted(row)
    with open(os.path.join(res.log_dir, "log-meta.txt")) as f:
        meta = f.read()
    assert "Termination flag: 0" in meta


def test_cpu_accounting_and_no_span_artifacts(tmp_path):
    """The rusage window (always on) lands in the result, and a run
    with no `trace` key and no profiler session leaves no artifact of
    the served window's spans behind (they are profiler annotations
    only): set-up's record, kept up to the start barrier, is the one
    span file (PR 51)."""
    cfg = _write_config(tmp_path, _two_step())
    # enough videos that the measured window exceeds the kernel's
    # CPU-time accounting granularity: with every jit cache warm from
    # earlier suite files, 25 videos complete in a few ms and rusage
    # can legitimately report a 0.0 delta
    res = run_benchmark(cfg, mean_interval_ms=0, num_videos=300,
                        queue_size=400, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    assert res.host_cpu_s > 0
    files = os.listdir(res.log_dir)
    assert sorted(f for f in files if "group" not in f) \
        == ["log-meta.txt", "pipeline.json", "setup-trace.json"]
    with open(os.path.join(res.log_dir, "setup-trace.json")) as f:
        assert {e["name"].split(".")[0] for e in json.load(f)["traceEvents"]
                if e["ph"] != "M"} == {"setup"}
    assert res.trace_events == 0
    # the tiny pipeline decodes nothing through the native pool
    assert res.decode_busy_s == 0 and res.decode_frames == 0

def test_poisson_end_to_end_replicated(tmp_path):
    cfg = _write_config(tmp_path, _two_step(devices_a=(0, 1),
                                            devices_b=(2, 3)))
    res = run_benchmark(cfg, mean_interval_ms=1, num_videos=20,
                        queue_size=100, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    reports = [f for f in os.listdir(res.log_dir) if "group" in f]
    assert len(reports) == 2  # one per final-step instance


def test_three_step_pipeline_values_flow(tmp_path):
    cfg = {
        "video_path_iterator": "tests.pipeline_helpers.CountingPathIterator",
        "pipeline": [
            {"model": "tests.pipeline_helpers.TinyLoader",
             "queue_groups": [{"devices": [0], "out_queues": [0]}]},
            {"model": "tests.pipeline_helpers.TinyDouble",
             "queue_groups": [{"devices": [1, 2], "in_queue": 0,
                               "out_queues": [1]}]},
            {"model": "tests.pipeline_helpers.TinySink",
             "queue_groups": [{"devices": [-1], "in_queue": 1}]},
        ],
    }
    path = _write_config(tmp_path, cfg)
    res = run_benchmark(path, mean_interval_ms=0, num_videos=10,
                        queue_size=50, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == TerminationFlag.TARGET_NUM_VIDEOS_REACHED


def test_segmentation_with_aggregation(tmp_path):
    cfg = {
        "video_path_iterator": "tests.pipeline_helpers.CountingPathIterator",
        "pipeline": [
            {"model": "tests.pipeline_helpers.TinyLoader",
             "queue_groups": [{"devices": [0], "out_queues": [0]}],
             "num_segments": 2, "num_shared_tensors": 8,
             "rows_per_video": 4},
            {"model": "tests.pipeline_helpers.TinyDouble",
             "queue_groups": [{"devices": [1, 2, 3], "in_queue": 0,
                               "out_queues": [1]}]},
            {"model": "rnb_tpu.models.r2p1d.model.R2P1DAggregator",
             "queue_groups": [{"devices": [-1], "in_queue": 1}],
             "aggregate": 2},
        ],
    }
    path = _write_config(tmp_path, cfg)
    res = run_benchmark(path, mean_interval_ms=0, num_videos=12,
                        queue_size=100, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    # merged TimeCards: post-fork events appear per segment in the report
    reports = [f for f in os.listdir(res.log_dir) if "group" in f]
    with open(os.path.join(res.log_dir, reports[0])) as f:
        header = f.readline().split()
    assert "runner1_start-0" in header
    assert "runner1_start-1" in header
    assert "inference2_finish" in header  # post-merge event, unsuffixed


def test_exit_markers_never_overtake_items(tmp_path):
    """Regression: with competing replicas feeding one queue, a fast
    replica's end-of-stream markers must not starve the consumer of a
    slower sibling's in-flight items. Only the LAST producer on an edge
    may enqueue markers (EdgeTracker), so every run completes all
    videos."""
    cfg = {
        "video_path_iterator": "tests.pipeline_helpers.CountingPathIterator",
        "pipeline": [
            {"model": "tests.pipeline_helpers.TinyLoader",
             "queue_groups": [{"devices": [0], "out_queues": [0]}],
             "num_segments": 2, "num_shared_tensors": 8},
            {"model": "tests.pipeline_helpers.TinyDouble",
             "queue_groups": [{"devices": [1, 2, 3, 4], "in_queue": 0,
                               "out_queues": [1]}]},
            {"model": "rnb_tpu.models.r2p1d.model.R2P1DAggregator",
             "queue_groups": [{"devices": [-1], "in_queue": 1}],
             "aggregate": 2},
        ],
    }
    path = _write_config(tmp_path, cfg)
    for trial in range(5):
        res = run_benchmark(path, mean_interval_ms=0, num_videos=40,
                            queue_size=500,
                            log_base=str(tmp_path / ("logs%d" % trial)),
                            print_progress=False)
        assert res.termination_flag == \
            TerminationFlag.TARGET_NUM_VIDEOS_REACHED, \
            "trial %d lost items (flag=%s)" % (trial, res.termination_flag)


def test_filename_queue_overflow_aborts(tmp_path):
    cfg = {
        "video_path_iterator": "tests.pipeline_helpers.CountingPathIterator",
        "pipeline": [
            {"model": "tests.pipeline_helpers.TinySlowSink",
             "queue_groups": [{"devices": [-1]}], "delay_s": 0.3},
        ],
    }
    path = _write_config(tmp_path, cfg)
    res = run_benchmark(path, mean_interval_ms=1, num_videos=1000,
                        queue_size=2, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == TerminationFlag.FILENAME_QUEUE_FULL
    with open(os.path.join(res.log_dir, "log-meta.txt")) as f:
        assert "Termination flag: 1" in f.read()


def test_broken_stage_class_fails_fast(tmp_path):
    cfg = {
        "video_path_iterator": "tests.pipeline_helpers.CountingPathIterator",
        "pipeline": [
            {"model": "tests.pipeline_helpers.DoesNotExist",
             "queue_groups": [{"devices": [0]}]},
        ],
    }
    path = _write_config(tmp_path, cfg)
    res = run_benchmark(path, mean_interval_ms=1, num_videos=10,
                        queue_size=10, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == TerminationFlag.INTERNAL_ERROR


def test_target_race_registers_inflight_record(tmp_path):
    """A completion counted after a sibling already hit the target must
    still land in the timing table (reference runner.py:176-202
    registered every completed record; round-3 verdict weak#6)."""
    import queue
    import threading

    from rnb_tpu.control import InferenceCounter, TerminationState
    from rnb_tpu.devices import DeviceSpec
    from rnb_tpu.runner import RunnerContext, runner
    from rnb_tpu.telemetry import TimeCard

    num_videos = 5
    counter = InferenceCounter()
    counter.add(num_videos)  # a sibling instance already hit the target

    tc = TimeCard(99)
    tc.record("enqueue_filename")
    in_queue = queue.Queue()
    in_queue.put((None, "video-99", tc))

    sink: list = []
    ctx = RunnerContext(
        in_queue=in_queue,
        out_queues=None,
        queue_selector_path="rnb_tpu.selector.RoundRobinSelector",
        print_progress=False,
        job_id="race-test",
        device=DeviceSpec(-1),
        group_idx=0,
        instance_idx=0,
        counter=counter,
        num_videos=num_videos,
        termination=TerminationState(),
        step_idx=0,
        sta_bar=threading.Barrier(1),
        fin_bar=threading.Barrier(1),
        model_class_path="tests.pipeline_helpers.TinySink",
        num_segments=1,
        input_rings=None,
        output_ring=None,
        log_base=str(tmp_path / "logs"),
        summary_sink=sink,
    )
    runner(ctx)
    assert counter.value == num_videos + 1
    assert len(sink) == 1
    # the in-flight record was registered despite the sibling's target
    assert len(sink[0].latencies_ms(num_skips=0)) == 1
