"""End-to-end run of the real R(2+1)D stages (reduced geometry).

One bounded integration test: Poisson client -> R2P1DLoader (synthetic
decode, 2-frame clips) -> R2P1DRunner (1-block layers, 8 classes) ->
logs, on two virtual devices. Uses the shared jit/param caches, so cost
is one compile for the whole test session.
"""

import json
import os

import pytest

from rnb_tpu.benchmark import run_benchmark
from rnb_tpu.control import TerminationFlag


def test_r2p1d_whole_pipeline(tmp_path):
    cfg = {
        "video_path_iterator":
            "rnb_tpu.models.r2p1d.model.R2P1DVideoPathIterator",
        "pipeline": [
            {"model": "rnb_tpu.models.r2p1d.model.R2P1DLoader",
             "queue_groups": [{"devices": [0], "out_queues": [0]}],
             "num_shared_tensors": 8,
             "max_clips": 2, "consecutive_frames": 2,
             "num_clips_population": [1, 2], "weights": [3, 1],
             "num_warmups": 1},
            {"model": "rnb_tpu.models.r2p1d.model.R2P1DRunner",
             "queue_groups": [{"devices": [1], "in_queue": 0}],
             "start_index": 1, "end_index": 5,
             "num_classes": 8, "layer_sizes": [1, 1, 1, 1],
             "max_rows": 2, "consecutive_frames": 2, "num_warmups": 1},
        ],
    }
    path = os.path.join(str(tmp_path), "whole.json")
    with open(path, "w") as f:
        json.dump(cfg, f)
    res = run_benchmark(path, mean_interval_ms=0, num_videos=4,
                        queue_size=20, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    reports = [f for f in os.listdir(res.log_dir) if "group" in f]
    with open(os.path.join(res.log_dir, reports[0])) as f:
        lines = f.read().strip().split("\n")
    header = lines[0].split()
    # the loader's phase-refinement stamps are on every request, with
    # no `trace` key: the table has their three columns, in order
    assert header == ["enqueue_filename", "runner0_start",
                      "inference0_start", "decode0_done",
                      "transfer0_start", "transfer0_done",
                      "inference0_finish", "runner1_start",
                      "inference1_start", "inference1_finish",
                      "device0", "device1"]
    rows = [line.split() for line in lines[1:]
            if line and not line.startswith("#")]
    assert len(rows) >= 4
    # ... and attribute_phases partitions every row: the phases sum
    # to the request's latency, decode/hold/transfer among them
    from rnb_tpu.trace import attribute_phases
    for row in rows:
        stamps = {k: float(v) for k, v in zip(header[:10], row)}
        phases = attribute_phases(stamps)
        assert {"decode", "hold", "transfer", "inference1"} <= set(phases)
        latency = (stamps["inference1_finish"]
                   - stamps["enqueue_filename"]) * 1000.0
        assert sum(phases.values()) == pytest.approx(latency, abs=1e-6)
        assert all(ms >= 0.0 for ms in phases.values())
    # no Tracer, no profiler session: the spans left nothing behind
    assert "trace.json" not in os.listdir(res.log_dir)


def test_r2p1d_layer_split_pipeline(tmp_path):
    """Inter-layer partitioning end-to-end: loader -> conv1-4 -> conv5.

    The mid-pipeline feature-map hand-off the reference could never wire
    (its TODO #69: output shapes hardcoded to full-range logits); here
    the conv1-4 stage declares its exact shape via output_shape_for and
    the runtime sizes its ring from it.
    """
    tiny = {"num_classes": 8, "layer_sizes": [1, 1, 1, 1],
            "consecutive_frames": 2, "num_warmups": 1}
    cfg = {
        "video_path_iterator":
            "rnb_tpu.models.r2p1d.model.R2P1DVideoPathIterator",
        "pipeline": [
            {"model": "rnb_tpu.models.r2p1d.model.R2P1DLoader",
             "queue_groups": [{"devices": [0], "out_queues": [0]}],
             "num_shared_tensors": 8,
             "max_clips": 2, "consecutive_frames": 2,
             "num_clips_population": [1, 2], "weights": [3, 1],
             "num_warmups": 1},
            {"model": "rnb_tpu.models.r2p1d.model.R2P1DRunner",
             "queue_groups": [{"devices": [1], "in_queue": 0,
                               "out_queues": [0]}],
             "start_index": 1, "end_index": 4, "max_rows": 2, **tiny},
            {"model": "rnb_tpu.models.r2p1d.model.R2P1DRunner",
             "queue_groups": [{"devices": [2], "in_queue": 0}],
             "start_index": 5, "end_index": 5, "max_rows": 2, **tiny},
        ],
    }
    path = os.path.join(str(tmp_path), "split.json")
    with open(path, "w") as f:
        json.dump(cfg, f)
    res = run_benchmark(path, mean_interval_ms=0, num_videos=4,
                        queue_size=20, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    reports = [f for f in os.listdir(res.log_dir) if "group" in f]
    with open(os.path.join(res.log_dir, reports[0])) as f:
        header = f.readline().split()
    assert "inference2_finish" in header  # all three stages timed


def test_split_range_logits_match_whole_range(tmp_path):
    """conv1-4 -> conv5 staged inference must reproduce the whole-range
    logits when both load the same checkpoint (weight-sharing via
    explicit ckpt_path, checkpoint.load_or_init)."""
    import jax.numpy as jnp
    import numpy as np

    from rnb_tpu.models.r2p1d import checkpoint as ckpt
    from rnb_tpu.models.r2p1d.model import R2P1DRunner
    from rnb_tpu.stage import PaddedBatch
    from rnb_tpu.telemetry import TimeCard

    tiny = dict(num_classes=8, layer_sizes=(1, 1, 1, 1), max_rows=2,
                consecutive_frames=2, num_warmups=1)
    path = os.path.join(str(tmp_path), "tiny.msgpack")
    ckpt.save_checkpoint(path, ckpt.init_variables(
        seed=3, num_classes=8, layer_sizes=(1, 1, 1, 1)))

    import jax
    dev = jax.devices()[0]
    stage_a = R2P1DRunner(dev, start_index=1, end_index=4,
                          ckpt_path=path, **tiny)
    stage_b = R2P1DRunner(dev, start_index=5, end_index=5,
                          ckpt_path=path, **tiny)
    whole = R2P1DRunner(dev, start_index=1, end_index=5,
                        ckpt_path=path, **tiny)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 2, 112, 112, 3)),
                    jnp.bfloat16)
    pb = PaddedBatch(x, 2)
    (feat,), _, tc = stage_a((pb,), None, TimeCard(0))
    (split_logits,), _, tc = stage_b((feat,), None, tc)
    (whole_logits,), _, _ = whole((pb,), None, TimeCard(1))
    np.testing.assert_allclose(np.asarray(split_logits.data),
                               np.asarray(whole_logits.data),
                               rtol=0, atol=0.05)
    assert split_logits.valid == whole_logits.valid == 2


def test_fused_loader_keeps_filling_while_the_ring_is_full(tmp_path):
    """Emission under back-pressure, through the real runtime: a
    backlog of one-clip videos -> R2P1DFusingLoader (hold 0 ms, 8-row
    cap, a ring of 2) -> a stage that holds each dispatch until the
    loader has filled the ring behind it and closed one batch more
    (a condition, not a delay: decode speed under a loaded host does
    not decide the counts). Once
    the ring is full the expired hold no longer ships what happens to
    be decoded: the loader keeps filling, and every batch from the
    first deferral on, but the drain's last, is a whole bucket with no
    pad row. Counts only (the CPU harness)."""
    num_videos = 60    # some seven 8-row batches and a tail
    cfg = {
        "video_path_iterator":
            "rnb_tpu.models.r2p1d.model.R2P1DVideoPathIterator",
        "trace": {"enabled": True, "sample_hz": 0},
        "pipeline": [
            {"model": "rnb_tpu.models.r2p1d.model.R2P1DFusingLoader",
             "queue_groups": [{"devices": [0], "out_queues": [0]}],
             "num_shared_tensors": 2,
             "fuse": 64, "max_clips": 8, "row_buckets": [4, 8],
             "max_hold_ms": 0.0, "consecutive_frames": 2,
             "num_clips_population": [1], "weights": [1],
             "num_warmups": 1},
            {"model": "tests.pipeline_helpers.BackpressureSink",
             "queue_groups": [{"devices": [1], "in_queue": 0}],
             "ahead": 3, "total_rows": num_videos},
        ],
    }
    path = os.path.join(str(tmp_path), "backpressure.json")
    with open(path, "w") as f:
        json.dump(cfg, f)
    res = run_benchmark(path, mean_interval_ms=0, num_videos=num_videos,
                        queue_size=100, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    assert res.clips_completed == num_videos   # every request, one clip
    assert res.num_failed == 0 and res.num_shed == 0

    with open(os.path.join(res.log_dir, "trace.json")) as f:
        events = sorted(json.load(f)["traceEvents"],
                        key=lambda e: e.get("ts", 0.0))
    emits = [e for e in events if e["name"] == "loader.emit"]
    deferred = [e["ts"] for e in events
                if e["name"] == "loader.emit_deferred"]
    # the span's stats foot to the whole-run Padding: counters
    assert sum(e["args"]["rows"] for e in emits) == num_videos
    assert sum(e["args"]["bucket"] for e in emits) == res.total_rows
    assert res.pad_rows == res.total_rows - num_videos
    with open(os.path.join(res.log_dir, "log-meta.txt")) as f:
        assert "Padding: pad_rows=%d total_rows=%d" % (
            res.pad_rows, res.total_rows) in f.read()
    # the mechanism engaged, and from then on no latency rule shipped
    # a part-filled bucket into the full ring: every batch but the
    # drain's last is a whole bucket, no pad row (a full batch under
    # back-pressure, or the drain closing on a boundary)
    assert deferred
    held = [e["args"] for e in emits if e["ts"] > deferred[0]]
    assert len(held) >= 5
    assert {a["reason"] for a in held} <= {"full", "drain"}
    assert [a["bucket"] - a["rows"] for a in held[:-1]] \
        == [0] * (len(held) - 1)
    assert any(a["reason"] == "full" for a in held)
    # at most one deferral a batch
    assert len(deferred) <= len(emits)

    # the phases still partition every request's latency
    from rnb_tpu.trace import attribute_phases
    reports = [f for f in os.listdir(res.log_dir) if "group" in f]
    with open(os.path.join(res.log_dir, reports[0])) as f:
        lines = f.read().strip().split("\n")
    header = lines[0].split()
    stamps_n = header.index("device0")
    rows = [line.split() for line in lines[1:]
            if line and not line.startswith("#")]
    assert rows
    for row in rows:
        stamps = {k: float(v) for k, v in zip(header[:stamps_n], row)}
        phases = attribute_phases(stamps)
        assert {"decode", "hold", "transfer"} <= set(phases)
        latency = (stamps[header[stamps_n - 1]]
                   - stamps["enqueue_filename"]) * 1000.0
        assert sum(phases.values()) == pytest.approx(latency, abs=1e-6)
        assert all(ms >= 0.0 for ms in phases.values())
