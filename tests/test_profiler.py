"""Profiler bridge smoke test — the test_cupti.py equivalent.

Reference behavior (test_cupti.py:1-21 + README.md:194-212): run one
small op under the bridge, expect kernel records with plausible
timestamps from ``report()``.  Here: a jitted matmul under
initialize/flush/report; both the native parser and the pure-Python
fallback must see the same events.
"""

import os
import subprocess

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from rnb_tpu import profiler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    trace_dir = str(tmp_path_factory.mktemp("xprof"))
    profiler.initialize(trace_dir)
    x = jnp.ones((128, 128), jnp.float32)
    jax.jit(lambda a: a @ a)(x).block_until_ready()
    profiler.flush()
    return trace_dir


def test_report_returns_intervals(captured):
    events = profiler.report(keep_trace=True)
    assert events, "no events captured"
    names = [n for n, _, _ in events]
    assert any(n for n in names), names
    for name, t0, t1 in events:
        assert isinstance(name, str)
        assert t1 >= t0 >= 0


def test_report_include_plane(captured):
    """include_plane=True appends the owning plane to every tuple and
    matches the 3-tuple form element-for-element (same parse, plane
    stripped vs kept)."""
    with_plane = profiler.report(keep_trace=True, include_plane=True)
    bare = profiler.report(keep_trace=True)
    assert with_plane and bare
    assert [(n, t0, t1) for n, t0, t1, _p in with_plane] == bare
    planes = {p for _n, _t0, _t1, p in with_plane}
    assert all(isinstance(p, str) and p for p in planes), planes


def test_native_and_python_parsers_agree(captured):
    files = profiler._xplane_files()
    assert files, "no xplane.pb produced"
    lib = profiler._xplane_lib()
    if lib is None:
        try:
            subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                           check=True, capture_output=True)
        except (OSError, subprocess.CalledProcessError):
            pytest.skip("native toolchain unavailable")
        lib = profiler._xplane_lib()
        if lib is None:
            pytest.skip("native xplane library failed to load")
    for path in files:
        native = profiler._parse_native(lib, path, "")
        python = profiler._parse_python(path, "")
        assert native == python
        assert len(native) > 0


def test_python_parser_tolerates_truncated_file(tmp_path, captured):
    files = profiler._xplane_files()
    src = files[0]
    trunc = tmp_path / "trunc.xplane.pb"
    data = open(src, "rb").read()
    trunc.write_bytes(data[:len(data) // 3])
    # must not raise; partial (possibly empty) results are fine
    events = profiler._parse_python(str(trunc), "")
    assert isinstance(events, list)
    lib = profiler._xplane_lib()
    if lib is not None:
        assert isinstance(profiler._parse_native(lib, str(trunc), ""),
                          list)


def test_report_keeps_caller_supplied_dir(tmp_path):
    d = tmp_path / "run1"
    d.mkdir()
    (d / "precious.txt").write_text("keep me")
    profiler.initialize(str(d))
    import jax.numpy as jnp
    jnp.zeros((8,)).block_until_ready()
    profiler.flush()
    profiler.report()
    assert (d / "precious.txt").exists()


def test_double_initialize_rejected(tmp_path):
    profiler.initialize(str(tmp_path / "t"))
    try:
        with pytest.raises(RuntimeError):
            profiler.initialize(str(tmp_path / "t2"))
    finally:
        profiler.flush()
        profiler.report()  # drain


def test_report_drains_trace(tmp_path):
    profiler.initialize(str(tmp_path / "t"))
    jnp.zeros((8,)).block_until_ready()
    profiler.flush()
    first = profiler.report()
    assert profiler.report() == []
    assert isinstance(first, list)


def test_benchmark_xprof_end_to_end(tmp_path):
    """run_benchmark(xprof=True) through the real runtime on the CPU
    backend: xprof-ops.txt carries the 4-column header, the epoch
    window line, and at least two window-marker events."""
    import json

    import numpy as np

    from rnb_tpu.benchmark import run_benchmark
    from rnb_tpu.control import TerminationFlag
    from rnb_tpu.decode import write_y4m
    from rnb_tpu.models.r2p1d import checkpoint as ckpt

    root = os.path.join(str(tmp_path), "data")
    os.makedirs(os.path.join(root, "label0"))
    rng = np.random.default_rng(0)
    for i in range(3):
        write_y4m(os.path.join(root, "label0", "v%d.y4m" % i),
                  rng.integers(0, 256, (30, 64, 64, 3), dtype=np.uint8))
    os.environ["RNB_TPU_DATA_ROOT"] = root
    try:
        ckpt_path = os.path.join(str(tmp_path), "tiny.msgpack")
        ckpt.save_checkpoint(ckpt_path, ckpt.init_variables(
            seed=1, num_classes=8, layer_sizes=(1, 1, 1, 1)))
        cfg = {
            "video_path_iterator":
                "rnb_tpu.models.r2p1d.model.R2P1DVideoPathIterator",
            "pipeline": [
                {"model":
                    "rnb_tpu.models.r2p1d.model.R2P1DFusingLoader",
                 "queue_groups": [{"devices": [0], "out_queues": [0]}],
                 "num_shared_tensors": 10,
                 "fuse": 2, "max_clips": 4,
                 "num_clips_population": [2], "weights": [1],
                 "consecutive_frames": 2, "num_warmups": 0,
                 "pixel_path": "yuv420"},
                {"model": "rnb_tpu.models.r2p1d.model.R2P1DRunner",
                 "queue_groups": [{"devices": [0], "in_queue": 0}],
                 "start_index": 1, "end_index": 5, "num_classes": 8,
                 "layer_sizes": [1, 1, 1, 1], "max_rows": 4,
                 "consecutive_frames": 2, "num_warmups": 0,
                 "ckpt_path": ckpt_path, "pixel_path": "yuv420"},
            ],
        }
        cfg_path = os.path.join(str(tmp_path), "fused.json")
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
        log_base = os.path.join(str(tmp_path), "logs")
        res = run_benchmark(cfg_path, mean_interval_ms=0, num_videos=6,
                            log_base=log_base, print_progress=False,
                            xprof=True)
        assert res.termination_flag == \
            TerminationFlag.TARGET_NUM_VIDEOS_REACHED
        job = os.listdir(log_base)[0]
        trace = os.path.join(log_base, job, "xprof-ops.txt")
        with open(trace) as f:
            head = [f.readline(), f.readline()]
        assert head[0].startswith("# t0_ns t1_ns plane op_name")
        assert "window_epoch" in head[1] and "flush_epoch" in head[1]
        with open(trace) as f:
            n_markers = sum("rnb_window_marker" in line for line in f)
        assert n_markers >= 2, n_markers
    finally:
        os.environ.pop("RNB_TPU_DATA_ROOT", None)
