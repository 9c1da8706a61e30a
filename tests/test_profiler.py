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
