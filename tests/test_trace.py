"""Unified pipeline tracing (rnb_tpu.trace): spans/counters/export,
deterministic phase attribution, trace-off byte-stability.

Unit coverage runs without JAX; the e2e cases drive the tiny test
pipeline (tests.pipeline_helpers) through run_benchmark with the root
``trace`` config key on and off.
"""

import json
import os
import threading

import pytest

from rnb_tpu import trace
from rnb_tpu.trace import (TraceSettings, Tracer, attribute_phases,
                           phase_of, phase_stats, sorted_phases,
                           track_names, validate_trace)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_active_tracer():
    """Unit tests must never leak a module-global tracer into later
    tests (benchmark.py owns install/clear in real runs)."""
    trace.ACTIVE = None
    yield
    trace.ACTIVE = None


# -- settings / config validation -------------------------------------

def test_settings_from_config():
    assert TraceSettings.from_config(None) is None
    assert TraceSettings.from_config({"enabled": False}) is None
    s = TraceSettings.from_config({})
    assert s is not None and s.sample_hz == trace.DEFAULT_SAMPLE_HZ \
        and s.max_events == trace.DEFAULT_MAX_EVENTS
    s = TraceSettings.from_config({"sample_hz": 0, "max_events": 7})
    assert s.sample_hz == 0.0 and s.max_events == 7


def _cfg(trace_value):
    return {
        "video_path_iterator":
            "tests.pipeline_helpers.CountingPathIterator",
        "trace": trace_value,
        "pipeline": [
            {"model": "tests.pipeline_helpers.TinyLoader",
             "queue_groups": [{"devices": [0], "out_queues": [0]}],
             "num_shared_tensors": 4},
            {"model": "tests.pipeline_helpers.TinySink",
             "queue_groups": [{"devices": [1], "in_queue": 0}]},
        ],
    }


def test_config_accepts_valid_trace_key():
    from rnb_tpu.config import parse_config
    cfg = parse_config(_cfg({"enabled": True, "sample_hz": 5,
                             "max_events": 1000}))
    assert cfg.trace == {"enabled": True, "sample_hz": 5,
                        "max_events": 1000}


@pytest.mark.parametrize("bad", [
    "yes",                          # not an object
    {"enable": True},               # unknown key
    {"enabled": 1},                 # non-bool enabled
    {"sample_hz": -1},              # negative rate
    {"sample_hz": True},            # bool masquerading as number
    {"max_events": 0},              # cap must be positive
    {"max_events": 2.5},            # cap must be an int
])
def test_config_rejects_bad_trace_key(bad):
    from rnb_tpu.config import ConfigError, parse_config
    with pytest.raises(ConfigError):
        parse_config(_cfg(bad))


# -- collector + export -----------------------------------------------

def test_without_tracer_or_session_a_span_is_the_shared_noop():
    # no Tracer installed and no profiler session: span hands back the
    # one shared no-op (nothing allocated, no annotation opened), the
    # instant/counter hooks return, and nothing is retained anywhere
    from jax.profiler import TraceAnnotation
    assert not TraceAnnotation.is_enabled()
    probe = Tracer(TraceSettings(sample_hz=0))  # never installed
    with trace.span("exec0.queue_get"):
        pass
    span = trace.span("exec1.model_call", rid=3, rows=8, rows_valid=5)
    assert span is trace._NULL
    with span:
        pass
    trace.instant("client.enqueue", rid=1)
    trace.instant("compile.steady", signature="8x4:uint8")
    trace.counter("client.enqueued", 1)
    assert trace.ACTIVE is None and probe.num_events() == 0


def test_tracer_export_valid_and_flow_linked(tmp_path):
    tracer = Tracer(TraceSettings(sample_hz=0))
    trace.ACTIVE = tracer
    with trace.span("exec0.model_call", rid=7):
        pass
    trace.instant("client.enqueue", rid=7)
    trace.instant("client.enqueue", rid=8)  # single-event rid: no flow
    trace.counter("client.enqueued", 2)

    def other_thread():
        with trace.span("exec1.model_call", rid=7):
            pass

    t = threading.Thread(target=other_thread, name="runner-s1-g0-i0")
    t.start()
    t.join()
    path = str(tmp_path / "trace.json")
    written = tracer.export(path, "job-x")
    assert written == tracer.num_events() == 5
    assert validate_trace(path) == []
    with open(path) as f:
        doc = json.load(f)
    assert doc["otherData"]["num_events"] == 5
    assert doc["otherData"]["dropped_events"] == 0
    # rid 7 has 3 correlated events across 2 threads -> one flow chain
    assert doc["otherData"]["num_flows"] == 1
    flows = [ev for ev in doc["traceEvents"] if ev.get("cat") == "request"]
    assert [ev["ph"] for ev in flows] == ["s", "t", "f"]
    assert {ev["id"] for ev in flows} == {7}
    # one named track per thread role
    assert "runner-s1-g0-i0" in track_names(path)
    # every non-meta event carries ts/tid/ph; spans carry dur
    for ev in doc["traceEvents"]:
        for key in ("ph", "ts", "tid", "pid"):
            assert key in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0


def test_max_events_cap_counts_drops(tmp_path):
    tracer = Tracer(TraceSettings(max_events=3, sample_hz=0))
    trace.ACTIVE = tracer
    for i in range(10):
        trace.instant("client.enqueue", rid=i)
    assert tracer.num_events() == 3
    assert tracer.dropped == 7
    path = str(tmp_path / "trace.json")
    tracer.export(path, "job-cap")
    with open(path) as f:
        doc = json.load(f)
    assert doc["otherData"]["dropped_events"] == 7


def test_sampler_polls_counter_sources(tmp_path):
    tracer = Tracer(TraceSettings(sample_hz=200))
    tracer.add_counter_source("queue.e0.depth", lambda: 3)
    tracer.add_counter_source("queue.e1.depth",
                              lambda: (_ for _ in ()).throw(
                                  RuntimeError("dying probe")))
    tracer.start_sampler()
    import time
    deadline = time.monotonic() + 2.0
    while tracer.num_events() < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    tracer.stop_sampler()
    assert tracer.num_events() >= 2  # dying probe killed neither loop
    path = str(tmp_path / "trace.json")
    tracer.export(path, "job-s")
    with open(path) as f:
        doc = json.load(f)
    counters = [ev for ev in doc["traceEvents"]
                if ev.get("ph") == "C"]
    assert counters and all(ev["name"] == "queue.e0.depth"
                            and ev["args"]["value"] == 3
                            for ev in counters)


def test_validate_trace_reports_structural_problems(tmp_path):
    path = str(tmp_path / "bad.json")
    with open(path, "w") as f:
        json.dump({"traceEvents": [
            {"name": "x", "ph": "X", "pid": 1, "tid": 1, "ts": 0},
            {"name": "request", "ph": "s", "id": 4, "pid": 1,
             "tid": 1, "ts": 0},
        ]}, f)
    problems = validate_trace(path)
    assert any("dur" in p for p in problems)
    assert any("flow id 4" in p for p in problems)
    assert validate_trace(str(tmp_path / "missing.json"))


# -- deterministic phase attribution ----------------------------------

def test_phase_of_classification():
    assert phase_of("enqueue_filename", "runner0_start") == "client_queue"
    assert phase_of("runner0_start", "inference0_start") == "client_queue"
    assert phase_of("inference0_start", "decode0_done") == "decode"
    assert phase_of("decode0_done", "transfer0_start") == "hold"
    assert phase_of("transfer0_start", "transfer0_done") == "transfer"
    assert phase_of("transfer0_done", "inference0_finish") == "drain"
    assert phase_of("inference0_finish", "runner1_start") \
        == "inter_stage_queue"
    assert phase_of("runner1_start", "inference1_start") \
        == "inter_stage_queue"
    assert phase_of("inference1_start", "inference1_finish") \
        == "inference1"
    # un-refined past logs: the whole loader span reports as decode
    assert phase_of("inference0_start", "inference0_finish") == "decode"
    # merged segment cards: the -{sub_id} suffix is ignored
    assert phase_of("inference1_start-0", "inference1_finish-0") \
        == "inference1"


def test_attribute_phases_partitions_end_to_end():
    t0 = 1000.0
    timings = {
        "enqueue_filename": t0,
        "runner0_start": t0 + 0.010,
        "inference0_start": t0 + 0.011,
        "decode0_done": t0 + 0.020,
        "transfer0_start": t0 + 0.024,
        "transfer0_done": t0 + 0.030,
        "inference0_finish": t0 + 0.031,
        "runner1_start": t0 + 0.033,
        "inference1_start": t0 + 0.034,
        "inference1_finish": t0 + 0.040,
    }
    phases = attribute_phases(timings)
    assert phases["decode"] == pytest.approx(9.0, abs=1e-6)
    assert phases["hold"] == pytest.approx(4.0, abs=1e-6)
    assert phases["transfer"] == pytest.approx(6.0, abs=1e-6)
    assert phases["drain"] == pytest.approx(1.0, abs=1e-6)
    assert phases["inference1"] == pytest.approx(6.0, abs=1e-6)
    assert sum(phases.values()) == pytest.approx(40.0, abs=1e-6)
    # deterministic: same stamps -> same decomposition, dict order
    # irrelevant (attribution sorts by time)
    shuffled = dict(reversed(list(timings.items())))
    assert attribute_phases(shuffled) == phases


def test_attribute_phases_drops_nans_and_handles_tiny_cards():
    assert attribute_phases({}) == {}
    assert attribute_phases({"enqueue_filename": 1.0}) == {}
    phases = attribute_phases({"enqueue_filename": 1.0,
                               "runner0_start": float("nan"),
                               "inference0_finish": 1.5})
    assert phases == {"decode": pytest.approx(500.0)}


def test_phase_stats_and_sort_order():
    stats = phase_stats({"inference1": [2.0, 4.0], "decode": [1.0],
                         "client_queue": [0.5], "empty": []})
    assert "empty" not in stats
    assert stats["inference1"]["mean_ms"] == pytest.approx(3.0)
    assert stats["inference1"]["count"] == 2
    assert sorted_phases(stats) == ["client_queue", "decode",
                                    "inference1"]


def test_record_clamped_keeps_cards_time_ordered():
    from rnb_tpu.models.r2p1d.model import _record_clamped
    from rnb_tpu.telemetry import TimeCard
    tc = TimeCard(1)
    tc.record("inference0_start", at=100.0)
    _record_clamped(tc, "decode0_done", 99.0)  # earlier: clamps to 100
    _record_clamped(tc, "transfer0_start", 100.5)
    assert tc.timings["decode0_done"] == 100.0
    assert tc.timings["transfer0_start"] == 100.5
    assert attribute_phases(tc.timings)["decode"] == 0.0


# -- e2e: traced and un-traced tiny pipeline runs ----------------------

def _run(tmp_path, name, trace_value, videos=30, interval_ms=1):
    from rnb_tpu.benchmark import run_benchmark
    cfg = _cfg(trace_value)
    if trace_value is None:
        del cfg["trace"]
    path = os.path.join(str(tmp_path), "%s.json" % name)
    with open(path, "w") as f:
        json.dump(cfg, f)
    return run_benchmark(path, mean_interval_ms=interval_ms,
                         num_videos=videos, queue_size=50,
                         log_base=os.path.join(str(tmp_path),
                                               "logs-%s" % name),
                         print_progress=False)


def test_traced_run_end_to_end(tmp_path):
    res = _run(tmp_path, "traced",
               {"enabled": True, "sample_hz": 200, "max_events": 50000})
    assert res.termination_flag == 0
    assert res.trace_events > 0 and res.trace_dropped == 0
    # the tracer is cleared after export: nothing leaks into later runs
    assert trace.ACTIVE is None

    trace_path = os.path.join(res.log_dir, "trace.json")
    assert os.path.isfile(trace_path)
    assert validate_trace(trace_path) == []
    # distinct thread-role tracks: client + one executor per stage
    tracks = set(track_names(trace_path))
    assert {"client", "runner-s0-g0-i0", "runner-s1-g0-i0"} <= tracks
    with open(trace_path) as f:
        doc = json.load(f)
    names = {ev.get("name") for ev in doc["traceEvents"]}
    # deterministic event vocabulary for this topology
    assert {"client.enqueue", "client.enqueued", "exec0.model_call",
            "exec1.model_call", "exec0.publish"} <= names
    # sampled counter tracks (inter-stage queue + client queue): the
    # 1 ms Poisson client keeps the run alive >= a few sampler ticks
    assert {"queue.filename.depth", "queue.e0.depth"} <= names
    # flow-linked request chains across stages
    assert any(ev.get("ph") == "s" and ev.get("cat") == "request"
               for ev in doc["traceEvents"])

    # per-request attribution surfaced everywhere
    assert res.phases and "client_queue" in res.phases
    total = sum(s["mean_ms"] for s in res.phases.values())
    with open(os.path.join(res.log_dir, "log-meta.txt")) as f:
        meta_text = f.read()
    assert "Trace: events=%d dropped=0\n" % res.trace_events in meta_text
    assert "Phases: " in meta_text
    tables = [n for n in os.listdir(res.log_dir) if "group" in n]
    assert tables
    with open(os.path.join(res.log_dir, tables[0])) as f:
        report = f.read()
    assert "# phases n=" in report

    # offline tooling agrees with the online summaries
    import sys
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import parse_utils
        assert parse_utils.check_job(res.log_dir) == []
        stats = parse_utils.attribute_job(res.log_dir)
        assert set(stats) == set(res.phases)
        for phase in stats:
            assert stats[phase]["mean_ms"] == pytest.approx(
                res.phases[phase]["mean_ms"], abs=1e-6)
        # mean phase components sum to the mean end-to-end latency
        assert total == pytest.approx(
            sum(s["mean_ms"] for s in stats.values()), abs=1e-6)
        assert parse_utils.print_attribution(
            res.log_dir, out=open(os.devnull, "w")) == 0
    finally:
        sys.path.remove(os.path.join(REPO, "scripts"))


def test_untraced_run_stays_byte_stable(tmp_path):
    res = _run(tmp_path, "plain", None)
    assert res.termination_flag == 0
    assert res.trace_events == 0 and res.trace_dropped == 0
    assert res.phases == {}
    assert not os.path.isfile(os.path.join(res.log_dir, "trace.json"))
    with open(os.path.join(res.log_dir, "log-meta.txt")) as f:
        meta_text = f.read()
    assert "Trace:" not in meta_text and "Phases:" not in meta_text
    tables = [n for n in os.listdir(res.log_dir) if "group" in n]
    with open(os.path.join(res.log_dir, tables[0])) as f:
        report = f.read()
    assert "# phases" not in report
    # the stamp schema is exactly the pre-trace set: no refinement
    # columns leak into untraced tables
    header = report.split("\n", 1)[0].split()
    assert header == ["enqueue_filename", "runner0_start",
                      "inference0_start", "inference0_finish",
                      "runner1_start", "inference1_start",
                      "inference1_finish", "device0", "device1"]


def test_trace_overhead_is_bounded(tmp_path):
    # guard, not a benchmark: a traced bulk run of the tiny pipeline
    # must complete promptly (the disabled path is separately pinned
    # to a single None test by rnb-lint's hot-path discipline)
    import time
    t0 = time.monotonic()
    res = _run(tmp_path, "overhead",
               {"enabled": True, "sample_hz": 20}, videos=50,
               interval_ms=0)
    assert res.termination_flag == 0
    assert time.monotonic() - t0 < 60.0


# -- the profiler's host plane (PR 24) ---------------------------------

def _host_events(trace_dir):
    import glob

    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    found = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                found += [(e.name, e.duration_ns, dict(e.stats))
                          for e in line.events
                          if e.name.split(".")[0] in ("exec1", "compile")]
    return found


def test_worker_thread_span_and_instant_land_in_the_xplane(tmp_path):
    import jax

    def work():
        with trace.span("exec1.model_call", rid=7, rows=48,
                        rows_valid=41, device=0):
            pass
        trace.instant("compile.steady", signature="8x2:uint8")

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # as benchmarks/run.py traces
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        t = threading.Thread(target=work, name="runner-s1-g0-i0")
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    finally:
        jax.profiler.stop_trace()
    events = {name: (dur, stats)
              for name, dur, stats in _host_events(str(tmp_path))}
    dur, stats = events["exec1.model_call"]
    assert dur > 0
    assert {k: int(stats[k]) for k in ("rid", "rows", "rows_valid",
                                       "device")} \
        == {"rid": 7, "rows": 48, "rows_valid": 41, "device": 0}
    assert events["compile.steady"][1]["signature"] == "8x2:uint8"


def test_tracer_gets_the_counts_and_instants_stay_instants():
    tracer = Tracer(TraceSettings(sample_hz=0))
    trace.ACTIVE = tracer
    with trace.span("exec1.model_call", rid=3, rows=16, rows_valid=9,
                    device=2):
        pass
    with trace.span("loader.transfer"):
        pass
    trace.instant("loader.decode_ready", rid=3)
    trace.instant("health.lane_state", args={"from": "a", "to": "b"})
    events = tracer.snapshot_events()
    assert [(e[0], e[1], e[5], e[6]) for e in events] == [
        ("exec1.model_call", "X", 3,
         {"rows": 16, "rows_valid": 9, "device": 2}),
        ("loader.transfer", "X", None, None),
        ("loader.decode_ready", "i", 3, None),
        ("health.lane_state", "i", None, {"from": "a", "to": "b"})]


def test_compile_steady_instant_once_per_new_signature():
    import numpy as np

    from rnb_tpu.compilestats import SignatureTracker
    tracer = Tracer(TraceSettings(sample_hz=0))
    trace.ACTIVE = tracer
    tracker = SignatureTracker()
    warm = np.zeros((8, 2), np.uint8)
    tracker.observe(warm)
    tracker.freeze()
    tracker.observe(warm)                        # warmed: no instant
    tracker.observe(np.zeros((16, 2), np.uint8))  # a compile in the run
    tracker.observe(np.zeros((16, 2), np.uint8))  # the same one again
    assert tracker.snapshot() == {"warmup": 1, "steady_new": 1,
                                  "steady_calls": 3}
    assert [(e[0], e[1], e[6]) for e in tracer.snapshot_events()] == [
        ("compile.steady", "i", {"signature": "16x2:uint8"})]


def test_traced_run_dispatch_spans_carry_rows_and_device(tmp_path):
    res = _run(tmp_path, "rows", {"enabled": True, "sample_hz": 0},
               videos=12, interval_ms=0)
    assert res.termination_flag == 0
    with open(os.path.join(res.log_dir, "trace.json")) as f:
        events = json.load(f)["traceEvents"]
    calls = [e for e in events if e["name"] == "exec1.model_call"]
    assert len(calls) >= 12
    for e in calls:
        # TinyLoader ships 2 valid rows in a 4-row batch to device 1
        assert (e["args"]["rows"], e["args"]["rows_valid"]) == (4, 2)
        assert isinstance(e["args"]["device"], int)
    # a loader takes no batch: its call spans carry no counts
    assert all("rows" not in e.get("args", {}) for e in events
               if e["name"] == "exec0.model_call")
    names = {e["name"] for e in events}
    assert {"exec0.finish", "exec1.finish", "exec0.publish"} <= names
