"""Device observability plane (rnb_tpu.devobs / rnb_tpu.memledger):
settings validation, ledger register/peak/footing semantics, MFU
arithmetic against hand-computed dispatches, trace-merge validity with
device-track flow linkage, the watermark trigger, the devobs-off
byte-stability contract, and an e2e run held to ``parse_utils
--check``.

Unit coverage runs without a JAX backend; the e2e cases drive the tiny
test pipeline (tests.pipeline_helpers.TinyComputeSink declares the
compute/params seam) through run_benchmark.
"""

import json
import os
import sys

import pytest

from rnb_tpu import devobs, memledger, metrics, trace
from rnb_tpu.devobs import (DevObsPlane, DevObsSettings,
                            StageComputeMeter, model_call_spans)
from rnb_tpu.memledger import MEM_OWNERS, MemLedger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_active_plane():
    """Unit tests must never leak the module-global plane/ledger into
    later tests (benchmark.py owns install/clear in real runs)."""
    devobs.ACTIVE = None
    memledger.ACTIVE = None
    metrics.ACTIVE = None
    trace.ACTIVE = None
    yield
    devobs.ACTIVE = None
    memledger.ACTIVE = None
    metrics.ACTIVE = None
    trace.ACTIVE = None


def _parse_utils():
    scripts = os.path.join(REPO, "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    import parse_utils
    return parse_utils


# -- settings / config validation -------------------------------------

def test_settings_from_config():
    assert DevObsSettings.from_config(None) is None
    assert DevObsSettings.from_config({"enabled": False}) is None
    s = DevObsSettings.from_config({})
    assert s is not None and s.capture_window_ms == 0.0
    s = DevObsSettings.from_config(
        {"capture_window_ms": 150, "watermark_mb": 2,
         "max_captures": 2, "capture_max_ops": 100,
         "capture_on_trigger": False, "sample_hz": 5})
    assert s.capture_window_ms == 150.0
    assert s.watermark_mb == 2.0
    assert s.max_captures == 2 and s.capture_max_ops == 100
    assert not s.capture_on_trigger and s.sample_hz == 5.0


def _minimal_config(devobs_raw):
    return {
        "video_path_iterator":
            "tests.pipeline_helpers.CountingPathIterator",
        "devobs": devobs_raw,
        "pipeline": [
            {"model": "tests.pipeline_helpers.TinyLoader",
             "queue_groups": [{"devices": [0], "out_queues": [0]}]},
            {"model": "tests.pipeline_helpers.TinySink",
             "queue_groups": [{"devices": [0], "in_queue": 0}]},
        ],
    }


def test_config_accepts_and_rejects_devobs_keys():
    from rnb_tpu.config import ConfigError, parse_config
    cfg = parse_config(_minimal_config(
        {"enabled": True, "capture_window_ms": 100,
         "watermark_mb": 1.5}))
    assert cfg.devobs["watermark_mb"] == 1.5
    with pytest.raises(ConfigError):
        parse_config(_minimal_config({"bogus_knob": 1}))
    with pytest.raises(ConfigError):
        parse_config(_minimal_config({"capture_window_ms": -1}))
    with pytest.raises(ConfigError):
        parse_config(_minimal_config({"watermark_mb": 0}))
    with pytest.raises(ConfigError):
        parse_config(_minimal_config({"max_captures": 0}))
    with pytest.raises(ConfigError):
        parse_config(_minimal_config({"enabled": "yes"}))


# -- memory ledger ----------------------------------------------------

def test_ledger_register_sample_and_footing():
    ledger = MemLedger()
    ledger.register("params", "cpu:0", ("p", 1), 1000, live=True)
    ledger.register("cache", "cpu:0", ("c", 1), lambda: 250)
    ledger.register("staging", "host", ("s", 1), 4096)
    record = ledger.sample()
    assert record["total"] == 1000 + 250 + 4096
    assert record["owners"] == {"params": 1000, "cache": 250,
                                "staging": 4096}
    assert record["devices"] == {"cpu:0": 1250, "host": 4096}
    snap = ledger.snapshot()
    # owner rows foot to the total by construction
    assert sum(entry["bytes"] for entry in snap["owners"].values()) \
        == snap["total_bytes"]


def test_ledger_dedupes_shared_keys_and_rejects_undeclared():
    ledger = MemLedger()
    # replicas sharing one parameter copy register the same key: the
    # second registration replaces, never double-counts
    ledger.register("params", "cpu:0", ("shared", 7), 500)
    ledger.register("params", "cpu:1", ("shared", 7), 500)
    assert ledger.sample()["total"] == 500
    with pytest.raises(ValueError):
        ledger.register("mystery_owner", "cpu:0", ("x", 1), 10)
    assert "params" in MEM_OWNERS and "handoff" in MEM_OWNERS


def test_ledger_peak_tracks_release():
    calls = {"n": 1024}
    ledger = MemLedger()
    ledger.register("cache", "cpu:0", ("c", 1), lambda: calls["n"])
    ledger.sample()
    calls["n"] = 64  # eviction shrank the cache
    record = ledger.sample()
    assert record["total"] == 64
    snap = ledger.snapshot()
    assert snap["peak_bytes"] == 1024          # high-water sticks
    assert snap["total_bytes"] == 64           # final reflects release
    assert snap["owners"]["cache"]["peak_bytes"] == 1024
    assert snap["peak_bytes"] >= snap["total_bytes"]


def test_ledger_watermark_counts_crossings_once_per_episode():
    calls = {"n": 5}
    ledger = MemLedger(watermark_bytes=100)
    ledger.register("cache", "cpu:0", ("c", 1), lambda: calls["n"])
    ledger.sample()
    assert ledger.watermark_hits == 0
    calls["n"] = 150
    ledger.sample()
    ledger.sample()  # still above: same episode, no second hit
    assert ledger.watermark_hits == 1
    calls["n"] = 10
    ledger.sample()
    calls["n"] = 200
    ledger.sample()  # dipped below and crossed again
    assert ledger.watermark_hits == 2


def test_watermark_arms_flight_recorder_and_capture_hook():
    from rnb_tpu.metrics import (MetricsRegistry, MetricsSettings,
                                 SpanBridge)
    reg = MetricsRegistry(MetricsSettings())
    reg.bridge = SpanBridge(reg, ring_events=16)
    fired = []
    reg.trigger_hooks.append(lambda reason, detail:
                             fired.append((reason, detail)))
    metrics.ACTIVE = reg
    ledger = MemLedger(watermark_bytes=10)
    ledger.register("cache", "cpu:0", ("c", 1), 100)
    ledger.sample()
    assert reg.num_triggers == 1
    assert fired and fired[0][0] == metrics.TRIGGER_MEMORY_WATERMARK
    assert fired[0][1]["total_bytes"] == 100


def test_trigger_hooks_fire_with_flight_recorder_disarmed():
    """A disarmed flight recorder (no ring) must not swallow the
    capture-arming hooks: the watermark crossing still reaches the
    devobs observer even though no dump can be written."""
    from rnb_tpu.metrics import MetricsRegistry, MetricsSettings
    reg = MetricsRegistry(MetricsSettings(
        flight_recorder={"enabled": False}))
    assert reg.bridge is None  # recorder off: no ring, no dumps
    fired = []
    reg.trigger_hooks.append(lambda reason, detail:
                             fired.append(reason))
    metrics.ACTIVE = reg
    ledger = MemLedger(watermark_bytes=10)
    ledger.register("cache", "cpu:0", ("c", 1), 100)
    ledger.sample()
    assert fired == [metrics.TRIGGER_MEMORY_WATERMARK]
    assert reg.num_dumps == 0  # the dump machinery stayed disarmed


def test_watermark_arms_capture_without_metrics():
    """A metrics-less devobs run still gets the watermark capture:
    the ledger's direct observer arms it (and with a live registry it
    defers to the trigger-hook path — one crossing, one capture)."""
    plane = DevObsPlane(DevObsSettings(watermark_mb=0.00001))
    plane.ledger.register("cache", "cpu:0", ("c", 1), 100)
    assert metrics.ACTIVE is None
    plane.ledger.sample()
    assert plane._capture_requests \
        == [metrics.TRIGGER_MEMORY_WATERMARK]
    # dedupe side: with a registry live, the direct observer defers
    from rnb_tpu.metrics import MetricsRegistry, MetricsSettings
    plane2 = DevObsPlane(DevObsSettings(watermark_mb=0.00001))
    plane2.ledger.register("cache", "cpu:0", ("c", 1), 100)
    metrics.ACTIVE = MetricsRegistry(MetricsSettings())
    plane2.ledger.sample()
    assert plane2._capture_requests == []


def test_capture_budget_counts_inflight():
    plane = DevObsPlane(DevObsSettings(max_captures=1))
    plane._captures_inflight = 1  # a capture is mid-flight
    plane.request_capture("window")
    assert plane._capture_requests == []
    assert plane.captures_skipped == 1


# -- compute meters / MFU arithmetic ----------------------------------

def test_meter_mfu_against_hand_computed_dispatches():
    meter = StageComputeMeter(1, flops_per_row=2_000_000, devices=1)
    meter.note(3, 0.5)   # 3 rows in 0.5 s
    meter.note(5, 1.5)   # 5 rows in 1.5 s
    snap = meter.snapshot()
    assert snap == {"rows": 8, "dispatches": 2, "busy_s": 2.0}
    # 8 rows x 2 MFLOP / 2 s = 8 MFLOP/s = 8e-6 TFLOP/s
    assert meter.achieved_tflops() == pytest.approx(8e-6)


def test_compute_summary_cross_foots_bench_arithmetic():
    plane = DevObsPlane(DevObsSettings())
    plane._peak_tflops = 100.0  # pretend-device peak
    meter = StageComputeMeter(1, flops_per_row=1_000_000_000)
    meter.note(4, 2.0)
    plane.meters[1] = meter
    summary = plane.compute_summary(total_time_s=2.0,
                                    devices_used_count=2)
    assert summary["stages"] == 1 and summary["rows"] == 4
    assert summary["flops_total"] == 4_000_000_000
    assert summary["window_us"] == 2_000_000
    # bench arithmetic: (4 rows / 2 s) * 1 GF / 1e12 = 0.002 TFLOP/s
    assert summary["tflops_milli"] == 2
    # mfu = 0.002 / (100 * 2) = 1e-5 -> round(., 4) = 0.0 -> 0
    assert summary["mfu_e4"] == 0
    detail = summary["stage_detail"]["step1"]
    assert detail["flops"] == detail["flops_per_row"] * detail["rows"]
    assert detail["tflops_busy"] == pytest.approx(0.002, rel=1e-3)
    assert detail["mfu_busy"] == pytest.approx(2e-5, rel=1e-3)


def test_compute_summary_without_peak_reports_sentinel():
    plane = DevObsPlane(DevObsSettings())
    plane._peak_tflops = None  # the CPU harness: no known peak
    meter = StageComputeMeter(0, flops_per_row=10)
    meter.note(1, 0.1)
    plane.meters[0] = meter
    summary = plane.compute_summary(1.0, 1)
    assert summary["mfu_e4"] == -1
    assert summary["stage_detail"]["step0"]["mfu_busy"] is None
    # no meters at all: the record still exists (zero flops) so the
    # captures counter stays checkable on flops-less pipelines
    empty = DevObsPlane(DevObsSettings())
    empty._peak_tflops = None
    summary = empty.compute_summary(1.0, 1)
    assert summary["stages"] == 0 and summary["flops_total"] == 0
    assert summary["rows"] == 0 and summary["stage_detail"] == {}


# -- trace merge ------------------------------------------------------

def test_device_events_merge_validates_and_flow_links(tmp_path):
    from rnb_tpu.devobs import _Capture
    from rnb_tpu.trace import Tracer, TraceSettings, validate_trace
    tracer = Tracer(TraceSettings(sample_hz=0))
    # a model_call span for rid 7 covering [t0+1.0, t0+2.0]
    t0 = 1000.0
    tracer.add_event("exec1.model_call", "X", t0 + 1.0, 1.0, 7, None)
    tracer.add_event("client.enqueue", "i", t0 + 0.5, 0.0, 7, None)
    plane = DevObsPlane(DevObsSettings())
    # a capture whose plane clock ends at 5000 ns anchored to
    # t1_epoch = t0 + 2.0: op [4000, 5000] ns maps to
    # [t0 + 2.0 - 1e-6, t0 + 2.0] — inside the model_call span
    plane.captures.append(_Capture(
        0, "window", t0, t0 + 2.0,
        [("fusion.1", 4000, 5000, "/device:TPU:0")], 1, None))
    events = plane.device_events(
        model_call_spans(tracer.snapshot_events()))
    assert len(events) == 1
    name, ph, ts, dur, track, rid, args = events[0]
    assert track == "device:/device:TPU:0" and ph == "X"
    assert rid == 7  # flow-correlated to the enclosing model_call
    assert args["devobs_capture"] == 0
    tracer.extend(events)
    path = str(tmp_path / "trace.json")
    tracer.export(path, "merge-test")
    assert validate_trace(path) == []
    doc = json.load(open(path))
    device_tids = {ev["tid"] for ev in doc["traceEvents"]
                   if ev.get("ph") == "M"
                   and ev.get("name") == "thread_name"
                   and ev["args"]["name"].startswith("device:")}
    assert device_tids
    assert any(ev.get("ph") in ("s", "t", "f")
               and ev.get("tid") in device_tids
               for ev in doc["traceEvents"])


def test_device_events_rid_with_overlapping_spans():
    """Replica lanes run concurrent model_call spans: an op inside a
    long span that STARTED before a shorter one must still bind (the
    enclosure walk, not just the latest-started span)."""
    from rnb_tpu.devobs import _Capture
    plane = DevObsPlane(DevObsSettings())
    # op [900, 1000] ns anchored to t1_epoch=10.4: midpoint ~10.4 —
    # inside lane A's [10.0, 10.5] but past lane B's [10.2, 10.3],
    # which is the later-started span the naive bisect would pick
    plane.captures.append(_Capture(
        0, "window", 10.0, 10.4,
        [("op", 900, 1000, "/device:TPU:0")], 1, None))
    spans = [(10.0, 10.5, 1), (10.2, 10.3, 2)]
    events = plane.device_events(spans)
    assert len(events) == 1 and events[0][5] == 1


def test_device_events_outside_spans_carry_no_rid():
    from rnb_tpu.devobs import _Capture
    plane = DevObsPlane(DevObsSettings())
    plane.captures.append(_Capture(
        0, "forced", 0.0, 10.0,
        [("op", 100, 200, "/host:CPU")], 1, None))
    events = plane.device_events([])  # no model_call spans at all
    assert len(events) == 1 and events[0][5] is None


# -- e2e --------------------------------------------------------------

TINY_DEVOBS_CONFIG = {
    "video_path_iterator":
        "tests.pipeline_helpers.CountingPathIterator",
    "pipeline": [
        {"model": "tests.pipeline_helpers.TinyRoutedLoader",
         "queue_groups": [{"devices": [0], "out_queues": [0]}],
         "num_shared_tensors": 4},
        {"model": "tests.pipeline_helpers.TinyComputeSink",
         "queue_groups": [{"devices": [1], "in_queue": 0}]},
    ],
}


def _run(tmp_path, name, devobs_raw, videos=24, trace_on=False):
    from rnb_tpu.benchmark import run_benchmark
    cfg = dict(TINY_DEVOBS_CONFIG)
    if devobs_raw is not None:
        cfg["devobs"] = devobs_raw
    if trace_on:
        cfg["trace"] = {"enabled": True, "sample_hz": 50}
    path = os.path.join(str(tmp_path), "%s.json" % name)
    with open(path, "w") as f:
        json.dump(cfg, f)
    return run_benchmark(path, mean_interval_ms=1, num_videos=videos,
                         queue_size=50,
                         log_base=os.path.join(str(tmp_path),
                                               "logs-%s" % name),
                         print_progress=False)


def test_e2e_devobs_run_foots_and_checks_green(tmp_path):
    from tests.pipeline_helpers import TinyComputeSink
    res = _run(tmp_path, "on",
               {"enabled": True, "capture_window_ms": 80,
                "watermark_mb": 0.000001, "sample_hz": 100},
               trace_on=True)
    assert res.termination_flag == 0
    # rows are the completed clips (TinyRoutedLoader's num_clips
    # stamps), flops are the declared per-row count times the rows
    assert res.compute_stages == 1
    assert res.compute_rows == res.clips_completed > 0
    assert res.compute_flops_total \
        == TinyComputeSink.FLOPS_PER_ROW * res.compute_rows
    assert res.compute_dispatches > 0
    detail = res.compute_stage_detail["step1"]
    assert detail["flops_per_row"] == TinyComputeSink.FLOPS_PER_ROW
    # the ledger: params owner == the 2x2 float32 eye (16 bytes), and
    # owner rows foot to the total
    assert res.memory_owner_detail["params"]["bytes"] == 16
    assert sum(entry["bytes"] for entry
               in res.memory_owner_detail.values()) \
        == res.memory_total_bytes
    assert res.memory_peak_bytes >= res.memory_total_bytes
    assert res.memory_watermark_hits >= 1  # 16 B > the ~1 B watermark
    # the configured window produced a bounded on-disk artifact
    captures = [n for n in os.listdir(res.log_dir)
                if n.startswith("devobs-capture-")]
    assert len(captures) == res.compute_captures >= 1
    # log-meta carries the new lines and parse_meta round-trips them
    parse_utils = _parse_utils()
    meta = parse_utils.parse_meta(res.log_dir)
    assert meta["compute_flops_total"] == res.compute_flops_total
    assert meta["memory_total_bytes"] == res.memory_total_bytes
    # the full cross-artifact invariant set holds
    problems = parse_utils.check_job(res.log_dir)
    assert problems == [], problems


def test_e2e_check_catches_memory_footing_violation(tmp_path):
    """--check is a real tripwire: corrupt the Memory owners: line and
    the footing invariant must fire."""
    res = _run(tmp_path, "tamper",
               {"enabled": True, "sample_hz": 100})
    assert res.termination_flag == 0
    meta_path = os.path.join(res.log_dir, "log-meta.txt")
    text = open(meta_path).read()
    tampered = text.replace('"bytes": 16', '"bytes": 17')
    assert tampered != text
    open(meta_path, "w").write(tampered)
    parse_utils = _parse_utils()
    problems = parse_utils.check_job(res.log_dir)
    assert any("foot to the ledger total" in p
               or "sum to" in p for p in problems), problems


def test_e2e_check_catches_cooked_tflops(tmp_path):
    """tflops_milli is recomputed offline from rows/window x per-row
    flops — a cooked headline number fails --check."""
    res = _run(tmp_path, "cooked", {"enabled": True, "sample_hz": 100})
    assert res.termination_flag == 0
    meta_path = os.path.join(res.log_dir, "log-meta.txt")
    text = open(meta_path).read()
    tampered = text.replace(
        "tflops_milli=%d" % res.compute_tflops_milli,
        "tflops_milli=%d" % (res.compute_tflops_milli + 999))
    assert tampered != text
    open(meta_path, "w").write(tampered)
    parse_utils = _parse_utils()
    problems = parse_utils.check_job(res.log_dir)
    assert any("recompute to" in p for p in problems), problems


def test_check_survives_malformed_detail(tmp_path):
    """A malformed Compute stages:/Memory owners: detail (the
    adversarial-edit case) must surface as a finding, never crash the
    checker."""
    res = _run(tmp_path, "malformed", {"enabled": True,
                                       "sample_hz": 100})
    assert res.termination_flag == 0
    meta_path = os.path.join(res.log_dir, "log-meta.txt")
    lines = open(meta_path).read().splitlines(True)
    out = []
    for line in lines:
        if line.startswith("Compute stages:"):
            out.append('Compute stages: {"bogus": {"rows": "abc"}}\n')
        else:
            out.append(line)
    open(meta_path, "w").write("".join(out))
    parse_utils = _parse_utils()
    problems = parse_utils.check_job(res.log_dir)
    assert any("malformed" in p or "stages" in p for p in problems), \
        problems


def test_e2e_flopsless_pipeline_still_counts_captures(tmp_path):
    """A devobs run whose stages declare no compute profile still
    writes the Compute: line (zero flops) so the captures-vs-
    artifacts invariant stays live."""
    from rnb_tpu.benchmark import run_benchmark
    cfg = {
        "video_path_iterator":
            "tests.pipeline_helpers.CountingPathIterator",
        "devobs": {"enabled": True, "capture_window_ms": 60,
                   "sample_hz": 100},
        "pipeline": [
            {"model": "tests.pipeline_helpers.TinyLoader",
             "queue_groups": [{"devices": [0], "out_queues": [0]}],
             "num_shared_tensors": 4},
            {"model": "tests.pipeline_helpers.TinySink",
             "queue_groups": [{"devices": [1], "in_queue": 0}]},
        ],
    }
    path = os.path.join(str(tmp_path), "flopsless.json")
    with open(path, "w") as f:
        json.dump(cfg, f)
    res = run_benchmark(path, mean_interval_ms=1, num_videos=24,
                        queue_size=50,
                        log_base=os.path.join(str(tmp_path), "logs"),
                        print_progress=False)
    assert res.termination_flag == 0
    assert res.compute_stages == 0 and res.compute_flops_total == 0
    captures = [n for n in os.listdir(res.log_dir)
                if n.startswith("devobs-capture-")]
    assert len(captures) == res.compute_captures >= 1
    parse_utils = _parse_utils()
    meta = parse_utils.parse_meta(res.log_dir)
    assert meta["compute_captures"] == res.compute_captures
    problems = parse_utils.check_job(res.log_dir)
    assert problems == [], problems


def test_devobs_off_run_stays_byte_stable(tmp_path):
    res = _run(tmp_path, "plain", None)
    assert res.termination_flag == 0
    assert res.compute_stages == 0 and res.memory_total_bytes == 0
    assert not [n for n in os.listdir(res.log_dir)
                if n.startswith("devobs-capture-")]
    with open(os.path.join(res.log_dir, "log-meta.txt")) as f:
        meta_text = f.read()
    assert "Compute:" not in meta_text and "Memory:" not in meta_text
    tables = [n for n in os.listdir(res.log_dir) if "group" in n]
    with open(os.path.join(res.log_dir, tables[0])) as f:
        header = f.read().split("\n", 1)[0].split()
    # the stamp schema is exactly the pre-devobs set
    assert header == ["enqueue_filename", "runner0_start",
                      "inference0_start", "inference0_finish",
                      "runner1_start", "inference1_start",
                      "inference1_finish", "device0", "device1"]
