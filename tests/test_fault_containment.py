"""Request-level fault containment, load shedding, and the
deterministic fault-injection harness (rnb_tpu.faults).

Covers the failure taxonomy (transient/permanent/fatal), the executor's
retry + dead-letter path, the "shed" overload policy at both overflow
sites, the fusing loader's internal containment, the extended summary
schema end-to-end through scripts/parse_utils, and — the acceptance
scenario — a 100-video chaos run that completes with exact fault
accounting while the fault-free run keeps reference-parity behavior.
"""

import json
import os

import numpy as np
import pytest

from rnb_tpu.benchmark import run_benchmark
from rnb_tpu.config import ConfigError, parse_config
from rnb_tpu.control import TerminationFlag
from rnb_tpu.faults import (FATAL, PERMANENT, TRANSIENT, CorruptVideoError,
                            FaultPlan, InjectedPermanentError,
                            InjectedTransientError, TransientDecodeError,
                            classify_error, fault_reason, validate_plan)

chaos = pytest.mark.chaos


def _write_config(tmp_path, cfg, name="pipeline.json"):
    path = os.path.join(str(tmp_path), name)
    with open(path, "w") as f:
        json.dump(cfg, f)
    return path


def _two_step(extra_root=None, extra_step0=None):
    cfg = {
        "video_path_iterator": "tests.pipeline_helpers.CountingPathIterator",
        "pipeline": [
            {"model": "tests.pipeline_helpers.TinyLoader",
             "queue_groups": [{"devices": [0], "out_queues": [0]}],
             "num_shared_tensors": 4},
            {"model": "tests.pipeline_helpers.TinySink",
             "queue_groups": [{"devices": [1], "in_queue": 0}]},
        ],
    }
    cfg.update(extra_root or {})
    cfg["pipeline"][0].update(extra_step0 or {})
    return cfg


# -- taxonomy ---------------------------------------------------------

def test_classify_error_taxonomy():
    assert classify_error(InjectedTransientError("x")) is TRANSIENT
    assert classify_error(TransientDecodeError("x")) is TRANSIENT
    assert classify_error(OSError("io blip")) is TRANSIENT
    assert classify_error(InjectedPermanentError("x")) is PERMANENT
    assert classify_error(CorruptVideoError("x")) is PERMANENT
    # deterministic OSErrors are verdicts, not blips: retrying an
    # open() of a missing file cannot succeed
    assert classify_error(FileNotFoundError("gone")) is PERMANENT
    assert classify_error(PermissionError("denied")) is PERMANENT
    assert fault_reason(FileNotFoundError("gone")) == "file-not-found"
    # anything unclassified stays fatal — containment must not paper
    # over genuine bugs
    assert classify_error(ValueError("bug")) is FATAL
    assert classify_error(AssertionError()) is FATAL
    assert classify_error(KeyError("k")) is FATAL
    # classified decode errors still read as ValueError for
    # pre-containment callers
    assert isinstance(CorruptVideoError("x"), ValueError)
    assert isinstance(TransientDecodeError("x"), ValueError)


def test_fault_reasons():
    assert fault_reason(CorruptVideoError("x")) == "corrupt-video"
    assert fault_reason(InjectedPermanentError("x")) == "injected-permanent"
    assert fault_reason(OSError("x")) == "os-error"
    e = InjectedTransientError("x")
    e.fault_reason = "custom"
    assert fault_reason(e) == "custom"


# -- plan validation + determinism ------------------------------------

def test_validate_plan_rejects_malformed():
    for bad in (
            [],                                          # not an object
            {"faults": "nope"},                          # faults not a list
            {"faults": [{"kind": "bogus",
                         "request_ids": [1]}]},          # unknown kind
            {"faults": [{"kind": "transient"}]},         # no selector
            {"faults": [{"kind": "transient", "request_ids": [1],
                         "probability": 0.5}]},          # both selectors
            {"faults": [{"kind": "latency",
                         "request_ids": [1]}]},          # latency needs ms
            {"faults": [{"kind": "transient", "request_ids": [1],
                         "times": 0}]},                  # times >= 1
            {"faults": [{"kind": "transient", "request_ids": [1],
                         "typo": True}]},                # unknown key
            {"faults": [{"kind": "transient", "request_ids": [1],
                         "ms": 100}]},                   # ms on error kind
            {"faults": [{"kind": "latency", "ms": 5, "request_ids": [1],
                         "times": 2}]},                  # times on delay
            {"seed": "x", "faults": []},                 # non-int seed
    ):
        with pytest.raises(ValueError):
            validate_plan(bad)
    validate_plan({"seed": 3, "faults": [
        {"step": 0, "kind": "permanent", "request_ids": [1]},
        {"kind": "transient", "probability": 0.25},
        {"step": 1, "kind": "latency", "ms": 5, "probability": 1.0},
        {"step": 0, "kind": "stall", "ms": 5, "request_ids": [2]},
    ]})


@pytest.mark.parametrize("kind", ["net_refused", "net_reset",
                                  "net_timeout", "net_partial_frame",
                                  "net_corrupt"])
def test_validate_plan_refuses_the_removed_net_kinds_by_name(kind):
    """The transport they addressed is gone (PR 45): a plan that still
    names one is refused as any unknown kind is, by name."""
    with pytest.raises(ValueError, match=repr(kind)):
        validate_plan({"faults": [{"kind": kind, "request_ids": [1]}]})


def test_plan_fire_and_determinism():
    spec = {"seed": 11, "faults": [
        {"step": 0, "kind": "transient", "request_ids": [4], "times": 2},
        {"step": 0, "kind": "permanent", "probability": 0.3},
    ]}
    plan_a, plan_b = FaultPlan(spec), FaultPlan(spec)
    # id-listed transient fires on the first `times` attempts only
    with pytest.raises(InjectedTransientError):
        plan_a.fire(0, 4, attempt=0)
    with pytest.raises(InjectedTransientError):
        plan_a.fire(0, 4, attempt=1)
    plan_a.fire(0, 4, attempt=2)  # budget spent: no raise
    plan_a.fire(1, 4, attempt=0)  # wrong step: no raise
    # probability draws are a pure function of (seed, site): two plan
    # instances agree on every request id
    for rid in range(200):
        hit_a = hit_b = False
        try:
            plan_a.fire(0, rid + 1000, attempt=0)
        except InjectedPermanentError:
            hit_a = True
        try:
            plan_b.fire(0, rid + 1000, attempt=0)
        except InjectedPermanentError:
            hit_b = True
        assert hit_a == hit_b
    # ~30% of draws hit (loose bounds; deterministic, so never flaky)
    hits = 0
    for rid in range(1000):
        try:
            plan_b.fire(0, rid + 1000, attempt=0)
        except InjectedPermanentError:
            hits += 1
    assert 200 < hits < 400


def test_plan_from_env(monkeypatch):
    monkeypatch.delenv("RNB_FAULT_PLAN", raising=False)
    assert FaultPlan.from_env() is None
    monkeypatch.setenv("RNB_FAULT_PLAN", json.dumps(
        {"faults": [{"kind": "permanent", "request_ids": [1]}]}))
    plan = FaultPlan.from_env()
    with pytest.raises(InjectedPermanentError):
        plan.fire(0, 1)
    monkeypatch.setenv("RNB_FAULT_PLAN", "{not json")
    with pytest.raises(ValueError):
        FaultPlan.from_env()


# -- config schema ----------------------------------------------------

def test_config_schema_robustness_keys():
    base = _two_step()
    cfg = parse_config(dict(base))
    assert cfg.overload_policy == "abort"
    assert cfg.fault_containment is True
    assert cfg.fault_plan is None
    assert cfg.steps[0].max_retries == 0

    rich = _two_step(
        extra_root={"overload_policy": "shed",
                    "fault_containment": True,
                    "fault_plan": {"faults": [
                        {"kind": "transient", "probability": 0.1}]}},
        extra_step0={"max_retries": 3, "retry_backoff_ms": 2})
    cfg = parse_config(rich)
    assert cfg.overload_policy == "shed"
    assert cfg.steps[0].max_retries == 3
    assert cfg.steps[0].retry_backoff_ms == 2.0
    assert cfg.steps[1].max_retries == 0
    # the retry knobs are schema, not model kwargs
    assert "max_retries" not in cfg.steps[0].extras

    for bad_root in ({"overload_policy": "drop"},
                     {"fault_containment": "yes"},
                     {"fault_plan": {"faults": [{"kind": "??"}]}},
                     {"overload_polcy": "shed"}):          # typo'd key
        with pytest.raises(ConfigError):
            parse_config(_two_step(extra_root=bad_root))
    for bad_step in ({"max_retries": -1}, {"max_retries": "2"},
                     {"retry_backoff_ms": -5}):
        with pytest.raises(ConfigError):
            parse_config(_two_step(extra_step0=bad_step))
    # a fault targeting a step the pipeline does not have would
    # silently never fire — rejected at parse time
    with pytest.raises(ConfigError):
        parse_config(_two_step(extra_root={"fault_plan": {"faults": [
            {"step": 2, "kind": "permanent", "request_ids": [1]}]}}))


def test_plan_check_steps():
    plan = FaultPlan({"faults": [
        {"step": 1, "kind": "permanent", "request_ids": [1]},
        {"kind": "transient", "probability": 0.1}]})  # step-less: any
    plan.check_steps(2)
    with pytest.raises(ValueError):
        plan.check_steps(1)


# -- the acceptance chaos run -----------------------------------------

@chaos
def test_chaos_acceptance_run(tmp_path):
    """100 videos, k=3 injected permanent decode failures plus a
    3-request transient burst: the run completes (no abort), reports
    exactly num_failed == k, the retried transients succeed and count
    in num_retries, and latency percentiles cover successes only —
    while the same pipeline without a plan behaves exactly like the
    pre-containment runtime."""
    plan = {"seed": 7, "faults": [
        {"step": 0, "kind": "permanent", "request_ids": [5, 25, 50]},
        {"step": 0, "kind": "transient", "request_ids": [10, 11, 12]},
        {"step": 1, "kind": "latency", "ms": 10, "request_ids": [7]},
        {"step": 0, "kind": "stall", "ms": 20, "request_ids": [60]},
    ]}
    cfg = _two_step(extra_root={"fault_plan": plan},
                    extra_step0={"max_retries": 2, "retry_backoff_ms": 1})
    path = _write_config(tmp_path, cfg)
    res = run_benchmark(path, mean_interval_ms=0, num_videos=100,
                        queue_size=500, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == \
        TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    assert res.num_failed == 3
    assert res.failure_reasons == {"injected-permanent": 3}
    assert res.num_retries == 3  # one retry per burst member, then ok
    assert res.num_shed == 0
    assert res.num_completed >= 97
    assert res.p99_latency_ms >= res.p50_latency_ms > 0
    # dead-letter record names the exact ids
    with open(os.path.join(res.log_dir, "failed-requests.txt")) as f:
        lines = [ln.split() for ln in f if not ln.startswith("#")]
    assert sorted(int(ln[0]) for ln in lines) == [5, 25, 50]
    assert all(ln[1] == "0" and ln[2] == "injected-permanent"
               for ln in lines)
    # meta carries the same accounting
    with open(os.path.join(res.log_dir, "log-meta.txt")) as f:
        meta_text = f.read()
    assert "Termination flag: 0" in meta_text
    assert "Faults: num_failed=3 num_shed=0 num_retries=3" in meta_text

    # reference parity: no plan, abort policy -> byte-compatible
    # fault-free schema (no '# faults' trailer, zero counters)
    parity = _write_config(tmp_path, _two_step(), name="parity.json")
    res2 = run_benchmark(parity, mean_interval_ms=0, num_videos=100,
                         queue_size=500,
                         log_base=str(tmp_path / "logs2"),
                         print_progress=False)
    assert res2.termination_flag == \
        TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    assert (res2.num_failed, res2.num_shed, res2.num_retries) == (0, 0, 0)
    report = [f for f in os.listdir(res2.log_dir) if "group" in f][0]
    with open(os.path.join(res2.log_dir, report)) as f:
        text = f.read()
    assert "# faults" not in text
    assert not os.path.exists(
        os.path.join(res2.log_dir, "failed-requests.txt"))


@chaos
def test_transient_without_retry_budget_fails_request(tmp_path):
    """With max_retries=0 a transient fault degrades to a contained
    permanent failure with a 'retries-exhausted:' reason."""
    cfg = _two_step(extra_root={"fault_plan": {"faults": [
        {"step": 0, "kind": "transient", "request_ids": [3],
         "times": 99}]}})
    path = _write_config(tmp_path, cfg)
    res = run_benchmark(path, mean_interval_ms=0, num_videos=20,
                        queue_size=100, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == \
        TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    assert res.num_failed == 1
    assert res.failure_reasons == \
        {"retries-exhausted:injected-transient": 1}


@chaos
def test_containment_off_keeps_failfast(tmp_path):
    """fault_containment: false restores strict reference semantics —
    even a classified injected error aborts the job."""
    cfg = _two_step(
        extra_root={"fault_containment": False,
                    "fault_plan": {"faults": [
                        {"step": 0, "kind": "permanent",
                         "request_ids": [2]}]}})
    path = _write_config(tmp_path, cfg)
    res = run_benchmark(path, mean_interval_ms=0, num_videos=20,
                        queue_size=100, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == TerminationFlag.INTERNAL_ERROR


@chaos
def test_segment_step_failure_stays_failfast(tmp_path):
    """A permanent fault at a stage consuming forked SEGMENT cards is
    not contained (dead-lettering one segment would strand its sibling
    in the aggregator and double-count the request) — the job aborts
    exactly as pre-containment. A fault at the forking step itself
    (before the fork) is contained normally."""
    def seg_cfg(fault_step):
        return {
            "video_path_iterator":
                "tests.pipeline_helpers.CountingPathIterator",
            "fault_plan": {"faults": [
                {"step": fault_step, "kind": "permanent",
                 "request_ids": [6]}]},
            "pipeline": [
                {"model": "tests.pipeline_helpers.TinyLoader",
                 "queue_groups": [{"devices": [0], "out_queues": [0]}],
                 "num_segments": 2, "num_shared_tensors": 8,
                 "rows_per_video": 4},
                {"model": "tests.pipeline_helpers.TinyDouble",
                 "queue_groups": [{"devices": [1, 2], "in_queue": 0,
                                   "out_queues": [1]}]},
                {"model": "rnb_tpu.models.r2p1d.model.R2P1DAggregator",
                 "queue_groups": [{"devices": [-1], "in_queue": 1}],
                 "aggregate": 2},
            ],
        }
    path = _write_config(tmp_path, seg_cfg(fault_step=1))
    res = run_benchmark(path, mean_interval_ms=0, num_videos=12,
                        queue_size=100, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == TerminationFlag.INTERNAL_ERROR

    path = _write_config(tmp_path, seg_cfg(fault_step=0), name="fork.json")
    res = run_benchmark(path, mean_interval_ms=0, num_videos=12,
                        queue_size=100, log_base=str(tmp_path / "logs2"),
                        print_progress=False)
    assert res.termination_flag == \
        TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    assert res.num_failed == 1  # once, not once per segment


@chaos
def test_env_plan_overrides_config(tmp_path, monkeypatch):
    monkeypatch.setenv("RNB_FAULT_PLAN", json.dumps(
        {"faults": [{"step": 0, "kind": "permanent",
                     "request_ids": [1, 2]}]}))
    path = _write_config(tmp_path, _two_step())
    res = run_benchmark(path, mean_interval_ms=0, num_videos=15,
                        queue_size=100, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == \
        TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    assert res.num_failed == 2


# -- shed overload policy ---------------------------------------------

@chaos
def test_shed_at_filename_queue(tmp_path):
    """Under "shed" a full filename queue drops new requests with a
    counted outcome and the run still terminates cleanly — the same
    topology under "abort" dies with FILENAME_QUEUE_FULL
    (test_pipeline.test_filename_queue_overflow_aborts)."""
    cfg = {
        "video_path_iterator": "tests.pipeline_helpers.CountingPathIterator",
        "overload_policy": "shed",
        "pipeline": [
            {"model": "tests.pipeline_helpers.TinySlowSink",
             "queue_groups": [{"devices": [-1]}], "delay_s": 0.1},
        ],
    }
    path = _write_config(tmp_path, cfg)
    res = run_benchmark(path, mean_interval_ms=1, num_videos=30,
                        queue_size=2, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == \
        TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    assert res.num_shed > 0
    assert res.num_failed == 0
    assert res.num_completed + res.num_shed >= 30
    assert res.shed_sites == {"filename_queue": res.num_shed}
    with open(os.path.join(res.log_dir, "log-meta.txt")) as f:
        meta_text = f.read()
    assert "num_shed=%d" % res.num_shed in meta_text
    assert '"filename_queue"' in meta_text  # per-site breakdown


@chaos
def test_shed_between_stages(tmp_path):
    """A full inter-stage queue under "shed" drops the new item at the
    producer instead of raising FRAME_QUEUE_FULL."""
    cfg = {
        "video_path_iterator": "tests.pipeline_helpers.CountingPathIterator",
        "overload_policy": "shed",
        "pipeline": [
            {"model": "tests.pipeline_helpers.TinyLoader",
             "queue_groups": [{"devices": [0], "out_queues": [0]}],
             "num_shared_tensors": 4},
            {"model": "tests.pipeline_helpers.TinySlowSink",
             "queue_groups": [{"devices": [1], "in_queue": 0}],
             "delay_s": 0.15},
        ],
    }
    path = _write_config(tmp_path, cfg)
    res = run_benchmark(path, mean_interval_ms=1, num_videos=25,
                        queue_size=2, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == \
        TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    assert res.num_shed > 0
    # the sheds happened somewhere (client or step 0); no aborts
    assert res.num_completed + res.num_shed >= 25


# -- malformed real inputs through the pipeline -----------------------

def _write_tiny_dataset(root, corrupt=True):
    """3 valid 2-frame y4m videos (+1 corrupt) in a label subtree."""
    from rnb_tpu.decode import write_y4m
    label = os.path.join(root, "label0")
    os.makedirs(label, exist_ok=True)
    rng = np.random.default_rng(0)
    for i in range(3):
        frames = rng.integers(0, 256, (4, 16, 16, 3), dtype=np.uint8)
        write_y4m(os.path.join(label, "ok%d.y4m" % i), frames,
                  colorspace="420")
    if corrupt:
        with open(os.path.join(label, "bad.y4m"), "wb") as f:
            f.write(b"NOT_A_Y4M_STREAM totally corrupt payload\n")


@chaos
def test_corrupt_y4m_contained_in_pipeline(tmp_path, monkeypatch):
    """A corrupt video among good ones: with containment on, every
    request for it is a contained failure — the run completes and the
    good videos' requests all succeed (satellite: malformed-input error
    paths end in a failed request, not an aborted run)."""
    data_root = str(tmp_path / "data")
    _write_tiny_dataset(data_root)
    monkeypatch.setenv("RNB_TPU_DATA_ROOT", data_root)
    cfg = {
        "video_path_iterator":
            "rnb_tpu.models.r2p1d.model.R2P1DVideoPathIterator",
        "pipeline": [
            {"model": "rnb_tpu.models.r2p1d.model.R2P1DLoader",
             "queue_groups": [{"devices": [0]}],
             "max_clips": 2, "consecutive_frames": 2,
             "num_clips_population": [1, 2], "weights": [1, 1],
             "num_warmups": 0},
        ],
    }
    path = _write_config(tmp_path, cfg)
    # 8 requests cycling 4 files (sorted: bad, ok0, ok1, ok2): the
    # corrupt video is requested exactly twice
    res = run_benchmark(path, mean_interval_ms=0, num_videos=8,
                        queue_size=50, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == \
        TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    assert res.num_failed == 2
    assert res.failure_reasons == {"corrupt-video": 2}
    assert res.num_completed >= 6
    # the final instance's report carries the '# faults' trailer (the
    # failures happened AT the final step) and parse_utils reads both
    # the trailer-bearing table and the extended meta
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import parse_utils
    meta, df = parse_utils.get_data(res.log_dir)
    assert meta["num_failed"] == 2
    assert meta["failure_reasons"] == {"corrupt-video": 2}
    assert len(df) >= 6  # successes only in the table
    letters = parse_utils.parse_dead_letters(res.log_dir)
    assert list(letters["reason"].unique()) == ["corrupt-video"]
    report = [f for f in os.listdir(res.log_dir) if "group" in f][0]
    with open(os.path.join(res.log_dir, report)) as f:
        assert "# faults num_failed=2" in f.read()


@chaos
def test_fusing_loader_strict_mode_aborts(tmp_path, monkeypatch):
    """fault_containment: false applies to stage-INTERNAL containment
    too: a corrupt video surfacing inside the fusing loader's batch
    assembly must abort the job, not quietly dead-letter — strict
    semantics cannot depend on which code path the error takes."""
    data_root = str(tmp_path / "data")
    _write_tiny_dataset(data_root)
    monkeypatch.setenv("RNB_TPU_DATA_ROOT", data_root)
    cfg = {
        "video_path_iterator":
            "rnb_tpu.models.r2p1d.model.R2P1DVideoPathIterator",
        "fault_containment": False,
        "pipeline": [
            {"model": "rnb_tpu.models.r2p1d.model.R2P1DFusingLoader",
             "queue_groups": [{"devices": [0]}],
             "max_clips": 2, "consecutive_frames": 2, "fuse": 2,
             "num_clips_population": [1], "weights": [1],
             "num_warmups": 0},
        ],
    }
    path = _write_config(tmp_path, cfg)
    res = run_benchmark(path, mean_interval_ms=0, num_videos=8,
                        queue_size=50, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == TerminationFlag.INTERNAL_ERROR


def test_fusing_loader_transient_retry(monkeypatch):
    """A transient decode failure during fused-batch assembly honors
    the step's retry budget (synchronous re-decode) instead of being
    dead-lettered immediately."""
    import jax

    from rnb_tpu.models.r2p1d.model import R2P1DFusingLoader, _FuseRecord
    from rnb_tpu.telemetry import TimeCard

    loader = R2P1DFusingLoader(jax.devices()[0], max_clips=2,
                               consecutive_frames=2, num_warmups=0,
                               num_clips_population=[1], weights=[1])
    video = "synth://retry-test"
    tc = TimeCard(0)

    class BoomHandle:
        n = 1
        out = None
        error = None

        def wait(self, v):
            raise TransientDecodeError("rc -1")

    # no budget: transient is dead-lettered with the exhausted prefix
    loader.fault_retry_budget = (0, 0.0)
    assert loader._wait_contained(
        _FuseRecord(BoomHandle(), video, tc)) is False
    ((failed_tc, reason),) = loader.take_failed()
    assert failed_tc is tc
    assert reason == "retries-exhausted:decode-io"
    assert loader.take_retries() == 0

    # with budget: the synchronous re-decode succeeds on retry
    loader.fault_retry_budget = (2, 0.0)
    handle = BoomHandle()
    assert loader._wait_contained(_FuseRecord(handle, video, tc)) is True
    assert handle.out is not None and handle.out.shape[0] >= 1
    assert loader.take_retries() == 1
    assert loader.take_failed() == []


@chaos
def test_corrupt_y4m_contained_fusing_loader(tmp_path, monkeypatch):
    """The fusing loader excludes a corrupt video from its fused batch
    (internal containment via take_failed) — its batchmates complete."""
    data_root = str(tmp_path / "data")
    _write_tiny_dataset(data_root)
    monkeypatch.setenv("RNB_TPU_DATA_ROOT", data_root)
    cfg = {
        "video_path_iterator":
            "rnb_tpu.models.r2p1d.model.R2P1DVideoPathIterator",
        "pipeline": [
            {"model": "rnb_tpu.models.r2p1d.model.R2P1DFusingLoader",
             "queue_groups": [{"devices": [0]}],
             "max_clips": 2, "consecutive_frames": 2, "fuse": 2,
             "num_clips_population": [1], "weights": [1],
             "num_warmups": 0},
        ],
    }
    path = _write_config(tmp_path, cfg)
    res = run_benchmark(path, mean_interval_ms=0, num_videos=8,
                        queue_size=50, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == \
        TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    assert res.num_failed == 2
    assert res.failure_reasons == {"corrupt-video": 2}
    assert res.num_completed >= 6


@chaos
def test_injection_hits_fused_batches(tmp_path):
    """A fault targeting a step that consumes fused TimeCardList
    batches fires when ANY constituent matches, failing the whole
    dispatch (batch blast radius) — plans against downstream-of-batcher
    steps must not be silently inert."""
    cfg = {
        "video_path_iterator": "tests.pipeline_helpers.CountingPathIterator",
        "fault_plan": {"faults": [
            {"step": 2, "kind": "permanent", "request_ids": [2]}]},
        "pipeline": [
            {"model": "tests.pipeline_helpers.TinyLoader",
             "queue_groups": [{"devices": [0], "out_queues": [0]}],
             "num_shared_tensors": 4},
            {"model": "rnb_tpu.batcher.Batcher",
             "queue_groups": [{"devices": [1], "in_queue": 0,
                               "out_queues": [1]}],
             "batch": 2, "shapes": [[4, 2]]},
            {"model": "tests.pipeline_helpers.TinySink",
             "queue_groups": [{"devices": [2], "in_queue": 1}]},
        ],
    }
    path = _write_config(tmp_path, cfg)
    res = run_benchmark(path, mean_interval_ms=0, num_videos=12,
                        queue_size=100, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == \
        TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    # request 2's fused batch (requests 2 and 3) fails as a unit
    assert res.num_failed == 2
    assert res.failure_reasons == {"injected-permanent": 2}
    assert res.num_completed >= 10


@chaos
def test_prefetch_handle_retired_on_injected_fault(tmp_path, monkeypatch):
    """An injected fault can fire BEFORE a prefetched decode handle is
    completed; the executor must retire the abandoned handle or its
    native-pool tickets pin the decode buffers for the process's
    life."""
    data_root = str(tmp_path / "data")
    _write_tiny_dataset(data_root, corrupt=False)
    monkeypatch.setenv("RNB_TPU_DATA_ROOT", data_root)
    cfg = {
        "video_path_iterator":
            "rnb_tpu.models.r2p1d.model.R2P1DVideoPathIterator",
        "fault_plan": {"faults": [
            {"step": 0, "kind": "permanent", "request_ids": [1, 3]}]},
        "pipeline": [
            {"model": "rnb_tpu.models.r2p1d.model.R2P1DLoader",
             "queue_groups": [{"devices": [0]}],
             "max_clips": 2, "consecutive_frames": 2, "prefetch": 2,
             "num_clips_population": [1, 2], "weights": [1, 1],
             "num_warmups": 0},
        ],
    }
    path = _write_config(tmp_path, cfg)
    res = run_benchmark(path, mean_interval_ms=0, num_videos=8,
                        queue_size=50, log_base=str(tmp_path / "logs"),
                        print_progress=False)
    assert res.termination_flag == \
        TerminationFlag.TARGET_NUM_VIDEOS_REACHED
    assert res.num_failed == 2
    from rnb_tpu.decode.native import DecodePool, native_available
    if native_available() and DecodePool._shared is not None:
        # every submitted ticket was waited or discarded
        assert DecodePool._shared._pending == {}


# -- malformed inputs at the decoder layer ----------------------------

def _contained(exc_info):
    return classify_error(exc_info.value) is not FATAL


def test_numpy_y4m_malformed_errors(tmp_path):
    from rnb_tpu.decode import Y4MDecoder, write_y4m
    dec = Y4MDecoder()
    bad_magic = str(tmp_path / "bad.y4m")
    with open(bad_magic, "wb") as f:
        f.write(b"JUNKJUNKJUNK\n" * 4)
    with pytest.raises(CorruptVideoError):
        dec.num_frames(bad_magic)

    # truncated inside the first FRAME marker line
    good = str(tmp_path / "good.y4m")
    frames = np.zeros((2, 16, 16, 3), dtype=np.uint8)
    write_y4m(good, frames, colorspace="420")
    data = open(good, "rb").read()
    header_end = data.index(b"\n") + 1
    trunc = str(tmp_path / "trunc.y4m")
    with open(trunc, "wb") as f:
        f.write(data[:header_end + 3])  # "FRA"
    with pytest.raises(CorruptVideoError):
        dec.num_frames(trunc)

    # a header lying about geometry (payload shorter than one frame)
    lying = str(tmp_path / "lying.y4m")
    with open(lying, "wb") as f:
        f.write(b"YUV4MPEG2 W64 H64 C420\nFRAME\n")
        f.write(b"\x00" * (64 * 64 * 3 // 2))  # exactly one frame...
    data = open(lying, "rb").read()
    with open(lying, "wb") as f:
        f.write(data[:-100])  # ...now truncated mid-payload
    # count floors to 0; any requested clip start is an error path,
    # and whatever surfaces must be contained, never fatal
    with pytest.raises(Exception) as ei:
        dec.decode_clips(lying, [0], consecutive_frames=1,
                         width=16, height=16)
    assert _contained(ei)


def test_mjpeg_malformed_errors(tmp_path):
    from rnb_tpu.decode import MjpegPILDecoder, write_mjpeg
    dec = MjpegPILDecoder()
    garbage = str(tmp_path / "garbage.mjpg")
    with open(garbage, "wb") as f:
        f.write(b"\x00\x01\x02 not a jpeg at all" * 10)
    with pytest.raises(CorruptVideoError):
        dec.num_frames(garbage)

    # a single frame truncated mid-entropy: the scanner finds no
    # complete frame -> classified, not a PIL crash
    good = str(tmp_path / "good.mjpg")
    frames = np.random.default_rng(1).integers(
        0, 256, (1, 16, 16, 3), dtype=np.uint8)
    write_mjpeg(good, frames)
    data = open(good, "rb").read()
    trunc = str(tmp_path / "trunc.mjpg")
    with open(trunc, "wb") as f:
        f.write(data[: int(len(data) * 0.6)])
    with pytest.raises(CorruptVideoError):
        dec.num_frames(trunc)


def test_native_malformed_errors(tmp_path):
    from rnb_tpu.decode.native import NativeY4MDecoder, native_available
    if not native_available():
        pytest.skip("native decode library not built")
    dec = NativeY4MDecoder(use_pool=False)
    bad = str(tmp_path / "bad.y4m")
    with open(bad, "wb") as f:
        f.write(b"JUNKJUNKJUNK\n" * 4)
    with pytest.raises(Exception) as ei:
        dec.num_frames(bad)
    assert _contained(ei)
    # vanished file: the native probe's I/O failure is transient
    with pytest.raises(TransientDecodeError):
        dec.num_frames(str(tmp_path / "nope.y4m"))
    garbage_mjpg = str(tmp_path / "garbage.mjpg")
    with open(garbage_mjpg, "wb") as f:
        f.write(b"\x00\x01\x02 not a jpeg" * 16)
    with pytest.raises(Exception) as ei:
        dec.num_frames(garbage_mjpg)
    assert _contained(ei)


# -- TimeCard / summary plumbing --------------------------------------

def test_timecard_status_fork_merge():
    from rnb_tpu.telemetry import TimeCard
    tc = TimeCard(1)
    assert tc.status == "ok"
    tc.record("a")
    forks = [tc.fork(0), tc.fork(1)]
    forks[1].record("b")
    forks[0].record("b")
    forks[0].mark_failed("corrupt-video")
    merged = TimeCard.merge(forks)
    assert merged.status == "failed"
    assert merged.failure_reason == "corrupt-video"
    tc2 = TimeCard(2)
    tc2.mark_shed("filename_queue")
    assert tc2.status == "shed"


def test_summary_fault_counters_and_trailer():
    import io

    from rnb_tpu.telemetry import TimeCard, TimeCardSummary
    s = TimeCardSummary()
    tc = TimeCard(0)
    tc.record("a"); tc.record("b")  # noqa: E702
    tc.add_device("cpu:0")
    s.register(tc)
    assert s.faults_line() is None  # fault-free: byte-stable schema
    s.note_failure("corrupt-video")
    s.note_retries(2)
    s.note_shed()
    line = s.faults_line()
    assert line.startswith("# faults num_failed=1 num_shed=1 "
                           "num_retries=2")
    assert "reason:corrupt-video=1" in line
    buf = io.StringIO()
    s.save_full_report(buf)
    text = buf.getvalue()
    assert text.splitlines()[-1] == line
    # latencies exclude the faulted accounting entirely
    assert len(s.latencies_ms(0)) == 1
