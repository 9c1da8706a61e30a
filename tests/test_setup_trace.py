"""Set-up's record (PR 51): the launcher keeps ``setup.*`` spans from
``run_benchmark``'s first line to the start barrier, whatever the
``trace`` key says, and behind the barrier the program is what it was.

Three runs on the CPU, each once a module: a toy token pipeline
(``TokenLoader`` -> ``Batcher`` -> ``PackedPrefill`` over a toy
Nemotron-H recipe), the toy R(2+1)D pipeline of
``test_pipeline_r2p1d.py``, and the tiny pipeline of
``pipeline_helpers`` with the ``trace`` key set. The client asks its
path iterator for the first request behind the barrier:
``ProbingPathIterator`` keeps what it sees there.
"""

import json
import logging
import os
import re

import numpy as np
import pytest

from rnb_tpu import trace
from rnb_tpu.telemetry import TRACE_EVENT_REGISTRY
from rnb_tpu.trace import setup_account, validate_trace
from tests import pipeline_helpers

REGISTERED = re.compile("^(?:%s)$" % "|".join(
    re.escape(spec.pattern).replace(re.escape("{step}"), r"\d+")
    for spec in TRACE_EVENT_REGISTRY))

#: the toy widths: 14 blocks of the published pattern (scan, experts,
#: attention), served at two row buckets
from tests.test_nemotron_h import HELD, TOY  # noqa: E402

class Run:
    """One ``run_benchmark`` with what was seen around it."""

    def __init__(self, tmp, name, cfg, paths, num_videos):
        from rnb_tpu.benchmark import run_benchmark
        path = os.path.join(str(tmp), name + ".json")
        with open(path, "w") as f:
            json.dump(dict(cfg, video_path_iterator="tests.pipeline_helpers."
                                                    "ProbingPathIterator"), f)
        pipeline_helpers.PROBE_PATHS[:] = paths
        del pipeline_helpers.PROBED[:]
        compilations = []

        class Keep(logging.Handler):
            def emit(self, record):
                compilations.append(record.getMessage())

        # the stages compile in their runners' threads, where a
        # `jax.log_compiles` of this thread does not reach: the
        # messages are logged at DEBUG there
        handler = Keep(level=logging.DEBUG)
        loggers = [logging.getLogger(n) for n in (
            "jax._src.dispatch", "jax._src.interpreters.pxla",
            "jax._src.compiler")]
        levels = [logger.level for logger in loggers]
        for logger in loggers:
            logger.addHandler(handler)
            logger.setLevel(logging.DEBUG)
        self.listeners_before = pipeline_helpers.listener_counts()
        try:
            self.result = run_benchmark(
                path, mean_interval_ms=0, num_videos=num_videos,
                queue_size=50, log_base=os.path.join(str(tmp), "logs"),
                print_progress=False, job_id=name)
        finally:
            for logger, level in zip(loggers, levels):
                logger.removeHandler(handler)
                logger.setLevel(level)
        self.listeners_after = pipeline_helpers.listener_counts()
        self.active_after = trace.ACTIVE
        self.probed = list(pipeline_helpers.PROBED)
        #: backend compilations by program name, as PR 37's test
        #: counts them (tests/test_r2p1d_scopes.py)
        self.compiled = [m.split("Finished XLA compilation of ")[1]
                         .split(" in ")[0] for m in compilations
                         if m.startswith("Finished XLA compilation of ")]
        self.events = self.result.setup["events"]
        with open(os.path.join(self.result.log_dir, "log-meta.txt")) as f:
            self.meta = f.read()

    def names(self, thread=None):
        return [e[0] for e in self.events if thread in (None, e[3])]

    def setup_line(self):
        line = [l for l in self.meta.splitlines()
                if l.startswith("Setup: ")]
        assert len(line) == 1
        return json.loads(line[0].split(":", 1)[1])


@pytest.fixture(scope="module")
def token_run(tmp_path_factory):
    from rnb_tpu.models.nemotron_h import checkpoint
    tmp = tmp_path_factory.mktemp("token")
    recipe = str(tmp / "toy.recipe.json")
    checkpoint.save_recipe(recipe, TOY, 11, HELD)
    rng = np.random.default_rng(0)
    prompts = []
    for i in range(6):
        prompts.append(str(tmp / ("prompt%d.npy" % i)))
        np.save(prompts[-1], rng.integers(
            0, 256, size=int(rng.integers(8, 100)), dtype=np.int32))
    cfg = {"pipeline": [
        {"model": "rnb_tpu.models.token_stages.TokenLoader",
         "queue_groups": [{"devices": [-1], "out_queues": [0]}],
         "num_shared_tensors": 8, "max_rows": 8, "chunk": 16},
        {"model": "rnb_tpu.batcher.Batcher",
         "queue_groups": [{"devices": [-1], "in_queue": 0,
                           "out_queues": [1]}],
         "num_shared_tensors": 4, "batch": 8, "segments": True,
         "shapes": [[8, 16], [8]], "row_buckets": [4, 8]},
        {"model": "rnb_tpu.models.token_stages.PackedPrefill",
         "queue_groups": [{"devices": [0], "in_queue": 1}],
         "max_rows": 8, "chunk": 16, "row_buckets": [4, 8],
         "num_warmups": 1, "sample_every": 5, "samples": 2,
         "ckpt_path": recipe}]}
    return Run(tmp, "token", cfg, prompts, 12)


@pytest.fixture(scope="module")
def r2p1d_run(tmp_path_factory):
    # a class count no other test compiles: nothing comes out of the
    # appliers' cache, so the compilations counted are this run's
    cfg = {"pipeline": [
        {"model": "rnb_tpu.models.r2p1d.model.R2P1DLoader",
         "queue_groups": [{"devices": [0], "out_queues": [0]}],
         "num_shared_tensors": 8, "max_clips": 2, "consecutive_frames": 2,
         "num_clips_population": [1, 2], "weights": [3, 1],
         "num_warmups": 1},
        {"model": "rnb_tpu.models.r2p1d.model.R2P1DRunner",
         "queue_groups": [{"devices": [1], "in_queue": 0}],
         "start_index": 1, "end_index": 5, "num_classes": 13,
         "layer_sizes": [1, 1, 1, 1], "max_rows": 2,
         "row_buckets": [1, 2], "consecutive_frames": 2,
         "num_warmups": 1}]}
    return Run(tmp_path_factory.mktemp("r2p1d"), "r2p1d", cfg,
               ["synth://kinetics/video-%04d" % i for i in range(8)], 4)


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    cfg = {"trace": {"enabled": True, "sample_hz": 50},
           "pipeline": [
               {"model": "tests.pipeline_helpers.TinyLoader",
                "queue_groups": [{"devices": [0], "out_queues": [0]}],
                "num_shared_tensors": 4},
               {"model": "tests.pipeline_helpers.TinySink",
                "queue_groups": [{"devices": [1], "in_queue": 0}]}]}
    return Run(tmp_path_factory.mktemp("traced"), "traced", cfg,
               ["video-%d" % i for i in range(30)], 30)


RUNS = ("token_run", "r2p1d_run", "traced_run")


@pytest.fixture(params=RUNS)
def run(request):
    return request.getfixturevalue(request.param)


# -- the record -----------------------------------------------------------


def test_the_run_served(run):
    assert run.result.termination_flag == 0
    assert run.probed, "the client asked for no path"


def test_the_record_closes(run):
    """Along the instance built last, the phases add up to the time
    from run_benchmark's first line to the barrier's release."""
    record = run.result.setup
    assert record["entered"] <= record["run_start"] < record["released"]
    account = setup_account(
        [(e[0], "X" if e[2] or e[0] != "setup.entered" else "i", e[1], e[2],
          e[3], None, e[4]) for e in run.events],
        record["run_start"], record["released"])
    total = record["released"] - record["run_start"]
    assert account["total"] == pytest.approx(total, abs=1e-5)
    phases = [k for k in account if k not in ("total", "instance")]
    assert sorted(phases) == sorted(
        ["launch", "barrier", "other"] + list(set(
            trace.SETUP_PHASES.values())))
    assert sum(account[k] for k in phases) == pytest.approx(total, abs=1e-3)
    assert all(account[k] >= -1e-6 for k in phases), account
    # ... and it is the line the launcher wrote
    assert run.setup_line() == account
    # the run span is the whole, the launch span its head
    at = {e[0]: e for e in run.events if e[3] == "MainThread"}
    assert at["setup.run"][1] == record["run_start"]
    assert at["setup.run"][2] == pytest.approx(total, abs=1e-9)
    assert at["setup.launch"][1] == record["run_start"]
    assert 0 < at["setup.launch"][2] <= total
    assert at["setup.entered"][1:3] == (record["entered"], 0.0)


def test_every_emitted_name_is_registered(run):
    names = set(run.names())
    assert names and all(n.startswith("setup.") for n in names)
    assert [n for n in names if not REGISTERED.match(n)] == []


def test_warmup_is_the_construct_spans_duration(run):
    by_step = {}
    for name, _t0, dur, thread, counts in run.events:
        if name.endswith(".construct"):
            step = "step" + name.split(".")[1][1:]
            by_step[step] = by_step.get(step, 0.0) + dur
            assert thread == "runner-s%s-g0-i%d" % (step[4:],
                                                    counts["instance"])
            assert counts["device"]
    assert run.result.warmup_s == {k: round(v, 3)
                                   for k, v in by_step.items()}
    line = [l for l in run.meta.splitlines() if l.startswith("Warmup: ")]
    assert json.loads(line[0].split(":", 1)[1]) == run.result.warmup_s


def test_setup_trace_json_validates_and_holds_the_record(run):
    path = os.path.join(run.result.log_dir, "setup-trace.json")
    assert validate_trace(path) == []
    with open(path) as f:
        doc = json.load(f)
    written = [e["name"] for e in doc["traceEvents"]
               if e["ph"] in ("X", "i") and e["name"].startswith("setup.")]
    assert sorted(written) == sorted(run.names())
    # nothing of the served window is in it
    released = run.result.setup["released"]
    base = doc["otherData"]["t_base_epoch_s"]
    assert all(base + e["ts"] / 1e6 <= released + 1e-6
               for e in doc["traceEvents"] if e["ph"] != "M")


# -- the stages' spans ----------------------------------------------------


def test_the_final_token_stage_names_its_phases(token_run):
    names = token_run.names("runner-s2-g0-i0")
    assert names.count("setup.s2.construct") == 1
    assert names.count("setup.s2.weights") == 1
    for kind in ("program", "scopes", "first_call"):
        assert names.count("setup.s2.%s" % kind) == 2, kind
    rows = [e[4]["rows"] for e in token_run.events
            if e[0] == "setup.s2.program"]
    assert rows == [4, 8]
    # the stages in front of it build in threads of their own
    assert token_run.names("runner-s0-g0-i0") == ["setup.s0.construct"]
    assert token_run.names("runner-s1-g0-i0") == ["setup.s1.construct"]
    assert token_run.setup_line()["instance"] == "runner-s2-g0-i0"


def test_jax_own_spans_nest_under_the_stage(token_run):
    """A program is traced, lowered and compiled once, on the thread
    that builds the stage, inside its program span."""
    spans = {e[0]: [] for e in token_run.events}
    for e in token_run.events:
        spans[e[0]].append(e)
    programs = spans["setup.s2.program"]
    for kind in ("trace", "lower", "compile"):
        mine = [e for e in spans["setup.jax." + kind]
                if e[4]["fun_name"].endswith("apply")
                or e[4]["fun_name"] == "jit(apply)"]
        assert len(mine) == 2, (kind, [e[4] for e in
                                       spans["setup.jax." + kind]])
        for event, program in zip(mine, programs):
            assert event[3] == "runner-s2-g0-i0"
            assert program[1] <= event[1]
            assert event[1] + event[2] <= program[1] + program[2]
    for e in spans["setup.jax.compile"]:
        assert e[4]["cache_hit"] in (0, 1)
        assert ("retrieval_s" in e[4]) == bool(e[4]["cache_hit"])
    # a trace inside a trace or a lowering has no span of its own
    lowering = sorted((e[1], e[1] + e[2]) for e in
                      spans["setup.jax.trace"] + spans["setup.jax.lower"]
                      if e[3] == "runner-s2-g0-i0")
    assert all(a[1] <= b[0] for a, b in zip(lowering, lowering[1:]))


def test_no_program_compiles_twice_because_of_a_span(token_run, r2p1d_run):
    """Counted as PR 37 counts (the backend's own log): one executable
    a row bucket."""
    assert token_run.compiled.count("jit(apply)") == 2, token_run.compiled
    assert r2p1d_run.compiled.count("jit(apply)") == 2, r2p1d_run.compiled
    for run in (token_run, r2p1d_run):
        recorded = [e[4]["fun_name"] for e in run.events
                    if e[0] == "setup.jax.compile"]
        # the record holds every compilation of set-up, and only those
        assert sorted(recorded) == sorted(run.compiled[:len(recorded)])
        assert run.result.compile_signatures[
            "step%d" % (2 if run is token_run else 1)]["steady_new"] == 0


def test_the_r2p1d_stages_name_their_phases(r2p1d_run):
    final = r2p1d_run.names("runner-s1-g0-i0")
    assert final.count("setup.s1.construct") == 1
    assert final.count("setup.s1.weights") == 1
    for kind in ("program", "first_call", "scopes"):
        assert final.count("setup.s1.%s" % kind) == 2, kind
    loader = r2p1d_run.names("runner-s0-g0-i0")
    assert loader.count("setup.s0.program") \
        == loader.count("setup.s0.first_call") >= 1
    assert "setup.s0.weights" not in loader


# -- behind the barrier ---------------------------------------------------


def test_behind_the_barrier_the_program_is_as_it_was(run):
    """``trace.ACTIVE`` is what the configuration asked for, a span on
    the hot path is the shared no-op (or the configured Tracer's), and
    ``jax.monitoring`` holds the listeners it held before the run."""
    configured = run.result.trace_events > 0
    for seen in run.probed:
        assert seen["listeners"] == run.listeners_before
        if configured:
            assert isinstance(seen["active"], trace.Tracer)
            assert isinstance(seen["span"], trace._Span)
        else:
            assert seen["active"] is None
            assert seen["span"] is trace._NULL
    assert run.listeners_after == run.listeners_before
    assert run.active_after is None


def test_an_untraced_run_writes_no_trace_json(token_run):
    assert token_run.result.trace_events == 0
    assert not os.path.exists(os.path.join(token_run.result.log_dir,
                                           "trace.json"))
    assert "Trace:" not in token_run.meta


def test_with_the_trace_key_trace_json_holds_the_set_up_spans_too(
        traced_run):
    path = os.path.join(traced_run.result.log_dir, "trace.json")
    assert validate_trace(path) == []
    with open(path) as f:
        names = [e["name"] for e in json.load(f)["traceEvents"]]
    for name in ("setup.entered", "setup.run", "setup.launch",
                 "setup.s0.construct", "setup.s1.construct"):
        assert names.count(name) == 1, name
    assert "exec1.model_call" in names
    assert traced_run.result.trace_events == len(
        [n for n in names if n not in ("request", "process_name",
                                       "thread_name")])


def test_a_run_that_fails_before_the_barrier_leaves_no_listener(tmp_path):
    from rnb_tpu.benchmark import run_benchmark
    before = pipeline_helpers.listener_counts()
    path = str(tmp_path / "broken.json")
    with open(path, "w") as f:
        json.dump({"video_path_iterator":
                   "tests.pipeline_helpers.CountingPathIterator",
                   "pipeline": [
                       {"model": "tests.pipeline_helpers.TinyLoader",
                        "queue_groups": [{"devices": [99],
                                          "out_queues": [0]}],
                        "num_shared_tensors": 4},
                       {"model": "tests.pipeline_helpers.TinySink",
                        "queue_groups": [{"devices": [1],
                                          "in_queue": 0}]}]}, f)
    with pytest.raises(Exception):
        run_benchmark(path, mean_interval_ms=0, num_videos=2,
                      queue_size=10, log_base=str(tmp_path / "logs"),
                      print_progress=False)
    assert pipeline_helpers.listener_counts() == before
    trace.ACTIVE = None


def test_the_entry_points_stamp_is_taken_once():
    """``enable_compilation_cache()`` stamps its first call; the next
    job takes the stamp and a later job stamps its own."""
    from rnb_tpu import benchmark
    benchmark._ENTERED = None
    benchmark.enable_compilation_cache()
    first = benchmark._ENTERED
    benchmark.enable_compilation_cache()
    assert first is not None and benchmark._ENTERED == first
    setup = benchmark._Setup()
    assert setup.entered == first <= setup.run_start
    setup.open(None)
    try:
        assert benchmark._ENTERED is None
        assert isinstance(trace.ACTIVE, trace.Tracer)
    finally:
        setup.release()
    assert trace.ACTIVE is None and setup.released is not None
    setup.release()  # the way out after the barrier: nothing left to do
    assert benchmark._Setup().entered > first
