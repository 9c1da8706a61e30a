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
import threading

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


#: the two threads a token stage's set-up lives on since PR 63: the
#: runner's, which builds the stage, and the constructor's one worker
BUILDER, LOADER = "runner-s2-g0-i0", "prefill-load"


def inside(event, outer) -> bool:
    return outer[1] <= event[1] \
        and event[1] + event[2] <= outer[1] + outer[2]


def test_the_final_token_stage_names_its_phases(token_run):
    names = token_run.names(BUILDER)
    assert names.count("setup.s2.construct") == 1
    assert names.count("setup.s2.weights") == 1
    assert names.count("setup.s2.program") == 2
    assert names.count("setup.s2.load_wait") == 1
    # what turns a lowered program into a warmed executable is the
    # worker's, a load span a row bucket
    loader = token_run.names(LOADER)
    for kind in ("load", "scopes", "first_call"):
        assert loader.count("setup.s2.%s" % kind) == 2, kind
        assert "setup.s2.%s" % kind not in names, kind
    assert not [n for n in loader if n.startswith("setup.s2.")
                and n.split(".")[2] not in ("load", "scopes", "first_call")]
    for kind in ("program", "load"):
        rows = [e[4]["rows"] for e in token_run.events
                if e[0] == "setup.s2.%s" % kind]
        assert rows == [4, 8], kind
    # the stages in front of it build in threads of their own
    assert token_run.names("runner-s0-g0-i0") == ["setup.s0.construct"]
    assert token_run.names("runner-s1-g0-i0") == ["setup.s1.construct"]
    assert token_run.setup_line()["instance"] == BUILDER


def test_jax_own_spans_nest_under_the_stage(token_run):
    """A program is traced and lowered once on the thread that builds
    the stage, inside its program span, and compiled once on the
    worker, inside its load span, with its scopes and first call."""
    spans = {e[0]: [] for e in token_run.events}
    for e in token_run.events:
        spans[e[0]].append(e)
    programs, loads = spans["setup.s2.program"], spans["setup.s2.load"]
    for kind, thread, outers in (("trace", BUILDER, programs),
                                 ("lower", BUILDER, programs),
                                 ("compile", LOADER, loads)):
        mine = [e for e in spans["setup.jax." + kind]
                if e[4]["fun_name"].endswith("apply")
                or e[4]["fun_name"] == "jit(apply)"]
        assert len(mine) == 2, (kind, [e[4] for e in
                                       spans["setup.jax." + kind]])
        for event, outer in zip(mine, outers):
            assert event[3] == thread and outer[3] == thread
            assert inside(event, outer)
    for kind in ("scopes", "first_call"):
        for event, load in zip(spans["setup.s2." + kind], loads):
            assert event[3] == LOADER and inside(event, load)
    for e in spans["setup.jax.compile"]:
        assert e[4]["cache_hit"] in (0, 1)
        assert ("retrieval_s" in e[4]) == bool(e[4]["cache_hit"])
    # a trace inside a trace or a lowering has no span of its own
    lowering = sorted((e[1], e[1] + e[2]) for e in
                      spans["setup.jax.trace"] + spans["setup.jax.lower"]
                      if e[3] == BUILDER)
    assert all(a[1] <= b[0] for a, b in zip(lowering, lowering[1:]))


def test_a_load_starts_behind_its_lowering_and_ends_before_the_join(
        token_run):
    """The pipeline's order: bucket i's load opens once its program span
    has closed, the loads follow each other on the one worker, and the
    constructor's wait closes behind the last of them; both threads'
    spans nest or follow, which the account's self times assume."""
    at = {kind: [e for e in token_run.events
                 if e[0] == "setup.s2.%s" % kind]
          for kind in ("program", "load", "load_wait", "construct")}
    for program, load in zip(at["program"], at["load"]):
        assert program[1] + program[2] <= load[1]
    first, second = at["load"]
    assert first[1] + first[2] <= second[1]
    (wait,), (construct,) = at["load_wait"], at["construct"]
    assert at["program"][-1][1] + at["program"][-1][2] <= wait[1]
    assert second[1] + second[2] <= wait[1] + wait[2]
    assert inside(wait, construct)
    for thread in (BUILDER, LOADER):
        mine = sorted((e[1], -e[2]) for e in token_run.events
                      if e[3] == thread and e[2])
        open_ = []
        for t0, neg in mine:
            while open_ and open_[-1] <= t0:
                open_.pop()
            assert not open_ or t0 - neg <= open_[-1] + 1e-9, thread
            open_.append(t0 - neg)


def test_load_and_load_wait_are_registered_names():
    patterns = {spec.pattern: spec for spec in TRACE_EVENT_REGISTRY}
    for kind in ("load", "load_wait"):
        spec = patterns["setup.s{step}.%s" % kind]
        assert spec.producer == "rnb_tpu/models/token_stages.py"
        assert REGISTERED.match("setup.s2.%s" % kind)
    # neither is a phase of the Setup: line: on the constructor's
    # thread the wait is `other`
    assert not [end for end in trace.SETUP_PHASES if "load" in end]


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


# -- the constructor's pipeline, on a stage made by hand -------------------


def toy_stage(tmp_path):
    from rnb_tpu.devices import DeviceSpec
    from rnb_tpu.models import token_stages
    from rnb_tpu.models.nemotron_h import checkpoint
    recipe = str(tmp_path / "toy.recipe.json")
    checkpoint.save_recipe(recipe, TOY, 11, HELD)
    return token_stages.PackedPrefill(
        DeviceSpec(-1), ckpt_path=recipe, max_rows=8, chunk=16,
        row_buckets=[4, 8])


def loaders():
    return [t for t in threading.enumerate() if t.name == LOADER]


def test_each_buckets_program_is_the_one_made_in_turn(tmp_path, monkeypatch):
    """What the worker loads is what ``jax.jit(apply).lower(...)
    .compile()`` gives a bucket at a time on one thread: the same text,
    in the buckets' order, and the scope table filled in that order."""
    import jax

    from rnb_tpu import hloscopes
    from rnb_tpu.models.token_stages import dispatch_meta
    jit, applied = jax.jit, []

    def keeping(fun, *args, **kwargs):
        if getattr(fun, "__name__", "") == "apply":
            applied.append(fun)
        return jit(fun, *args, **kwargs)

    monkeypatch.setattr(jax, "jit", keeping)
    stage = toy_stage(tmp_path)
    monkeypatch.undo()
    assert len(applied) == 2 and applied[0] is applied[1]
    assert list(stage._programs) == list(stage.row_buckets) == [4, 8]
    assert not loaders()
    scopes = {}
    for rows in stage.row_buckets:
        tokens = np.zeros((rows, 16), np.int32)
        meta = dispatch_meta((0, rows), np.full(rows, 16), rows, 16)
        text = jax.jit(applied[0]).lower(
            stage._params, stage._slots, tokens, meta).compile().as_text()
        assert stage._programs[rows].as_text() == text, rows
        scopes.update(hloscopes.scopes_of_hlo(text))
    assert stage.hlo_scopes == scopes
    assert list(stage.hlo_scopes) == list(scopes)


@pytest.mark.parametrize("bucket", [0, 1])
def test_what_the_worker_raises_the_constructor_raises(tmp_path,
                                                       monkeypatch, bucket):
    """The same error, from whichever bucket's load, with the worker
    gone and nothing loaded behind the failure."""
    from rnb_tpu import hloscopes

    class Broken(RuntimeError):
        pass

    raised, tables = [], []

    def scopes_of_hlo(text):
        tables.append(threading.current_thread().name)
        if len(tables) == bucket + 1:
            raised.append(Broken("bucket %d" % bucket))
            raise raised[0]
        return {}

    monkeypatch.setattr(hloscopes, "scopes_of_hlo", scopes_of_hlo)
    with pytest.raises(Broken) as caught:
        toy_stage(tmp_path)
    assert caught.value is raised[0]
    assert tables == [LOADER] * (bucket + 1)
    assert not loaders()


def test_what_the_lowering_raises_leaves_no_worker(tmp_path, monkeypatch):
    from rnb_tpu.models.nemotron_h import network

    def forward(*_args, **_kwargs):
        raise ZeroDivisionError("no stack")

    monkeypatch.setattr(network, "forward", forward)
    with pytest.raises(ZeroDivisionError, match="no stack"):
        toy_stage(tmp_path)
    assert not loaders()


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
