"""Set-up's account (``benchmarks/setup_account.py``) on a record kept
from a chip run of a token cell (``recorded/setup.json``: the file the
reader wrote beside that run's tables), on records made by hand, and
through the one command on the CPU."""

import json
import os
import shutil
import types

import pytest

from benchmarks import manifest as mm
from benchmarks import setup_account as sa

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded", "setup.json")
NAMES = sa.SECONDS + (sa.COUNT,)


def kept():
    with open(RECORDED) as f:
        return json.load(f)


def stage(step, t0, dur, inside=()):
    """A stage instance's spans: its constructor and what lies in it."""
    thread = "runner-s%d-g0-i0" % step
    return [("setup.s%d.construct" % step, t0, dur, thread,
             {"instance": 0, "device": "tpu:0"})] + [
        (name if name.startswith("setup.jax.")
         else "setup.s%d.%s" % (step, name), a, b, thread, counts)
        for name, a, b, counts in inside]


def record(*stages, entered=100.0, run_start=103.0, released=None):
    events = [e for s in stages for e in s]
    if released is None:
        released = max(e[1] + e[2] for e in events) + 0.002
    events += [("setup.entered", entered, 0.0, "MainThread", {}),
               ("setup.launch", run_start, 0.5, "MainThread", {}),
               ("setup.run", run_start, released - run_start,
                "MainThread", {})]
    return {"entered": entered, "run_start": run_start,
            "released": released, "events": events}


FINAL = stage(2, 103.6, 20.0, [
    ("weights", 103.7, 3.0, {}),
    ("setup.jax.trace", 103.8, 0.25, {"fun_name": "draw"}),
    ("setup.jax.lower", 104.1, 0.25, {"fun_name": "draw"}),
    ("setup.jax.compile", 104.4, 1.0, {"fun_name": "draw", "cache_hit": 0}),
    ("program", 107.0, 8.0, {"rows": 64}),
    ("setup.jax.trace", 107.0, 2.0, {"fun_name": "apply"}),
    ("setup.jax.lower", 109.0, 1.5, {"fun_name": "apply"}),
    ("setup.jax.compile", 110.5, 2.5,
     {"fun_name": "apply", "cache_hit": 1, "retrieval_s": 1.75}),
    ("scopes", 113.0, 0.5, {}),
    ("first_call", 113.5, 1.5, {}),
    ("setup.jax.compile", 113.75, 0.25, {"fun_name": "inner",
                                         "cache_hit": 0})])


# -- the record of a chip run ---------------------------------------------


def test_the_recorded_run_closes_and_names_nine_tenths():
    saved = kept()
    account = sa.reduce(saved["record"], saved["process_start"],
                        saved["window_start"])
    assert "problem" not in account
    metrics = account["metrics"]
    assert all(metrics[name] is not None for name in NAMES)
    assert all(metrics[name] >= 0 for name in NAMES), metrics
    setup_s = saved["window_start"] - saved["process_start"]
    assert sum(metrics[n] for n in sa.SECONDS) + account["ramp_s"] \
        == pytest.approx(setup_s, abs=sa.CLOSES_TO_S)
    assert account["setup_s"] == setup_s
    assert metrics["setup_unnamed_s"] <= 0.1 * setup_s
    # a token cell: the final stage is built last, a program a bucket
    assert account["critical"]["construct"] == "setup.s2.construct"
    assert len(account["programs"]) >= 2
    assert all(row["rows"] for row in account["programs"])
    assert metrics[sa.COUNT] == int(metrics[sa.COUNT])
    # what the reader wrote then is what it reduces to now
    assert {k: saved["metrics"][k] for k in NAMES} == metrics
    text = sa.describe(account)
    assert "DOES NOT CLOSE" not in text and "rows " in text


# -- records made by hand -------------------------------------------------


def test_self_times_and_the_sum():
    made = record(stage(0, 103.5, 0.1), FINAL)
    account = sa.reduce(made, process_start=93.0, window_start=127.602)
    metrics = account["metrics"]
    assert metrics["setup_runtime_s"] == 7.0
    assert metrics["setup_inputs_s"] == 3.0
    # the draw's trace, lowering and compile are not the weights'
    assert metrics["setup_weights_s"] == pytest.approx(3.0 - 1.5)
    assert metrics["setup_lower_s"] == pytest.approx(0.5 + 3.5)
    # a compile inside a first call is a compile
    assert metrics["setup_compile_s"] == pytest.approx(1.0 + 2.5 + 0.25)
    assert metrics["setup_first_call_s"] == pytest.approx(1.25)
    assert account["ramp_s"] == pytest.approx(4.0)
    assert metrics["setup_unnamed_s"] == pytest.approx(
        34.602 - 7 - 3 - 1.5 - 4 - 3.75 - 1.25 - 4.0)
    assert metrics[sa.COUNT] == 2.0
    assert sum(metrics[n] for n in sa.SECONDS) + account["ramp_s"] \
        == pytest.approx(34.602, abs=1e-9)
    (row,) = account["programs"]
    assert row["rows"] == 64 and row["dur"] == 8.0
    assert (row["trace"], row["lower"], row["scopes"]) == (2.0, 1.5, 0.5)
    assert row["compile"] == pytest.approx(2.75)
    assert row["first_call"] == pytest.approx(1.25)
    assert (row["compiled"], row["cache_hits"]) == (1, 1)
    assert row["retrieval_s"] == 1.75
    assert row["self"] == pytest.approx(0.0)


def test_of_two_stages_that_overlap_the_later_ending_is_followed():
    slow_loader = stage(0, 103.5, 25.0, [
        ("program", 104.0, 5.0, {"rows": 8}),
        ("first_call", 104.0, 5.0, {})])
    account = sa.reduce(record(slow_loader, FINAL), 93.0, 140.0)
    assert account["critical"]["thread"] == "runner-s0-g0-i0"
    assert account["metrics"]["setup_first_call_s"] == 5.0
    assert account["metrics"]["setup_weights_s"] == 0.0
    # the other instance's compilations are still the process's
    assert account["metrics"][sa.COUNT] == 2.0
    assert account["metrics"]["setup_compile_s"] == 0.0
    assert [i["thread"] for i in account["instances"]] \
        == ["runner-s0-g0-i0", "runner-s2-g0-i0"]
    followed = sa.reduce(record(FINAL, stage(0, 103.5, 1.0)), 93.0, 140.0)
    assert followed["critical"]["thread"] == "runner-s2-g0-i0"


def test_without_jax_spans_the_three_jax_metrics_read_none():
    plain = [e for e in FINAL if not e[0].startswith("setup.jax.")]
    account = sa.reduce(record(plain), 93.0, 127.602)
    metrics = account["metrics"]
    for name in ("setup_lower_s", "setup_compile_s", sa.COUNT):
        assert metrics[name] is None
    assert metrics["setup_weights_s"] == pytest.approx(3.0)
    assert metrics["setup_first_call_s"] == pytest.approx(1.5)
    assert metrics["setup_runtime_s"] == 7.0
    assert sum(metrics[n] or 0.0 for n in sa.SECONDS) + account["ramp_s"] \
        == pytest.approx(34.602, abs=1e-9)


def test_a_record_out_of_order_reads_none_and_says_why():
    account = sa.reduce(record(FINAL), process_start=101.0,
                        window_start=130.0)  # entered before the process
    assert set(account["metrics"].values()) == {None}
    assert "T_PROCESS" in account["problem"]
    assert "DOES NOT CLOSE" in sa.describe(account)


class Facts:
    """What a reader is handed: the result, the schedule's window."""

    def __init__(self, result, window_start=127.602):
        self.result = result
        self.schedule = types.SimpleNamespace(
            window=(window_start, window_start + 30.0))


@pytest.mark.parametrize("result", [
    types.SimpleNamespace(log_dir=None),                # a parent of PR 51
    types.SimpleNamespace(log_dir=None, setup={}),
    types.SimpleNamespace(log_dir=None, setup={
        "entered": 1.0, "run_start": 2.0, "released": 3.0, "events": []}),
    types.SimpleNamespace(log_dir=None,
                          setup=record(released=104.0)),  # no constructor
], ids=["no_field", "empty", "no_events", "no_constructor"])
def test_no_record_reads_none_everywhere_and_raises_nothing(result):
    facts = Facts(result)
    for name in NAMES:
        module = mm.load_layer_metric(name)
        assert module.read(facts) is None
    assert sa.of(facts) is None


def test_the_readers_share_one_reduction_and_write_it_down(
        tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(sa, "t_process", lambda: 93.0)
    facts = Facts(types.SimpleNamespace(
        log_dir=str(tmp_path), setup=record(stage(0, 103.5, 0.1), FINAL)))
    values = {name: mm.load_layer_metric(name).read(facts)
              for name in NAMES}
    assert values["setup_weights_s"] == pytest.approx(1.5)
    assert values[sa.COUNT] == 2.0
    assert capsys.readouterr().err.count("[bench] setup:") == 1
    with open(tmp_path / "setup.json") as f:
        saved = json.load(f)
    assert saved["metrics"] == values
    assert saved["process_start"] == 93.0
    # ... and the command prints the same account from the directory
    assert sa.main([str(tmp_path)]) == 0
    printed = capsys.readouterr().out
    assert "setup_s 34.602 = runtime 7.000 + inputs 3.000" in printed
    assert "rows 64: 8.000 s" in printed
    assert sa.main([str(tmp_path / "nothing")]) == 1


def test_t_process_is_the_running_commands(monkeypatch):
    import sys
    monkeypatch.setitem(sys.modules, "__main__",
                        types.SimpleNamespace(T_PROCESS=12.5))
    assert sa.t_process() == 12.5
    monkeypatch.setitem(sys.modules, "__main__", types.SimpleNamespace())
    monkeypatch.setitem(sys.modules, "benchmarks.run",
                        types.SimpleNamespace(T_PROCESS=3.25))
    assert sa.t_process() == 3.25
    monkeypatch.delitem(sys.modules, "benchmarks.run")
    assert sa.t_process() is None


# -- the manifest's eight entries -----------------------------------------


def test_the_eight_entries_move_setup_s_in_every_cell():
    manifest = mm.load()
    cells = [w["name"] for w in manifest["workloads"]]
    last = manifest["per_layer"][-len(NAMES):]
    assert [m["name"] for m in last] == list(NAMES)
    moving = [m for m in manifest["per_layer"] if m["moves"] == "setup_s"]
    assert moving == last
    for metric in last:
        assert metric["workloads"] == cells
        assert metric["layer"] == "set-up" and metric["better"] == "lower"
        assert metric["unit"] == ("programs" if metric["name"] == sa.COUNT
                                  else "s")
        assert metric["source"] == ("program_counter"
                                    if metric["name"] == sa.COUNT
                                    else "program_span")


# -- through the one command, on the CPU ----------------------------------


def test_a_traced_dry_run_reports_the_eight_and_they_close(tmp_path):
    """The fixture manifest is not edited: a copy lists the metrics."""
    from test_dry_run import FIXTURE, run
    shutil.copytree(FIXTURE, tmp_path / "copy")
    root = tmp_path / "copy"
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["per_layer"] += [
        dict(m, workloads=["tiny.poisson"])
        for m in mm.load()["per_layer"] if m["moves"] == "setup_s"]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    done = run(str(root / "BENCHMARK.json"), "tiny.poisson", "--platform",
               "cpu", "--out", str(tmp_path / "out"), trace=1)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    values = {n: line["metrics"][n]["value"] for n in NAMES}
    with open(tmp_path / "out" / "run" / "setup.json") as f:
        saved = json.load(f)
    assert saved["metrics"] == values
    assert sum(values[n] for n in sa.SECONDS) + saved["ramp_s"] \
        == pytest.approx(line["notes"]["setup_s"], abs=sa.CLOSES_TO_S)
    assert line["metrics"]["setup_unnamed_s"]["unit"] == "s"
    assert line["metrics"][sa.COUNT]["unit"] == "programs"
    assert saved["critical"]["construct"] == "setup.s1.construct"
    # the program's own file gives the program's part of the account
    account = sa.reduce(sa.record_of_trace(
        str(tmp_path / "out" / "run" / "setup-trace.json")))
    for name in ("setup_weights_s", "setup_lower_s", "setup_compile_s",
                 "setup_first_call_s"):
        assert account["metrics"][name] == pytest.approx(values[name],
                                                         abs=1e-4)
    assert account["metrics"]["setup_runtime_s"] is None
    assert account["metrics"][sa.COUNT] == values[sa.COUNT]
