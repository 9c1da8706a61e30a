"""What a token family's serving path is held to, stated once: its cell
through the one benchmark command over a toy-width copy of its
configuration, the control script's arms over that copy, the final stage
serving it from its recipe, the parent failing on the cell before JAX
starts, and the toy copy passing the family file's own check. Not
collected: a family's ``tests/test_<family>_cell.py`` calls the three
expensive ones (one file is one worker's under ``--dist loadfile``), and
``tests/test_family_contract.py`` runs the two cheap ones over every
family.

What varies is one ``Family`` record, ``CONTRACT`` in the family's own
test module beside its toy widths. ``FAMILIES`` is the list of them: a
new family adds its name here, its record and a cell file.

Which runs of the command a family keeps (``Family.traces``). A traced
run starts the profiler and reads the manifest's ``per_layer`` readers;
an untraced one reads two, ``videos_per_s`` and ``setup_s``, which are
the harness's own and branch on no family (``benchmarks/run.py``).
Everything a family brings (``build``, ``prepare_inputs``, the schedule,
the three stages, the counters and ``log-meta.txt`` lines, the eight
samples, ``hlo-scopes.json``, ``check_outputs`` against the float32
reference, ``correct`` / ``failed`` / ``attempted``) runs in both, so
every family keeps the traced run and two keep the untraced one as well:
``deepseek_v2`` (experts held) and ``falcon_h1`` (dense). Beside them the
two generic readers are held by ``tests/harness/test_harness_dry_run``
(``test_result_line[tiny.bulk-0]``, ``[tiny.poisson-0]``) and by
``tests/test_setup_trace.py``. A new family does not copy the untraced
run.

The backlog. The bulk mix draws 1.7 x the toy copy's
``capacity_videos_per_chip_s`` x (4 s of ramp + 3 s of window) requests,
and the run drains every one of them after the window. A toy copy's
capacity is sized to what this CPU serves: about half the requests are
left when the window closes (``notes.backlog.left_share`` 0.52 to 0.57
over the nine on an idle machine, PR 57; a run is not ``correct`` under
0.05, and a loaded worker serves fewer, which only raises it). The
older copies' 500 drew 5,951 requests and left 0.81 to 0.87 of them to
drain, most of a run's minute; a shorter backlog is also served faster
(the client's enqueueing shares the interpreter with the stages), so
size a new copy by reading ``left_share`` off a run, not by a rule of
three."""

import dataclasses
import importlib
import json
import os
import re
import subprocess
import sys
from typing import Callable, Mapping, Optional, Tuple

import numpy as np
import pytest

from benchmarks import manifest as mm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the families, by the name of their package under ``rnb_tpu/models``,
#: of their file under ``benchmarks/families`` and of their
#: ``tests/test_<family>.py``
FAMILIES = ("nemotron_h", "deepseek_v2", "minicpm_sala", "qwen3_next",
            "exaone_moe", "keye_vl2", "kimi_linear", "falcon_h1",
            "dots3_note", "phi4_flash", "xing4")


@dataclasses.dataclass(frozen=True)
class Stage:
    """One dispatch for the final stage, and what it must show back."""
    #: the prompts' lengths (drawn from seed 2), packed in order
    lengths: Tuple[int, ...]
    row_buckets: Tuple[int, ...]
    #: each must stand in some name of ``stage.hlo_scopes``
    scopes: Tuple[str, ...]
    #: of the first sample's ``chosen``
    chosen_shape: Tuple[int, ...]
    #: how often the dispatch is served
    dispatches: int = 1
    rows: int = 8
    samples: int = 2
    #: (``Served``) -> None: the family's own counters, lines and fields
    also: Optional[Callable] = None


@dataclasses.dataclass(frozen=True)
class Control:
    """One run of ``scripts/prefill_control.py`` over the toy copy."""
    lengths: str
    #: the arms that must read outside the limit
    outside: Tuple[str, ...]
    #: (arm, reading) -> the interval it must lie in: what an arm that
    #: is free to pass must still report, and how far out a control lies
    reads: Mapping[Tuple[str, str], str] = dataclasses.field(
        default_factory=dict)
    #: ``--arms``, where the family's default arms are not the ones run
    arms: Optional[str] = None
    #: the family file's ``CONTROL_MAY_PASS``, where the copy held it
    may_pass: Optional[Tuple[str, ...]] = None
    #: (the script's last line) -> None: the family's own
    also: Optional[Callable] = None


@dataclasses.dataclass(frozen=True)
class Family:
    name: str
    #: the workload of ``BENCHMARK.json``
    cell: str
    #: the configuration's file, from the root of the repo
    real: str
    #: () -> the toy-width copy of that file
    toy_config: Callable[[], dict]
    #: ``checkpoint.save_recipe``'s arguments behind the path: the toy
    #: widths, the seed and, for a family that holds experts, which
    recipe: tuple
    #: what ``log-meta.txt`` must and must not hold after a run
    meta: Tuple[str, ...]
    meta_absent: Tuple[str, ...] = ()
    #: each must stand in some name of the run's ``hlo-scopes.json``
    scopes: Tuple[str, ...] = ()
    #: what a sample's file must hold, and the shape an array starts with
    sample_fields: Tuple[str, ...] = ()
    sample_shapes: Mapping[str, tuple] = dataclasses.field(
        default_factory=dict)
    #: a traced run's metrics and the interval each must lie in
    traced: Mapping[str, str] = dataclasses.field(default_factory=dict)
    #: what stands against the chip's peak, or comes from the device's
    #: trace, does not come from a CPU: no metric's name matches
    not_from_a_cpu: str = "roofline|util"
    #: ``--trace`` of the runs the family keeps (the module's docstring)
    traces: Tuple[int, ...] = (1,)
    #: the family file's ``build`` exits on a checkout without the family
    refuses_a_parent: bool = True
    stage: Optional[Stage] = None
    control: Optional[Control] = None


def record(name: str) -> Family:
    return importlib.import_module("test_" + name).CONTRACT


def within(value, interval: str) -> bool:
    """Does ``value`` lie in ``interval``, written as ``"(0, 100]"``."""
    lo, hi = (float(x) for x in interval[1:-1].split(","))
    return (lo < value if interval[0] == "(" else lo <= value) \
        and (value < hi if interval[-1] == ")" else value <= hi)


def python(*argv):
    """One of the repo's commands on the CPU: -> the finished process,
    which returned 0."""
    done = subprocess.run(
        [sys.executable, *argv], capture_output=True, text=True,
        timeout=900, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-3000:]
    return done


def last_line(done) -> dict:
    return json.loads(done.stdout.strip().splitlines()[-1])


# -- through the one benchmark command ------------------------------------


def toy_tree(tmp_path, real: str, toy_config: dict) -> str:
    """The real manifest's cell over a toy-width copy of its
    configuration: the same family, stages, mix and readers."""
    os.makedirs(tmp_path / "benchmarks" / "configs")
    with open(tmp_path / real, "w") as f:
        json.dump(toy_config, f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(mm.load(), f)
    return str(tmp_path / "BENCHMARK.json")


def run_the_cell(family: Family, trace: int, tmp_path) -> None:
    """One run of the benchmark command over the toy copy, held to what
    a CPU run can show."""
    out = tmp_path / "out"
    done = python(
        os.path.join(REPO, "benchmarks", "run.py"),
        "--manifest", toy_tree(tmp_path, family.real, family.toy_config()),
        "--workload", family.cell, "--seed", "3000000019", "--seconds", "3",
        "--trace", str(trace), "--platform", "cpu", "--out", str(out))
    line = last_line(done)
    assert line["correct"] is True and line["failed"] == 0, \
        done.stderr[-3000:]
    assert line["attempted"] > 0
    meta = (out / "run" / "log-meta.txt").read_text()
    for name in family.meta:
        assert name in meta, name
    for name in family.meta_absent:
        assert name not in meta, name
    samples = sorted((out / "run").glob("prefill-sample-*.npz"))
    assert len(samples) == 8
    with np.load(samples[0]) as sample:
        assert set(family.sample_fields) <= set(sample.files)
        for name, shape in family.sample_shapes.items():
            assert sample[name].shape[:len(shape)] == shape, name
    with open(out / "run" / "hlo-scopes.json") as f:
        scopes = list(json.load(f).values())
    for scope in family.scopes:
        assert any(scope in name + "/" for name in scopes), scope
    metrics = line["metrics"]
    if trace:
        for name, interval in family.traced.items():
            assert within(metrics[name]["value"], interval), \
                (name, metrics[name], interval)
        assert not [n for n in metrics
                    if re.search(family.not_from_a_cpu, n)]
    else:
        assert metrics["videos_per_s"]["value"] > 0
        assert metrics["setup_s"]["value"] > 0


def toy_copy_is_sound(family: Family) -> None:
    """The family file's own check, which ``benchmarks/run.py`` does not
    call: an unsound copy would fail its run only at the end."""
    assert mm.load_family(family.name).check_config(
        family.toy_config()) == []


def parent_fails(family: Family, tmp_path) -> None:
    """A checkout whose program lacks the family (the parent of the PR
    that brought it, given that PR's benchmark files): the family file's
    ``build`` says so and exits, no result line; and the parent's own
    manifest has no such cell: ``manifest.cell`` raises at once."""
    module = mm.load_family(family.name)
    os.makedirs(tmp_path / "rnb_tpu" / "models")
    with pytest.raises(SystemExit, match=family.name):
        module.build(str(tmp_path))
    module.build(REPO)
    parents = dict(mm.load())
    parents["workloads"] = [w for w in parents["workloads"]
                            if w["name"] != family.cell]
    with pytest.raises(KeyError, match="no workload '%s'" % family.cell):
        mm.cell(parents, family.cell)


# -- the control script ---------------------------------------------------


def run_the_control(family: Family, tmp_path) -> None:
    """``scripts/prefill_control.py`` over the toy copy: as stated
    inside the limit, the family's controls outside it."""
    control = family.control
    path = tmp_path / "toy.json"
    path.write_text(json.dumps(family.toy_config()))
    arms = ("--arms", control.arms) if control.arms else ()
    out = last_line(python(
        os.path.join(REPO, "scripts", "prefill_control.py"),
        "--config", str(path), "--lengths", control.lengths, *arms))
    assert out["family"] == family.name and out["ok"]
    assert out["as_stated"]["ok"]
    for arm in control.outside:
        assert not out[arm]["ok"], (arm, out[arm])
    for (arm, reading), interval in control.reads.items():
        assert within(out[arm][reading], interval), (arm, out[arm])
    if control.may_pass is not None:
        assert mm.load_family(family.name).CONTROL_MAY_PASS \
            == control.may_pass
    if control.also:
        control.also(out)


# -- the final stage ------------------------------------------------------


class Card:
    def __init__(self, rid):
        self.id = rid


class Cards:
    def __init__(self, count):
        self.time_cards = [Card(i) for i in range(count)]


@dataclasses.dataclass
class Served:
    """What ``serve`` leaves behind for the assertions."""
    stage: object
    recipe: str
    prompts: list

    @property
    def valid(self) -> int:
        """The prompts' tokens, one dispatch's."""
        return sum(len(p) for p in self.prompts)


def prompts_of(family: Family, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, family.recipe[0]["vocab_size"], n).astype(
        np.int32) for n in lengths]


def serve(family: Family, tmp_path) -> Served:
    """The final stage made from the family's toy recipe and served
    ``family.stage``'s dispatch, a sample a request as far as
    ``samples`` goes."""
    from rnb_tpu.devices import DeviceSpec
    from rnb_tpu.models import token_stages
    from rnb_tpu.stage import PaddedBatch
    case = family.stage
    checkpoint = importlib.import_module(
        "rnb_tpu.models.%s.checkpoint" % family.name)
    chunk = family.recipe[0]["chunk_size"]
    recipe = os.path.join(tmp_path, "toy.recipe.json")
    checkpoint.save_recipe(recipe, *family.recipe)
    with pytest.raises(ValueError, match="names family"):
        token_stages.PackedPrefill(
            DeviceSpec(-1), ckpt_path=recipe, max_rows=case.rows,
            chunk=chunk, row_buckets=[case.rows], family="another")
    stage = token_stages.PackedPrefill(
        DeviceSpec(-1), ckpt_path=recipe, max_rows=case.rows, chunk=chunk,
        row_buckets=list(case.row_buckets), family=family.name,
        sample_every=1, samples=case.samples)
    # a family that holds experts gives the recipe which, and has slots
    assert stage.family == family.name
    assert (stage._slots is None) == (len(family.recipe) == 2)
    # only what the family counts, and none before the first dispatch
    assert set(stage.stage_counters()) == {"tokens_valid", "tokens_shipped"}
    prompts = prompts_of(family, case.lengths, seed=2)
    tokens, meta, offsets = token_stages.pack_prompts(
        prompts, case.rows, chunk)
    batch = PaddedBatch(tokens, offsets[-1])
    batch.segment_offsets = tuple(offsets)
    for _ in range(case.dispatches):
        stage((batch, PaddedBatch(meta[0], offsets[-1])), None,
              Cards(len(prompts)))
    return Served(stage, recipe, prompts)


def stage_serves(family: Family, tmp_path) -> None:
    """The final stage learns the family from the recipe, counts what
    its log-meta lines carry, names the scopes the readers look for and
    keeps a request's tokens and choices."""
    case = family.stage
    served = serve(family, tmp_path)
    stage, chunk = served.stage, family.recipe[0]["chunk_size"]
    counters = stage.stage_counters()
    assert counters["tokens_valid"] == case.dispatches * served.valid
    assert counters["tokens_shipped"] \
        == case.dispatches * case.rows * chunk
    assert ("expert_served" in counters) == (stage._slots is not None)
    for scope in case.scopes:
        assert any(scope in name + "/"
                   for name in stage.hlo_scopes.values()), scope
    kept = min(case.samples, len(served.prompts))
    if case.dispatches == 1:
        # a sample's arrays start for the host behind the next launch
        # and are read behind the one after
        assert len(stage._sampled) == kept and not stage._samples
    stage._send_samples()
    stage._collect_samples()
    assert len(stage._samples) == kept
    first = stage._samples[0]
    assert first["tokens"].tolist() == served.prompts[0].tolist()
    assert first["chosen"].shape == case.chosen_shape
    if case.also:
        case.also(served)
