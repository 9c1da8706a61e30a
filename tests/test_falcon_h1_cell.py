"""The Falcon-H1 family through the system's own stages and the one
benchmark command, at a toy size on the CPU: the final stage serving the
family from its recipe with the scopes and counters the readers look
for; the control script's arms over a toy-width copy of the
configuration's file; the cell through ``benchmarks/run.py`` over that
copy (untraced here; traced in ``test_falcon_h1_cell_traced.py``); the
parent failing on the cell before JAX starts; the six new readers on a
run without their scope, kernel or counter. The stack and the
configuration are ``test_falcon_h1.py``'s (one file is one worker's
under ``--dist loadfile``)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmarks import manifest as mm  # noqa: E402
from test_falcon_h1 import (  # noqa: E402
    CELL, Q, REAL, SEED, TOY, pack, prompts_of, toy_config)


# -- the stage ------------------------------------------------------------


def test_the_prefill_stage_serves_the_family(tmp_path):
    """The final stage learns the family from the recipe, counts the
    flash kernel's tiles and the rows that open a request, names the
    scopes the readers look for and keeps a request's tokens and
    logits."""
    from rnb_tpu.devices import DeviceSpec
    from rnb_tpu.models import token_stages
    from rnb_tpu.models.falcon_h1 import checkpoint
    from rnb_tpu.stage import PaddedBatch
    from rnb_tpu.telemetry import stage_counter_report
    recipe = str(tmp_path / "toy.recipe.json")
    checkpoint.save_recipe(recipe, TOY, SEED)
    stage = token_stages.PackedPrefill(
        DeviceSpec(-1), ckpt_path=recipe, max_rows=8, chunk=Q,
        row_buckets=[8], family="falcon_h1", sample_every=1, samples=2)
    assert stage.family == "falcon_h1" and stage._slots is None
    prompts = prompts_of([80, 9, 30], seed=2)
    tokens, meta, offsets = pack(prompts, 8)
    batch = PaddedBatch(tokens, offsets[-1])
    batch.segment_offsets = tuple(offsets)

    class Card:
        def __init__(self, rid):
            self.id = rid

    class Cards:
        time_cards = [Card(0), Card(1), Card(2)]
    for _ in range(2):
        stage((batch, PaddedBatch(meta[0], offsets[-1])), None, Cards())
    counters = stage.stage_counters()
    valid = sum(len(p) for p in prompts)
    assert counters["tokens_valid"] == 2 * valid
    assert counters["tokens_shipped"] == 2 * 8 * Q
    # four layers' tiles, two dispatches; three requests a dispatch
    assert counters["attn_tiles"].tolist() == [8, 8]
    assert counters["scan_resets"].tolist() == [6]
    assert "expert_served" not in counters
    lines, fields = stage_counter_report([counters])
    assert lines == [
        "Tokens: valid=%d shipped=%d scan_resets=6" % (2 * valid, 16 * Q),
        "Attention: tiles_visited=8 tiles_causal=8"]
    assert fields["tokens_scan_resets"] == 6
    for scope in ("/embed/", "/norm/", "/ssd/", "/ssd/conv/", "/ssd/scan/",
                  "/attn/", "/mlp/", "/head/"):
        assert any(scope in name + "/"
                   for name in stage.hlo_scopes.values()), scope
    stage._send_samples()
    stage._collect_samples()
    assert len(stage._samples) == 2
    first = stage._samples[0]
    assert first["tokens"].tolist() == prompts[0].tolist()
    assert first["chosen"].shape == (0, 80)
    assert first["logits"].shape == (TOY["vocab_size"],)


def test_the_control_script_takes_the_family_from_the_recipe(tmp_path):
    """``scripts/prefill_control.py`` over a toy-width copy of the
    configuration's file: as stated inside the limit, every layer's
    matrices through float8 outside it, the scan's states through
    bfloat16 reported and free to pass."""
    path = tmp_path / "toy.json"
    path.write_text(json.dumps(toy_config()))
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "prefill_control.py"),
         "--config", str(path), "--lengths", "120,37,70"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-3000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert out["family"] == "falcon_h1" and out["ok"]
    assert out["as_stated"]["ok"] and not out["layers_float8"]["ok"]
    assert out["state_bfloat16"]["share_of_spread"] < 0.2
    assert mm.load_family("falcon_h1").CONTROL_MAY_PASS \
        == ("state_bfloat16",)


# -- through the one benchmark command ------------------------------------


def toy_tree(tmp_path):
    """The real manifest's new cell over a toy-width copy of its
    configuration: the same family, stages, mix and readers."""
    os.makedirs(tmp_path / "benchmarks" / "configs")
    with open(tmp_path / REAL, "w") as f:
        json.dump(toy_config(), f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(mm.load(), f)
    return str(tmp_path / "BENCHMARK.json")


def test_the_toy_copy_is_a_sound_configuration():
    assert mm.load_family("falcon_h1").check_config(toy_config()) == []


def run_the_cell(trace, tmp_path):
    """One run of the benchmark command over the toy copy, held to what
    a CPU run can show."""
    out = tmp_path / "out"
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--manifest", toy_tree(tmp_path), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "3", "--trace", str(trace),
         "--platform", "cpu", "--out", str(out)],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, \
        done.stderr[-3000:]
    assert line["attempted"] > 0
    meta = (out / "run" / "log-meta.txt").read_text()
    for name in ("Tokens: valid=", " scan_resets=", "Attention:"):
        assert name in meta, name
    assert "Experts:" not in meta
    assert len(list((out / "run").glob("prefill-sample-*.npz"))) == 8
    with open(out / "run" / "hlo-scopes.json") as f:
        scopes = list(json.load(f).values())
    for scope in ("/ssd/scan/", "/ssd/conv/", "/attn/", "/mlp/", "/head/"):
        assert any(scope in name + "/" for name in scopes), scope
    metrics = line["metrics"]
    if trace:
        assert metrics["tokens_per_s.bulk"]["value"] > 0
        assert 0 < metrics["pad_token_pct.bulk"]["value"] < 100
        assert 0 < metrics["flash_tile_visit_pct.bulk"]["value"] <= 100
        assert 1 <= metrics["scan_resets_per_dispatch.bulk"]["value"] <= 8
        # what stands against the chip's peak, or comes from the
        # device's trace, does not come from a CPU
        assert not any("roofline" in n or "util" in n or "busy_pct" in n
                       or "ms_per_dispatch" in n for n in metrics)
    else:
        assert metrics["videos_per_s"]["value"] > 0
        assert metrics["setup_s"]["value"] > 0


def test_the_cell_through_the_benchmark_command(tmp_path):
    run_the_cell(0, tmp_path)


def test_the_parent_fails_on_the_cell_before_jax_starts(tmp_path):
    """A checkout whose program lacks the family (the parent of PR 53,
    given this PR's benchmark files): the family file's ``build`` says
    so and exits, no result line; without this PR's manifest it has no
    such workload at all."""
    family = mm.load_family("falcon_h1")
    os.makedirs(tmp_path / "rnb_tpu" / "models")
    with pytest.raises(SystemExit, match="falcon_h1"):
        family.build(str(tmp_path))
    family.build(REPO)
    parents = dict(mm.load(), workloads=[
        w for w in mm.load()["workloads"] if w["name"] != CELL])
    with pytest.raises(KeyError, match=CELL):
        mm.cell(parents, CELL)


# -- the six new readers --------------------------------------------------

NEW_READERS = {
    "ssm_branch_roofline_pct.bulk": "state-space scan",
    "ssd_kernel_roofline_pct.bulk": "state-space scan",
    "hybrid_flash_roofline_pct.bulk": "packed attention",
    "mlp_roofline_pct.bulk": "network",
    "attn_branch_ms_per_dispatch.bulk": "packed attention",
    "scan_resets_per_dispatch.bulk": "state-space scan"}
#: the accepted readers whose lists gained the cell
LISTED = (
    "host_cores_busy", "rows_per_dispatch", "pad_row_pct",
    "net_flops_util_pct", "net_roofline_pct", "device_idle_pct",
    "hbm_peak_gib", "pad_row_traced_pct", "tokens_per_s", "pad_token_pct",
    "flash_tile_visit_pct", "ssd_busy_pct", "attn_busy_pct", "mlp_busy_pct",
    "ssd_scan_ms_per_dispatch", "segment_conv_ms_per_dispatch")


def test_the_accepted_readers_list_the_cell_last():
    by_name = {m["name"]: m for m in mm.load()["per_layer"]}

    def last_of_its_pr(workloads):
        """The cell stands last but for the cells later PRs appended
        (PR 55's ``dots3-note.bulk``)."""
        behind = workloads[workloads.index(CELL) + 1:]
        return set(behind) <= {"dots3-note.bulk"}
    for name in LISTED:
        assert last_of_its_pr(by_name[name + ".bulk"]["workloads"]), name
    listed = {n for n, m in by_name.items() if CELL in m.get("workloads", ())
              and m["moves"] == "videos_per_s"}
    assert listed == {n + ".bulk" for n in LISTED} | set(NEW_READERS)
    # this PR's six stand behind the eight set-up metrics, appended in
    # the order of ``NEW_READERS``; the eight themselves are the
    # harness's test's (``tests/harness/test_harness_setup_account.py``)
    names = [m["name"] for m in mm.load()["per_layer"]]
    at = names.index(next(iter(NEW_READERS)))
    assert names[at:at + len(NEW_READERS)] == list(NEW_READERS)
    assert by_name[names[at - 1]]["moves"] == "setup_s"
    assert last_of_its_pr(by_name[names[at - 1]]["workloads"])
    # a reader that gives a dense family nothing does not list it
    # nor the three idle shares: the cell's spans paired under
    # ``hostspans.PAIR_RADIUS_NS`` in two of three traced runs only
    # (PERF.md section 6)
    for name in ("flash_roofline_pct.bulk", "ssd_roofline_pct.bulk",
                 "experts_busy_pct.bulk", "idle_starved_pct.bulk",
                 "idle_launch_pct.bulk", "idle_host_loop_pct.bulk"):
        assert CELL not in by_name[name]["workloads"], name


class Result:
    tokens_valid = 100
    pad_emissions = 2
    tokens_scan_resets = 0


def facts_of(tmp_path, family="falcon_h1"):
    class Facts:
        trace = None
        result = type("R", (Result,), {"log_dir": str(tmp_path)})
        config = json.load(open(os.path.join(REPO, REAL)))
        peak_flops_per_s = 1.97e14
        device_kind = "TPU v5 lite"
    Facts.family = mm.load_family(family)
    return Facts


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_a_new_reader_reads_nothing_on_a_run_without_its_source(
        name, tmp_path):
    """No trace, no counter (the parent's programs have neither the
    scopes nor the counter): None, not a raise; and the manifest repeats
    what the file declares."""
    module = mm.load_layer_metric(name)
    entry = [m for m in mm.load()["per_layer"] if m["name"] == name]
    assert entry and entry[0]["workloads"] == [CELL]
    assert mm.describe(module) == {k: entry[0][k] for k in mm.METRIC_FIELDS}
    assert module.LAYER == NEW_READERS[name]
    assert module.read(facts_of(tmp_path)) is None
    # an older family's file counts by another signature, or no such
    # mechanism: nothing, not a raise
    assert module.read(facts_of(tmp_path, "nemotron_h")) is None
    assert module.read(facts_of(tmp_path, "minicpm_sala")) is None


def test_the_readers_read_a_run_that_has_their_sources(tmp_path,
                                                       monkeypatch):
    """A trace reduced to two instructions and a kernel's call: the
    shares are the family's work over those seconds, the counter its
    rows a dispatch."""
    from benchmarks import scopes, subscopes
    facts = facts_of(tmp_path)

    class Trace:
        path = str(tmp_path / "none.xplane.pb")
        host_span = (0.0, 1.0)
    facts.trace = Trace
    facts.result.tokens_scan_resets = 14
    subscopes._CACHE[Trace.path] = {"%fusion.1 f32[8,8]": 0.5,
                                    "%fusion.2 f32[8,8]": 0.25,
                                    "%fusion.3 f32[8,8]": 0.125}
    (tmp_path / "hlo-scopes.json").write_text(json.dumps({
        "%fusion.1 f32[8,8]": "jit(apply)/jit(main)/mlp/dot",
        "%fusion.2 f32[8,8]": "jit(apply)/jit(main)/ssd/scan/ssd_scan",
        "%fusion.3 f32[8,8]": "jit(apply)/jit(main)/attn/dot"}))
    subscopes._op_names.cache_clear()
    tokens, dispatches = 16384.0, 2.0
    monkeypatch.setattr(scopes, "traced_tokens", lambda facts: tokens)
    monkeypatch.setattr(scopes, "kernel_seconds",
                        lambda facts, kernel: 0.01)
    try:
        family, config = facts.family, facts.config

        def least(mechanism):
            ops, nbytes = family.mechanism_work(config, mechanism, tokens,
                                                tokens * 2 / 100)
            return max(ops / 1.97e14, nbytes / 8.19e11)
        read = {name: mm.load_layer_metric(name).read(facts)
                for name in NEW_READERS}
        assert read["mlp_roofline_pct.bulk"] \
            == pytest.approx(100 * least("mlp") / 0.5)
        assert read["ssm_branch_roofline_pct.bulk"] \
            == pytest.approx(100 * least("ssm") / 0.25)
        assert read["ssd_kernel_roofline_pct.bulk"] \
            == pytest.approx(100 * least("scan") / 0.01)
        assert read["hybrid_flash_roofline_pct.bulk"] \
            == pytest.approx(100 * least("flash") / 0.01)
        assert read["attn_branch_ms_per_dispatch.bulk"] \
            == pytest.approx(1e3 * 0.125 / (tokens * 2 / 100))
        assert read["scan_resets_per_dispatch.bulk"] == 7.0
    finally:
        del subscopes._CACHE[Trace.path]
        subscopes._op_names.cache_clear()


def test_the_kernels_names_are_the_readers():
    from rnb_tpu.ops import ssd
    assert mm.load_layer_metric("ssd_kernel_roofline_pct.bulk").KERNEL \
        == ssd.KERNEL_NAME
    # the flash kernel's calls are the ones ``flash_roofline_pct.bulk``
    # reads for the expert families
    with open(os.path.join(mm.LAYER_METRICS_DIR,
                           "flash_roofline_pct.bulk.py")) as f:
        assert '"%s"' % mm.load_layer_metric(
            "hybrid_flash_roofline_pct.bulk").KERNEL in f.read()
