"""The Kimi-Linear family through the system's own stages and the one
benchmark command, at a toy size on the CPU: the cell runs through
``benchmarks/run.py`` over a toy-width copy of its configuration
(untraced here; traced in ``test_kimi_linear_cell_traced.py``), the
parent fails on the cell before JAX starts, and the two new readers on
a run without their scope or kernel. The kernel and the configuration
are ``test_kimi_linear.py``'s, the stack and the stage
``test_kimi_linear_stack.py``'s (one file is one worker's under
``--dist loadfile``, and a run of the command takes over a minute of
it)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmarks import manifest as mm  # noqa: E402
from test_kimi_linear import CELL, toy_config  # noqa: E402


# -- through the one benchmark command ------------------------------------


def toy_tree(tmp_path):
    """The real manifest's new cell over a toy-width copy of its
    configuration: the same family, stages, mix and readers."""
    from test_kimi_linear import REAL
    os.makedirs(tmp_path / "benchmarks" / "configs")
    with open(tmp_path / REAL, "w") as f:
        json.dump(toy_config(), f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(mm.load(), f)
    return str(tmp_path / "BENCHMARK.json")


def test_the_toy_copy_is_a_sound_configuration():
    assert mm.load_family("kimi_linear").check_config(toy_config()) == []


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
    for name in ("Tokens: valid=", "Experts:", "Attention:"):
        assert name in meta, name
    assert len(list((out / "run").glob("prefill-sample-*.npz"))) == 8
    with open(out / "run" / "hlo-scopes.json") as f:
        scopes = list(json.load(f).values())
    for scope in ("/deltanet/rule/", "/deltanet/gate/", "/deltanet/conv/"):
        assert any(scope in name + "/" for name in scopes), scope
    metrics = line["metrics"]
    if trace:
        assert metrics["tokens_per_s.bulk"]["value"] > 0
        assert 0 < metrics["pad_token_pct.bulk"]["value"] < 100
        assert 0 < metrics["held_assignment_pct.bulk"]["value"] < 100
        assert metrics["expert_load_max_over_mean.bulk"]["value"] >= 1
        assert 0 < metrics["flash_tile_visit_pct.bulk"]["value"] <= 100
        assert 0 < metrics["gmm_row_fill_pct.bulk"]["value"] <= 100
        # what stands against the chip's peak, or comes from the
        # device's trace, does not come from a CPU
        assert not any("roofline" in n or "util" in n or "deltarule" in n
                       or "busy_pct" in n or n.startswith("kda_")
                       for n in metrics)
    else:
        assert metrics["videos_per_s"]["value"] > 0
        assert metrics["setup_s"]["value"] > 0


def test_the_cell_through_the_benchmark_command(tmp_path):
    run_the_cell(0, tmp_path)


def test_the_parent_fails_on_the_cell_before_jax_starts(tmp_path):
    """A checkout whose program lacks the family (the parent of PR 49,
    given this PR's benchmark files): the family file's ``build`` says
    so and exits, no result line."""
    family = mm.load_family("kimi_linear")
    os.makedirs(tmp_path / "rnb_tpu" / "models")
    with pytest.raises(SystemExit, match="kimi_linear"):
        family.build(str(tmp_path))
    family.build(REPO)


# -- the two new readers --------------------------------------------------

NEW_READERS = ("kda_kernel_roofline_pct.bulk", "kda_gate_ms_per_dispatch.bulk")
#: the accepted readers whose lists gained the cell
LISTED = (
    "deltanet_busy_pct", "deltanet_roofline_pct", "deltarule_roofline_pct",
    "deltarule_ms_per_dispatch", "segment_conv_ms_per_dispatch",
    "attn_busy_pct", "flash_roofline_pct", "flash_tile_visit_pct",
    "mla_proj_ms_per_dispatch", "experts_busy_pct", "experts_roofline_pct",
    "gmm_roofline_pct", "gmm_row_fill_pct", "held_assignment_pct",
    "expert_load_max_over_mean", "net_flops_util_pct", "net_roofline_pct",
    "tokens_per_s", "pad_token_pct", "pad_row_pct", "pad_row_traced_pct",
    "rows_per_dispatch", "host_cores_busy", "device_idle_pct",
    "hbm_peak_gib")


def test_the_accepted_readers_list_the_cell():
    by_name = {m["name"]: m for m in mm.load()["per_layer"]}
    for name in LISTED:
        assert CELL in by_name[name + ".bulk"]["workloads"], name
    # (PR 51's set-up metrics list every cell and move `setup_s`)
    listed = {n for n, m in by_name.items() if CELL in m.get("workloads", ())
              and m["moves"] == "videos_per_s"}
    assert listed == {n + ".bulk" for n in LISTED} | set(NEW_READERS)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_reads_nothing_on_a_run_without_its_scope(
        name, tmp_path):
    """No trace, a trace whose run wrote no scope table, and a family
    whose file counts no ``deltarule`` (the parent's programs have
    neither the scope nor the kernel): None, not a raise."""
    module = mm.load_layer_metric(name)
    entry = [m for m in mm.load()["per_layer"] if m["name"] == name]
    assert entry and entry[0]["workloads"] == [CELL]
    assert mm.describe(module) == {k: entry[0][k] for k in mm.METRIC_FIELDS}
    assert module.LAYER == "gated delta rule"

    class Result:
        log_dir = str(tmp_path)
        tokens_valid = 100
        pad_emissions = 2

    class Facts:
        trace = None
        result = Result
        family = mm.load_family("kimi_linear")
        config = {}
        peak_flops_per_s = 1.97e14
        device_kind = "TPU v5 lite"
    assert module.read(Facts) is None

    class Trace:
        path = str(tmp_path / "none.xplane.pb")
        host_span = (0.0, 1.0)
    from benchmarks import subscopes
    subscopes._CACHE[Trace.path] = {"%fusion.1 f32[8,8]": 0.5}
    Facts.trace = Trace
    try:
        assert subscopes.seconds_under(Facts, "deltanet/gate") is None
        (tmp_path / "hlo-scopes.json").write_text(json.dumps(
            {"%fusion.1 f32[8,8]": "jit(apply)/jit(main)/deltanet/rule/dot"}))
        subscopes._op_names.cache_clear()
        assert subscopes.seconds_under(Facts, "deltanet/gate") is None
        (tmp_path / "hlo-scopes.json").write_text(json.dumps(
            {"%fusion.1 f32[8,8]":
             "jit(apply)/jit(main)/deltanet/gate/dot"}))
        subscopes._op_names.cache_clear()
        assert subscopes.seconds_under(Facts, "deltanet/gate") == 0.5
        assert subscopes.seconds_under(Facts, "deltanet") == 0.5
    finally:
        del subscopes._CACHE[Trace.path]
        subscopes._op_names.cache_clear()


def test_the_kernels_name_is_the_readers():
    from rnb_tpu.ops import deltanet
    reader = mm.load_layer_metric("kda_kernel_roofline_pct.bulk")
    assert reader.KERNEL == deltanet.KDA_KERNEL_NAME
    # an older family's file counts no such mechanism and raises: the
    # reader says None there
    with open(os.path.join(
            REPO, "benchmarks/configs/deepseek-v2-ep8.json")) as f:
        older = json.load(f)
    with pytest.raises(ValueError):
        mm.load_family("deepseek_v2").mechanism_work(
            older, "deltarule", 1.0, 1.0, 1.0)
