"""The dots3-note family through the system's own stages and the one
benchmark command, at a toy size on the CPU: the final stage serving the
family from its recipe with the scopes, counters and two kinds of choice
the readers and the check look for; the cell through
``benchmarks/run.py`` over a toy-width copy of the configuration's file,
untraced (traced, and the control script's arms over that copy, in
``test_dots3_note_cell_traced.py``); the parent failing on the cell
before JAX starts; the seven new readers on a
run without their kernel or counter. The stack and the toy are
``test_dots3_note.py``'s (one file is one worker's under ``--dist
loadfile``)."""

import inspect
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmarks import manifest as mm  # noqa: E402
from test_dots3_note import (  # noqa: E402
    CELL, HELD, Q, REAL, SEED, TOPK, TOY, TOY_KEY_SLACK, TOY_LIMIT, pack,
    prompts_of, real_config)


def toy_config():
    config = real_config()
    config.update(TOY)
    config["experts_held"] = {"first": 0, "count": 8}
    config["vocab_held"] = {"first": 0, "count": TOY["vocab_size"]}
    config["model"] = dict(config["model"], layers=5)
    config["dataset"] = {"seed": 0, "long_every": 11,
                         "short": {"count": 6, "median": 110, "sigma": 0.5,
                                   "min": 30, "max": 200},
                         "long": {"count": 2, "min": 200, "max": 256}}
    config["capacity_videos_per_chip_s"] = 60
    config["share_of_spread"] = TOY_LIMIT
    config["key_slack"] = TOY_KEY_SLACK
    config["ref_pad"] = 64
    loader, batcher, prefill = config["pipeline_config"]["pipeline"]
    loader.update(max_rows=8, chunk=Q)
    batcher.update(batch=8, shapes=[[8, Q], [8]], row_buckets=[8])
    prefill.update(max_rows=8, chunk=Q, row_buckets=[8],
                   sample_every=3, samples=8)
    return config


def test_the_toy_copy_is_a_sound_configuration():
    assert mm.load_family("dots3_note").check_config(toy_config()) == []


# -- the stage ------------------------------------------------------------


def test_the_prefill_stage_serves_the_family(tmp_path):
    """The final stage learns the family from the recipe, counts what
    the four log-meta lines carry, names the scopes the readers look for
    and keeps a request's tokens, logits and both kinds of choice."""
    from rnb_tpu.devices import DeviceSpec
    from rnb_tpu.models import token_stages
    from rnb_tpu.models.dots3_note import checkpoint
    from rnb_tpu.ops import banded, indexed
    from rnb_tpu.stage import PaddedBatch
    from rnb_tpu.telemetry import stage_counter_report
    recipe = str(tmp_path / "toy.recipe.json")
    checkpoint.save_recipe(recipe, TOY, SEED, HELD)
    stage = token_stages.PackedPrefill(
        DeviceSpec(-1), ckpt_path=recipe, max_rows=8, chunk=Q,
        row_buckets=[8], family="dots3_note", sample_every=1, samples=2)
    assert stage.family == "dots3_note" and stage._slots is not None
    prompts = prompts_of([120, 70, 30], seed=2)
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
    at = np.concatenate([np.arange(len(p)) for p in prompts]) + 1
    assert counters["tokens_valid"] == 2 * valid
    # two full layers, two dispatches
    assert counters["sparse"].tolist() == [
        4 * valid, 4 * int((at > TOPK).sum()),
        4 * int(at[at > TOPK].sum()), 4 * int((at > TOPK).sum()) * TOPK]
    # three sliding layers, two dispatches: the pairs a window of 37 keeps
    assert counters["window_keys"].tolist() == [
        6 * int(np.minimum(at, 37).sum()), 6 * int(at.sum())]
    assert counters["window_tiles"].tolist() == [6 * 4, 6 * 6]
    assert counters["index_tiles"].tolist()[1] == 4
    assert counters["expert_served"].shape == (4, 8)
    lines, fields = stage_counter_report([counters])
    assert [line.split(":")[0] for line in lines] \
        == ["Tokens", "Experts", "Sparse", "Attention"]
    assert " pair_rows_moved=" in lines[1] and " gmm_rows=" in lines[1]
    assert " tiles_chosen=" in lines[2] \
        and " chunks_walked=" in lines[2] \
        and lines[2].endswith(" chunks_to_diagonal=%d" % (2 * 2 * 2))
    assert lines[3].startswith("Attention: window_tiles_visited=24 ") \
        and " window_keys_kept=" in lines[3]
    assert fields["window_keys_causal"] == 6 * int(at.sum())
    for scope in ("/embed/", "/attn/", "/attn/mla_proj/", "/attn/gate/",
                  "/attn/select/", "/attn/select/index/", "/attn/full/",
                  "/attn/window/", "/experts/", "/head/"):
        assert any(scope in name + "/"
                   for name in stage.hlo_scopes.values()), scope
    kernels = " ".join(stage.hlo_scopes)
    for kernel in (indexed.LATENT_KERNEL, banded.LATENT_KERNEL_NAME,
                   indexed.SCORES_KERNEL, "mla_queries"):
        assert kernel in kernels or stage._jax_device.platform != "tpu"
    stage._send_samples()
    stage._collect_samples()
    assert len(stage._samples) == 2
    first = stage._samples[0]
    assert first["tokens"].tolist() == prompts[0].tolist()
    assert first["chosen"].shape == (4, 120, 8)
    assert first["key_sets"].shape[:2] == (2, 120) and first["first"] == 0
    assert first["logits"].shape == (TOY["vocab_size"],)


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
    for name in ("Tokens: valid=", "Experts:", " gmm_rows=",
                 " pair_rows_moved=", "Sparse: queries=", " tiles_chosen=",
                 " chunks_walked=", " chunks_to_diagonal=",
                 "Attention: window_tiles_visited=", " window_keys_kept="):
        assert name in meta, name
    samples = sorted((out / "run").glob("prefill-sample-*.npz"))
    assert len(samples) == 8
    with np.load(samples[0]) as sample:
        assert {"tokens", "logits", "chosen", "key_sets", "first"} \
            <= set(sample.files)
        assert sample["key_sets"].shape[0] == 2      # the full layers'
    with open(out / "run" / "hlo-scopes.json") as f:
        scopes = list(json.load(f).values())
    for scope in ("/attn/select/index/", "/attn/full/", "/attn/window/",
                  "/attn/mla_proj/", "/attn/gate/"):
        assert any(scope in name + "/" for name in scopes), scope
    metrics = line["metrics"]
    if trace:
        assert metrics["tokens_per_s.bulk"]["value"] > 0
        assert 0 < metrics["pad_token_pct.bulk"]["value"] < 100
        assert 0 < metrics["held_assignment_pct.bulk"]["value"] < 100
        assert metrics["expert_load_max_over_mean.bulk"]["value"] >= 1
        assert 0 < metrics["sparse_query_pct.bulk"]["value"] < 100
        assert 0 < metrics["selected_key_pct.bulk"]["value"] < 100
        assert 0 < metrics["chosen_tile_pct.bulk"]["value"] <= 100
        assert 0 < metrics["select_chunk_walk_pct.bulk"]["value"] <= 100
        assert 0 < metrics["window_key_pct.bulk"]["value"] < 100
        assert 0 < metrics["gmm_row_fill_pct.bulk"]["value"] <= 100
        assert 0 < metrics["pair_rows_moved_pct.bulk"]["value"] <= 100
        # what stands against the chip's peak, or comes from the
        # device's trace, does not come from a CPU
        assert not any("roofline" in n or "util" in n or "_ms_per_" in n
                       or "busy_pct" in n for n in metrics)
    else:
        assert metrics["videos_per_s"]["value"] > 0
        assert metrics["setup_s"]["value"] > 0


def test_the_cell_through_the_benchmark_command(tmp_path):
    run_the_cell(0, tmp_path)


def test_the_parent_fails_on_the_cell_before_jax_starts(tmp_path):
    """A checkout whose program lacks the family (the parent of PR 55,
    given this PR's benchmark files): the family file's ``build`` says
    so and exits, no result line; and the parent's own manifest has no
    such cell: ``manifest.cell`` raises at once."""
    family = mm.load_family("dots3_note")
    os.makedirs(tmp_path / "rnb_tpu" / "models")
    with pytest.raises(SystemExit, match="dots3_note"):
        family.build(str(tmp_path))
    family.build(REPO)
    parents = dict(mm.load())
    parents["workloads"] = [w for w in parents["workloads"]
                            if w["name"] != CELL]
    with pytest.raises(KeyError, match="no workload 'dots3-note.bulk'"):
        mm.cell(parents, CELL)


# -- the seven new readers ------------------------------------------------

NEW_READERS = {
    "mla_index_scores_roofline_pct.bulk": "index_scores",
    "mla_indexed_attn_ms_per_dispatch.bulk": "latent_indexed_attention",
    "mla_indexed_attn_roofline_pct.bulk": "latent_indexed_attention",
    "mla_window_attn_ms_per_dispatch.bulk": "latent_banded_attention",
    "mla_window_attn_roofline_pct.bulk": "latent_banded_attention",
    "mla_latent_proj_ms_per_dispatch.bulk": "attn/mla_proj",
    "window_key_pct.bulk": None}


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_a_new_reader_reads_nothing_on_a_run_without_its_kernel(
        name, tmp_path):
    """No trace, or a counter that counted nothing (the parent's
    program): None, not a raise. The kernels' names are the program's."""
    from rnb_tpu.ops import banded, indexed
    module = mm.load_layer_metric(name)
    entry = [m for m in mm.load()["per_layer"] if m["name"] == name]
    assert entry and entry[0]["workloads"] == [CELL]
    assert mm.describe(module) == {k: entry[0][k] for k in mm.METRIC_FIELDS}
    assert module.LAYER == "packed attention"

    class Result:
        log_dir = str(tmp_path)
        tokens_valid = 100
        pad_emissions = 2

    class Facts:
        trace = None
        result = Result
        family = mm.load_family("dots3_note")
        config = real_config()
        peak_flops_per_s = 1.97e14
        device_kind = "TPU v5 lite"
    assert module.read(Facts) is None
    if NEW_READERS[name] is None:
        Result.window_keys_kept, Result.window_keys_causal = 0, 0
        assert module.read(Facts) is None
        Result.window_keys_kept, Result.window_keys_causal = 9, 100
        assert module.read(Facts) == 9.0
        return
    if "/" in NEW_READERS[name]:
        # a reader of a scope the family names, not of a kernel
        assert not hasattr(module, "KERNEL")
        assert '"%s"' % NEW_READERS[name] in inspect.getsource(module.read)
        return
    assert module.KERNEL == NEW_READERS[name] and module.KERNEL in (
        indexed.SCORES_KERNEL, indexed.LATENT_KERNEL,
        banded.LATENT_KERNEL_NAME)
    # another family's file counts none of these mechanisms: no raise
    Facts.family = mm.load_family("keye_vl2")
    assert module.read(Facts) is None


def test_the_cell_joins_the_accepted_metrics_its_readers_serve():
    per_layer = {m["name"]: m for m in mm.load()["per_layer"]}
    joined = [name for name, m in per_layer.items()
              if CELL in m["workloads"]]
    # ISSUE 55's 23 and the eight of set-up, the two accepted readers
    # that find this family's scope and counter (the indexer's, the
    # window's tiles), the cell's own seven, and PR 56's reader of the
    # thresholds' walk, which came with both cells that run it
    assert len(joined) == 23 + 8 + 2 + 7 + 1
    assert {"indexer_ms_per_dispatch.bulk",
            "window_tile_visit_pct.bulk"} <= set(joined)
    # readers of another family's kernel or scope by name stay as they were
    for name in ("mla_proj_ms_per_dispatch.bulk", "flash_roofline_pct.bulk",
                 "indexed_attn_roofline_pct.bulk",
                 "window_attn_roofline_pct.bulk"):
        assert CELL not in per_layer[name]["workloads"]
    for m in per_layer.values():
        assert m["workloads"].count(CELL) <= 1
        if CELL in m["workloads"] and len(m["workloads"]) > 1:
            assert m["workloads"][-1] == CELL
