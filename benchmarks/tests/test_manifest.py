"""BENCHMARK.json against the contract and against the files it names."""

import json
import os
import re

import pytest

from benchmarks import manifest as mm
from benchmarks import peaks
from benchmarks import traffic

MANIFEST = mm.load()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_top_level_keys_and_limits():
    assert sorted(MANIFEST) == sorted(
        ["command", "paths", "run_seconds", "configs", "workloads",
         "end_to_end", "per_layer"])
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(mm.MANIFEST) <= 64 * 1024
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 4)
    names = [m["name"] for m in METRICS] + CELLS \
        + [c["name"] for c in MANIFEST["configs"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(m["name"] for m in METRICS)) == len(METRICS)
    assert len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if metric in MANIFEST["end_to_end"] \
        else {"layer", "moves"}
    assert set(metric) <= allowed
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_layer_metric_has_a_reader_that_agrees_and_a_target(metric):
    module = mm.load_layer_metric(metric["name"])
    assert mm.describe(module) == {k: metric[k] for k in mm.METRIC_FIELDS}
    assert callable(module.read)
    target = [m for m in MANIFEST["end_to_end"]
              if m["name"] == metric["moves"]]
    assert len(target) == 1
    # the end-to-end metric is reported in every cell where this one is
    assert set(metric.get("workloads", CELLS)) \
        <= set(target[0].get("workloads", CELLS))


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    e2e = [m["name"] for m in mm.metrics_for(MANIFEST, "end_to_end",
                                             cell["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert mm.metrics_for(MANIFEST, "per_layer", cell["name"])
    assert os.path.exists(os.path.join(
        mm.BENCH_DIR, "traffic", cell["traffic"] + ".json"))
    mm.config_entry(MANIFEST, cell["config"])


@pytest.mark.parametrize("entry", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_file_passes_the_programs_and_its_familys_checks(entry,
                                                                tmp_path):
    assert entry["file"].startswith("benchmarks/")
    assert any(w["config"] == entry["name"] for w in MANIFEST["workloads"])
    check_config_file(entry["file"], entry["reduced"], tmp_path)


PREPARED = sorted(
    os.path.join("benchmarks", "configs", f)
    for f in os.listdir(os.path.join(mm.BENCH_DIR, "configs"))
    if os.path.join("benchmarks", "configs", f)
    not in [c["file"] for c in MANIFEST["configs"]])


@pytest.mark.parametrize("path", PREPARED)
def test_prepared_config_file_loads_too(path, tmp_path):
    """Configurations measured in PR 23 and kept for the PR that admits
    their cells (PERF.md, Open questions)."""
    check_config_file(path, [], tmp_path)


def check_config_file(path, reduced, tmp_path):
    """The manifest's and the file's ``reduced`` agree; what the file's
    family holds its parts to (``check_config`` of the family file: the
    model-specific lines live there) finds nothing; the program's own
    parser and the static graph checks of rnb_lint (shapes, buckets,
    dtypes) take its pipeline."""
    from rnb_tpu.config import parse_config
    with open(os.path.join(mm.REPO, path)) as f:
        config = json.load(f)
    assert config["reduced"] == reduced and config["assumed"]
    assert mm.load_family(config["family"]).check_config(config) == []
    parsed = parse_config(config["pipeline_config"])
    assert parsed.video_path_iterator \
        == "benchmarks.traffic.ScheduledPathIterator"
    path = tmp_path / "pipeline.json"
    path.write_text(json.dumps(config["pipeline_config"]))
    import subprocess
    import sys
    lint = subprocess.run(
        [sys.executable, os.path.join(mm.REPO, "scripts", "rnb_lint.py"),
         "--config", str(path)], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=mm.REPO))
    assert lint.returncode == 0, lint.stdout + lint.stderr


BACKLOG_CELLS = [w for w in MANIFEST["workloads"]
                 if traffic.load_mix(w["traffic"])["arrivals"]["process"]
                 == "backlog"]


@pytest.mark.parametrize("cell", BACKLOG_CELLS, ids=lambda w: w["name"])
def test_a_backlog_cell_has_room_to_show_half_again_its_anchor(cell):
    """benchmarks/traffic.py's rule: the backlog is sized from the
    rate of the ledger line the configuration's capacity key names,
    with room for at least +50% before ``correct`` refuses the run."""
    arrivals = traffic.load_mix(cell["traffic"])["arrivals"]
    assert arrivals["backlog_factor"] \
        * (1.0 - arrivals["min_left_share"]) >= 1.5
    config = mm.load_config_file(MANIFEST, cell["config"])
    assert config["capacity_videos_per_chip_s"] > 0
    assert re.search(r"ledger, PR \d+", config["capacity_why"])


@pytest.mark.parametrize("sizes,frames", [((3, 4, 6, 3), 32),
                                          ((2, 2, 2, 2), 8),
                                          ((3, 4, 6, 3), 8)])
def test_flop_count_equals_the_programs(sizes, frames):
    from rnb_tpu.models.r2p1d.flops import range_flops_per_clip
    assert mm.load_family("r2p1d").flops_per_clip(sizes, frames) \
        == range_flops_per_clip(1, 5, layer_sizes=sizes,
                                consecutive_frames=frames)


def test_unknown_device_is_an_error():
    assert peaks.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak_for("TPU v9")
