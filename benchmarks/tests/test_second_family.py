"""A second model family is new files and appended manifest entries:
the harness runs a family it has never heard of, and none of its own
modules knows the one it has."""

import ast
import json
import os
import shutil

import pytest

from benchmarks import manifest as mm
from test_dry_run import FIXTURE, run

OVERLAY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "second_family")


@pytest.mark.parametrize("trace", [0, 1])
def test_a_second_family_is_new_files_and_appended_entries(trace, tmp_path):
    root = tmp_path / "copy"
    shutil.copytree(FIXTURE, root)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()
              and p.name != "BENCHMARK.json"}
    shutil.copytree(OVERLAY, root, dirs_exist_ok=True)
    assert all(p.read_bytes() == data for p, data in before.items())
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "toytok", "source": "none", "reduced": [],
        "file": "benchmarks/configs/toytok.json", "why": "dry run"})
    manifest["workloads"].append({
        "name": "toytok.poisson", "config": "toytok",
        "traffic": "poisson-tiny", "chips": 1, "why": "a new family"})
    for metric in manifest["per_layer"]:
        if "tiny.poisson" in metric["workloads"]:
            metric["workloads"].append("toytok.poisson")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    done = run(str(root / "BENCHMARK.json"), "toytok.poisson", "--platform",
               "cpu", "--out", str(tmp_path / "out"), trace=trace)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, \
        done.stderr[-2000:]
    assert line["attempted"] > 0 and line["metrics"]
    if trace:
        # the program's executor spans and finish stamps are any
        # family's; the video loader's refinement stamps are not, and
        # their readers find nothing to read
        assert line["metrics"]["completed_per_s.open"]["value"] > 0
        assert "phase_decode_ms.open" not in line["metrics"]
    else:
        assert line["metrics"]["videos_per_s"]["value"] > 0
    assert all(p.read_bytes() == data for p, data in before.items())


HARNESS = sorted(
    os.path.relpath(os.path.join(base, name), mm.BENCH_DIR)
    for base, _, names in os.walk(mm.BENCH_DIR) for name in names
    if name.endswith(".py") and os.path.relpath(base, mm.BENCH_DIR)
    .split(os.sep)[0] not in ("families", "references", "tests"))
FAMILY_WORDS = ("R2P1D", "r2p1d", "layer_sizes", "consecutive_frames",
                "pixel_path", "max_clips")


@pytest.mark.parametrize("path", HARNESS)
def test_harness_module_knows_no_family(path):
    with open(os.path.join(mm.BENCH_DIR, path)) as f:
        source = f.read()
    imported = []
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            imported += [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            imported.append(node.module or "")
    assert not [m for m in imported if m.startswith("rnb_tpu.models")]
    assert not [w for w in FAMILY_WORDS if w in source]


def test_a_configuration_without_a_family_is_an_error(tmp_path):
    (tmp_path / "c.json").write_text(json.dumps({"model": {}}))
    manifest = {"configs": [{"name": "c", "file": "c.json"}]}
    with pytest.raises(KeyError, match="family"):
        mm.load_config_file(manifest, "c", str(tmp_path))
    with pytest.raises(FileNotFoundError):
        mm.load_family("no-such-family")
