"""The one command, end to end on the CPU at a toy size: the result
line has the contract's keys and says "cpu"; without ``--platform
cpu`` a machine with no TPU gets no result; a fifth cell is new files
and one manifest entry."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import manifest as mm

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixture")


def run(manifest, workload, *extra, trace=0, seconds=3):
    return subprocess.run(
        [sys.executable, os.path.join(mm.REPO, "benchmarks", "run.py"),
         "--manifest", manifest, "--workload", workload,
         "--seed", "3000000019", "--seconds", str(seconds),
         "--trace", str(trace), *extra],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


@pytest.mark.parametrize("workload,trace", [("tiny.bulk", 0),
                                            ("tiny.poisson", 0),
                                            ("tiny.poisson", 1),
                                            ("tiny-r4.bulk", 1)])
def test_result_line(workload, trace, tmp_path):
    # a backlog's window ends on a boundary of the notes' 5 s bins
    seconds = 5 if workload.endswith(".bulk") else 3
    done = run(os.path.join(FIXTURE, "BENCHMARK.json"), workload,
               "--platform", "cpu", "--out", str(tmp_path), trace=trace,
               seconds=seconds)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} \
        <= set(line)
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    fixture = mm.load(os.path.join(FIXTURE, "BENCHMARK.json"))
    group = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in mm.metrics_for(fixture, group, workload)}
    assert set(line["metrics"]) <= allowed and line["metrics"]
    # nothing that stands against the chip's peak comes from a CPU
    assert not any("util" in n or "roofline" in n for n in line["metrics"])
    if trace:
        assert line["device"]["busy_s"] > 0
        assert {"device_ops", "idle_gaps"} == set(line["breakdown"])
    else:
        assert line["metrics"]["setup_s"]["value"] > 0
        if workload == "tiny.poisson":
            assert line["correct"] is True
    notes = line["notes"]
    if workload.endswith(".bulk"):
        # how far the run was from emptying its backlog, in every line
        room = notes["backlog"]
        assert set(room) == {"requests", "left_share", "min_left_share",
                             "empties_at_videos_per_s"}
        assert room["requests"] == notes["requests"]
        by_the_close = notes["finished_by_5s"][0]
        assert room["left_share"] == pytest.approx(
            1.0 - by_the_close / notes["requests"])
        assert room["min_left_share"] == 0.05
    else:
        assert "backlog" not in notes


def test_no_accelerator_no_result(tmp_path):
    done = run(os.path.join(FIXTURE, "BENCHMARK.json"), "tiny.bulk",
               "--out", str(tmp_path))
    assert done.returncode != 0 and not done.stdout.strip()


def test_a_fifth_cell_is_new_files_and_one_entry(tmp_path):
    shutil.copytree(FIXTURE, tmp_path / "copy")
    root = tmp_path / "copy"
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()
              and p.name != "BENCHMARK.json"}
    mix = json.loads((root / "benchmarks/traffic/poisson-tiny.json")
                     .read_text())
    mix["arrivals"]["burst"] = {"period_s": 2.0, "on_s": 0.5, "factor": 2.0}
    (root / "benchmarks/traffic/burst-tiny.json").write_text(json.dumps(mix))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["workloads"].append({"name": "tiny.burst", "config": "tiny-yuv",
                                  "traffic": "burst-tiny", "chips": 1,
                                  "why": "a new mix"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "tiny.poisson" in metric.get("workloads", []):
            metric["workloads"].append("tiny.burst")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    done = run(str(root / "BENCHMARK.json"), "tiny.burst", "--platform",
               "cpu", "--out", str(tmp_path / "out"))
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["metrics"]["videos_per_s"]["value"] > 0
    assert all(p.read_bytes() == data for p, data in before.items())
