"""``dots3-note.bulk`` through the one benchmark command with ``--trace
1``, and the control script's arms, at a toy size on the CPU: the
per-layer metrics a CPU run can report, and none that stands against the
chip's peak or comes from the device's trace. Beside
``test_dots3_note_cell.py`` in a file of its own, so that the two dry
runs go to two workers under ``--dist loadfile``."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmarks import manifest as mm  # noqa: E402
from test_dots3_note import TOY_KEY_SLACK  # noqa: E402
from test_dots3_note_cell import run_the_cell, toy_config  # noqa: E402


def test_the_traced_cell_through_the_benchmark_command(tmp_path):
    run_the_cell(1, tmp_path)


def test_the_control_script_runs_the_familys_arms(tmp_path):
    """``scripts/prefill_control.py`` over the toy copy: as stated inside
    the limit and both slacks, the arms asked for outside one of them."""
    path = tmp_path / "toy.json"
    path.write_text(json.dumps(toy_config()))
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "prefill_control.py"),
         "--config", str(path), "--lengths", "150,30,230",
         "--arms", "index_float8,flat_gates,old_draw,layers_float8"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-3000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert out["family"] == "dots3_note" and out["ok"]
    assert out["as_stated"]["ok"]
    assert out["as_stated"]["key_shortfall_max"] < TOY_KEY_SLACK
    for arm in ("index_float8", "flat_gates", "layers_float8"):
        assert not out[arm]["ok"], (arm, out[arm])
    # the witness is recorded either way: the toy's rescale is 1.4 and
    # 1.6, not the published 2.2 and 3.2, and its old draw is no sharper
    assert "old_draw" in mm.load_family("dots3_note").CONTROL_MAY_PASS
    assert out["old_draw"]["share_of_spread"] > 0
    assert "no_rescale" not in out
    assert out["index_float8"]["key_shortfall_max"] > TOY_KEY_SLACK
    assert out["flat_gates"]["share_of_spread"] > 0.2
