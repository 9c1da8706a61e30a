"""``test_falcon_h1_cell.py``'s run of the benchmark command, traced: a
file of its own because one file is one worker's under ``--dist
loadfile`` and a run takes about a minute."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_falcon_h1_cell import run_the_cell  # noqa: E402


def test_the_cell_through_the_benchmark_command_traced(tmp_path):
    run_the_cell(1, tmp_path)
