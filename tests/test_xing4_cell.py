"""``xing4.bulk`` through the one benchmark command, traced, the control
script's arms and the final stage serving the family, at a toy size on
the CPU, by ``family_contract.py``; the record is ``test_xing4.py``'s. A
file of its own because one file is one worker's under ``--dist
loadfile`` and a run takes over a minute."""

import pytest

import family_contract as contract

FAMILY = contract.record("xing4")


@pytest.mark.parametrize("trace", FAMILY.traces)
def test_the_cell_through_the_benchmark_command(trace, tmp_path):
    contract.run_the_cell(FAMILY, trace, tmp_path)


def test_the_control_script_runs_the_familys_arms(tmp_path):
    contract.run_the_control(FAMILY, tmp_path)


def test_the_prefill_stage_serves_the_family(tmp_path):
    contract.stage_serves(FAMILY, tmp_path)
