"""``nemotron3-nano.bulk`` through the one benchmark command at a toy
size on the CPU, by ``family_contract.py``; the record is
``test_nemotron_h.py``'s. A file of its own because one file is one
worker's under ``--dist loadfile`` and a run takes over a minute."""

import pytest

import family_contract as contract

FAMILY = contract.record("nemotron_h")


@pytest.mark.parametrize("trace", FAMILY.traces)
def test_the_cell_through_the_benchmark_command(trace, tmp_path):
    contract.run_the_cell(FAMILY, trace, tmp_path)
