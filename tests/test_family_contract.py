"""The cheap half of ``family_contract.py``, over every token family:
the toy copy its dry runs are fed passes the family file's own check,
and a parent's checkout fails on the cell before JAX starts."""

import pytest

import family_contract as contract


@pytest.mark.parametrize("name", contract.FAMILIES)
def test_the_toy_copy_is_a_sound_configuration(name):
    contract.toy_copy_is_sound(contract.record(name))


@pytest.mark.parametrize("name", [
    name for name in contract.FAMILIES
    if contract.record(name).refuses_a_parent])
def test_the_parent_fails_on_the_cell_before_jax_starts(name, tmp_path):
    contract.parent_fails(contract.record(name), tmp_path)
