"""The Kimi-Linear stack against its plain reference, at a toy size on
the CPU with weights from a seed: the packed prefill through dispatches
with several requests, a pad row and a request that ends inside a row,
the router's choices within the slack; packing that is invisible; the
KDA mixer's state and convolution history restarting at every request.
The kernel and the configuration are ``test_kimi_linear.py``'s, the cell,
the stage and the control script ``test_kimi_linear_cell.py``'s (one file
is one worker's under ``--dist loadfile``)."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmarks import manifest as mm  # noqa: E402
from benchmarks.references import compare  # noqa: E402
from benchmarks.references import kimi_linear as reference  # noqa: E402
from test_kimi_linear import (  # noqa: E402,F401
    Q, TOY, TOY_LIMIT, prompts_of, run_program, run_reference, toy)


# -- the whole stack ----------------------------------------------------------

#: dispatches of 16 rows: several requests, one that ends inside a row,
#: one that fills its rows, pad rows behind
DISPATCHES = {"three": [120, 37, 70], "whole_rows": [16, 96, 5, 64],
              "one_long": [250]}


@pytest.mark.parametrize("case", sorted(DISPATCHES))
def test_packed_prefill_matches_the_reference(toy, case):
    family = mm.load_family("kimi_linear")
    prompts = prompts_of(DISPATCHES[case], seed=4)
    logits, chosen, counts = run_program(toy, prompts, 16)
    refs = [run_reference(toy, p, forced=c)
            for p, c in zip(prompts, chosen)]
    want = np.stack([np.asarray(r["logits"]) for r in refs])
    verdict = compare(logits, want, TOY_LIMIT)
    assert verdict["ok"], verdict
    assert max(float(np.asarray(r["shortfall"]).max()) for r in refs) \
        < family.ROUTE_SLACK
    # the reference's own free choice agrees almost everywhere
    for prompt, mine in zip(prompts, chosen):
        free = np.asarray(run_reference(toy, prompt)["chosen"])
        assert (np.sort(free, -1) == np.sort(mine, -1)).all(-1).mean() > 0.9
    # four expert layers behind the dense one, one attention layer
    assert chosen[0].shape[0] == 4 and counts[2].shape == (1, 2)
    valid = sum(len(p) for p in prompts)
    assert 0 < counts[0].sum() < 4 * 4 * valid


def test_packing_is_invisible_and_state_restarts(toy):
    """A prompt's logits and choices depend neither on what shares its
    dispatch, nor on where in the pool it lies, nor on the bucket."""
    a, b, c, d = prompts_of([100, 5, 70, 20])
    alone, chosen, _ = run_program(toy, [a], 8)
    packed, packed_chosen, _ = run_program(toy, [b, c, a, d], 16)
    spread = float(np.asarray(
        run_reference(toy, a, forced=chosen[0])["logits"]).std())
    assert np.abs(packed[2] - alone[0]).max() < 0.005 * spread
    assert np.array_equal(packed_chosen[2], chosen[0])


def test_the_mixer_restarts_state_and_convolution_history(toy):
    """The KDA mixer on a packed pool against each request alone, and
    against the plain reference's mixer token by token."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.kimi_linear import network
    cfg, p = toy["cfg"], toy["params"]["l0"]
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(6, Q, 64)), jnp.bfloat16)
    first = jnp.asarray([True, False, False, True, False, False])
    mixer = jax.jit(lambda h, first: network.kda_mixer(
        cfg, p, h, first, interpret=True))
    packed = np.asarray(mixer(h, first))
    for lo in (0, 3):
        alone = np.asarray(mixer(h[lo:lo + 3], jnp.arange(3) == 0))
        assert np.abs(packed[lo:lo + 3] - alone).max() \
            < 1e-5 * np.abs(alone).max()
    w = {t: toy["read"]("l0." + t) for t in reference.KDA}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.kda(
            TOY, w, h[:3].reshape(3 * Q, 64).astype(jnp.float32)))
    assert np.abs(packed[:3].reshape(want.shape) - want).max() \
        < 0.03 * want.std()
