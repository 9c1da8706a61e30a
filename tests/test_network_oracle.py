"""Independent numerics validation of the Flax R(2+1)D network.

Drives the Flax modules and the pure-numpy oracle (oracle_r2p1d, no
Flax/XLA in its math) with identical parameter arrays and asserts
agreement — the check the reference got implicitly from running
pretrained torch weights through the submodule's blocks
(/root/reference/models/r2p1d/model.py:18,50-63). A padding, stride,
or factored-channel regression on the Flax side cannot hide here: the
oracle would diverge. A committed golden-logits fixture additionally
pins one seeded full-net forward against drift over time.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import oracle_r2p1d as oracle
from rnb_tpu.models.r2p1d.network import (LAYER_INPUT_SHAPES,
                                          R2Plus1DClassifier, R2Plus1DNet,
                                          SpatioTemporalConv,
                                          SpatioTemporalResBlock,
                                          factored_channels,
                                          range_output_shape)

GOLDEN_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "golden", "r2p1d_logits.npz")


def _randomize_dict(d, rng):
    """Non-trivial BN statistics and affine terms: init() gives
    mean=0/var=1/scale=1/bias=0, which would let a BN wiring bug pass
    as the identity."""
    out = {}
    for k, v in d.items():
        if hasattr(v, "items"):
            out[k] = _randomize_dict(v, rng)
        elif k == "mean":
            out[k] = rng.normal(0.0, 0.3, np.shape(v)).astype(np.float32)
        elif k == "var":
            out[k] = rng.uniform(0.5, 1.5, np.shape(v)).astype(np.float32)
        elif k == "scale":
            out[k] = rng.uniform(0.5, 1.5, np.shape(v)).astype(np.float32)
        elif k == "bias":
            out[k] = rng.normal(0.0, 0.3, np.shape(v)).astype(np.float32)
        else:
            out[k] = np.asarray(v)
    return out


def _prep(module, x, seed=0):
    """init on float32, randomize BN/affine leaves, return (flax_out,
    plain-numpy variables)."""
    variables = module.init(jax.random.PRNGKey(seed), x, train=False)
    plain = jax.tree_util.tree_map(np.asarray, variables)
    plain = {k: _randomize_dict(dict(v), np.random.default_rng(seed + 1))
             for k, v in dict(plain).items()}
    out = module.apply(plain, x, train=False)
    return np.asarray(out), plain


def test_conv3d_oracle_is_a_direct_conv():
    """The oracle itself, pinned on a hand-checkable case: 1-D identity
    kernel and a known sum."""
    x = np.arange(2 * 3 * 3 * 1, dtype=np.float64).reshape(1, 2, 3, 3, 1)
    w = np.ones((1, 2, 2, 1, 1))
    out = oracle.conv3d(x, w, (1, 1, 1), ((0, 0), (0, 0), (0, 0)))
    assert out.shape == (1, 2, 2, 2, 1)
    # top-left window of frame 0: 0+1+3+4
    assert out[0, 0, 0, 0, 0] == 8.0


def test_factored_channels_formula_pinned():
    """Hand-computed M_i values from the paper's parameter-matching
    formula, floor(t*d^2*Ni-1*No / (d^2*Ni-1 + t*No)) — literal
    expectations, not a comparison against a copy of the code."""
    assert factored_channels(3, 64, 3, 7) == 83      # stem
    assert factored_channels(64, 64, 3, 3) == 144    # layer 2 blocks
    assert factored_channels(64, 128, 3, 3) == 230   # layer 3 entry
    assert factored_channels(128, 256, 3, 3) == 460  # layer 4 entry
    assert factored_channels(256, 512, 3, 3) == 921  # layer 5 entry


@pytest.mark.parametrize("kernel,stride", [((3, 3), (1, 1)),
                                           ((3, 7), (1, 2)),
                                           ((3, 3), (2, 2)),
                                           ((1, 1), (2, 2))])
def test_spatiotemporal_conv_matches_oracle(kernel, stride):
    rng = np.random.default_rng(42)
    x = jnp.asarray(rng.normal(size=(2, 4, 6, 6, 3)).astype(np.float32))
    module = SpatioTemporalConv(5, kernel=kernel, stride=stride,
                                dtype=jnp.float32)
    flax_out, plain = _prep(module, x)
    ora = oracle.spatiotemporal_conv(plain, np.asarray(x), kernel, stride)
    assert flax_out.shape == ora.shape
    np.testing.assert_allclose(flax_out, ora, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("downsample,factored",
                         [(False, False), (True, False), (True, True)])
def test_res_block_matches_oracle(downsample, factored):
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(1, 4, 6, 6, 4)).astype(np.float32))
    module = SpatioTemporalResBlock(4, downsample=downsample,
                                    factored_shortcut=factored,
                                    dtype=jnp.float32)
    flax_out, plain = _prep(module, x)
    ora = oracle.res_block(plain, np.asarray(x), downsample=downsample,
                           factored_shortcut=factored)
    assert flax_out.shape == ora.shape
    np.testing.assert_allclose(flax_out, ora, rtol=2e-4, atol=2e-4)


def test_full_net_matches_oracle():
    """The real R18 architecture (layer sizes 2,2,2,2) end to end on a
    spatially small input — stem padding, every stage's downsampling
    schedule, the factored widths, and the global pool all in play."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(1, 8, 16, 16, 3)).astype(np.float32))
    module = R2Plus1DNet(dtype=jnp.float32)
    flax_out, plain = _prep(module, x)
    ora = oracle.r2plus1d_net(plain, np.asarray(x))
    assert flax_out.shape == (1, 512)
    np.testing.assert_allclose(flax_out, ora, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("start,end", [(2, 2), (2, 4), (3, 5)])
def test_partial_ranges_match_oracle_and_shape_table(start, end):
    rng = np.random.default_rng(start * 10 + end)
    t, h, w, c = LAYER_INPUT_SHAPES[start]
    # small spatial extent, true channel count (channels drive the
    # factored widths); T matters for the stride-2 temporal path
    x = jnp.asarray(rng.normal(size=(1, t, 8, 8, c)).astype(np.float32))
    module = R2Plus1DNet(start=start, end=end, dtype=jnp.float32)
    flax_out, plain = _prep(module, x)
    ora = oracle.r2plus1d_net(plain, np.asarray(x), start=start, end=end)
    assert flax_out.shape == ora.shape
    np.testing.assert_allclose(flax_out, ora, rtol=5e-4, atol=5e-4)


def test_classifier_matches_oracle():
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(1, 8, 16, 16, 3)).astype(np.float32))
    module = R2Plus1DClassifier(num_classes=10, dtype=jnp.float32)
    flax_out, plain = _prep(module, x)
    ora = oracle.r2plus1d_classifier(plain, np.asarray(x))
    assert flax_out.shape == (1, 10)
    np.testing.assert_allclose(flax_out, ora, rtol=5e-4, atol=5e-4)


def test_golden_logits_fixture():
    """One seeded full-net float32 forward pinned to a committed
    fixture — catches silent numerical drift (padding defaults, BN
    epsilon, init changes) between rounds. Regenerate deliberately
    with scripts/make_golden_logits.py when the architecture changes
    on purpose.

    Provenance: regenerated in PR 21 (2026-09-26) on the CPU backend
    under jax 0.9.0 / jaxlib 0.9.0 / flax 0.12.3, the installation the
    sandbox and the chip machine share. The fixture depends on how
    flax folds the init RNG, so it is tied to that installation: the
    one it replaced, made 2026-08-04 under another flax, failed at
    identical seeds with logits uncorrelated to these (corr 0.02:
    other draws, not a precision drift). The network arithmetic itself is pinned
    independently of init by the numpy-oracle tests above, which
    feed IDENTICAL parameter arrays to both implementations."""
    golden = np.load(GOLDEN_PATH)
    rng = np.random.default_rng(int(golden["input_seed"]))
    x = jnp.asarray(
        rng.normal(size=tuple(golden["input_shape"])).astype(np.float32))
    module = R2Plus1DClassifier(dtype=jnp.float32)
    variables = module.init(jax.random.PRNGKey(int(golden["param_seed"])),
                            x, train=False)
    out = np.asarray(module.apply(variables, x, train=False))
    np.testing.assert_allclose(out, golden["logits"], rtol=1e-3, atol=1e-3)


def test_range_output_shape_agrees_with_oracle():
    """The runtime's ring-sizing shape table vs shapes the oracle
    actually produces (the reference hardcoded this and documented the
    partial case broken, TODO #69)."""
    for start, end in [(1, 1), (1, 2), (2, 4), (3, 4), (4, 4)]:
        t, h, w, c = LAYER_INPUT_SHAPES[start]
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(1, t, h, w, c)).astype(np.float32))
        module = R2Plus1DNet(start=start, end=end, dtype=jnp.float32)
        variables = module.init(jax.random.PRNGKey(0), x, train=False)
        out = module.apply(variables, x, train=False)
        expect = range_output_shape(start, end, consecutive_frames=t)
        assert tuple(out.shape[1:]) == expect, (start, end)
