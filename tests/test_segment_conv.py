"""``ops/ssd.segment_conv1d``, the causal depthwise convolution in front
of the scan and of the delta rule as one Pallas kernel, in interpret mode
against the ``jax.numpy`` passes it replaced (``passes``, kept here as
the oracle): both callers' channel counts, pools whose requests open at
the pool's first row, inside a grid step and at a step's first row, pad
rows, a one-row pool, the filter's length, the bias, the activation and
the dtypes on both sides. Then, where a v5e can be described, the
compile of the kernel at both callers' real shapes (the topology inside
a fixture)."""

import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: name -> the rows that open a request (a pad row opens its own); a grid
#: step takes ``ssd._CONV_ROWS`` = 16 rows where the pool's rows allow
POOLS = {
    "one_row": [0],
    "opens_at_the_pools_first_row": [0, 3],
    "opens_inside_a_step": [0, 5, 21, 31],
    "opens_at_a_steps_first_row": [0, 16, 32, 47],
    "pad_rows": [0, 2, 3, 4, 15, 16, 17, 31],
    "every_row_its_own": [0, 1, 2],
}

#: caller -> (channels, a bias?, the dtype the kernel writes: the
#: activations' (None) or float32)
CALLERS = {"nemotron_h": (6144, True, None),
           "qwen3_next": (8192, False, "float32"),
           "falcon_h1": (5120, True, None),
           "phi4_flash": (5120, True, None)}


def first_of(pool):
    opens = POOLS[pool]
    first = np.zeros(opens[-1] + 1, bool)
    first[opens] = True
    return first


def passes(x, weight, bias, row_first):
    """``ops/ssd.segment_conv1d`` until PR 48: four float32 passes over
    the whole (tokens, channels) array. -> float32 (rows, Q, C)."""
    import jax.numpy as jnp
    rows, q, c = x.shape
    k_taps = weight.shape[1]
    flat = x.reshape(rows * q, c).astype(jnp.float32)
    w = weight.astype(jnp.float32)
    out = flat * w[:, k_taps - 1] + bias.astype(jnp.float32)
    col = jnp.arange(q)
    for k in range(1, k_taps):
        shifted = jnp.pad(flat, ((k, 0), (0, 0)))[:rows * q]
        # the k-th token back lies before the request's first token
        # for the first k tokens of the request's first row
        live = ~(row_first[:, None] & (col[None, :] < k))
        out = out + jnp.where(live.reshape(-1, 1), shifted, 0.0) \
            * w[:, k_taps - 1 - k]
    return out.reshape(rows, q, c)


def inputs(rows, q, c, taps, dtype, seed=0):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((rows, q, c)), dtype),
            jnp.asarray(rng.standard_normal((c, taps)) * 0.5, jnp.bfloat16),
            jnp.asarray(rng.standard_normal(c), jnp.bfloat16))


def want_of(x, weight, bias, first, activation, out_dtype):
    """The callers' lines before PR 48: the passes, the caller's SiLU in
    float32, one rounding."""
    import jax
    import jax.numpy as jnp
    if bias is None:
        bias = jnp.zeros(x.shape[-1], jnp.float32)
    out = passes(x, weight, bias, jnp.asarray(first))
    if activation == "silu":
        out = jax.nn.silu(out)
    return np.asarray(out.astype(out_dtype).astype(jnp.float32))


def conv(x, weight, bias, first, **kwargs):
    import jax.numpy as jnp

    from rnb_tpu.ops import ssd
    return ssd.segment_conv1d(x, weight, bias, jnp.asarray(first),
                              interpret=True, **kwargs)


def close(got, want, out_dtype):
    """Float32 rounding of the sum and of the SiLU, and where the kernel
    rounds to bfloat16 the one rounding: an element whose float32 value
    lies at a rounding boundary may fall to either side."""
    import jax.numpy as jnp
    assert got.dtype == jnp.dtype(out_dtype), got.dtype
    got = np.asarray(got.astype(jnp.float32))
    assert got.shape == want.shape
    slack = 2.0 ** -8 if jnp.dtype(out_dtype).itemsize == 2 else 1e-5
    assert (np.abs(got - want) <= slack * np.abs(want) + 1e-6).all(), \
        np.abs(got - want).max()


# -- the kernel against the passes it replaced ----------------------------------


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("caller", sorted(CALLERS))
def test_the_kernel_is_the_passes_it_replaced(caller, pool, dtype):
    """The callers' forms at their channel counts (twelve, sixteen and
    ten lane tiles of 512): the history is zero at ``row_first`` and nowhere
    else, whether the row lies first in the pool, inside a grid step or
    first in a step, and a pad row after a pad row reads nothing."""
    channels, biased, out_dtype = CALLERS[caller]
    first = first_of(pool)
    x, weight, bias = inputs(len(first), 16, channels, 4, dtype,
                             seed=len(first))
    bias = bias if biased else None
    out_dtype = out_dtype or dtype
    got = conv(x, weight, bias, first, activation="silu",
               out_dtype=out_dtype)
    close(got, want_of(x, weight, bias, first, "silu", out_dtype), out_dtype)


@pytest.mark.parametrize("out_dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("activation", [None, "silu"])
@pytest.mark.parametrize("biased", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("taps", [2, 4])
def test_taps_bias_activation_and_rounding(taps, biased, activation,
                                           out_dtype):
    """What states the mathematics is an argument: K from the weight's
    shape, a bias or none, the activation on the float32 sum, the dtype
    the one rounding goes to. Rows of 32 tokens: the history is the row
    before's last sublane tile, not the row whole."""
    first = first_of("opens_inside_a_step")
    x, weight, bias = inputs(len(first), 32, 256, taps, "bfloat16", seed=taps)
    bias = bias if biased else None
    got = conv(x, weight, bias, first, activation=activation,
               out_dtype=out_dtype)
    close(got, want_of(x, weight, bias, first, activation, out_dtype),
          out_dtype)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_packing_is_invisible(dtype):
    """A request alone equals the same request packed behind another and
    a pad row, bit for bit: nothing crosses ``row_first``."""
    import jax.numpy as jnp
    alone, weight, bias = inputs(9, 16, 384, 4, dtype, seed=3)
    ahead = inputs(10, 16, 384, 4, dtype, seed=4)[0]
    packed = jnp.concatenate([ahead, alone])
    first = np.zeros(19, bool)
    first[[0, 6, 9, 10]] = True
    got = conv(packed, weight, bias, first, activation="silu")[10:]
    want = conv(alone, weight, bias, first[10:], activation="silu")
    assert np.array_equal(np.asarray(got), np.asarray(want))


#: name -> (channels, Q, the parts a caller takes: channels and the
#: dtype the kernel writes of each)
SPLITS = {
    "nemotron_h": (6144, 16, ((4096, "bfloat16"), (1024, "bfloat16"),
                              (1024, "bfloat16"))),
    "qwen3_next": (8192, 16, ((4096, "float32"), (4096, "bfloat16"))),
    "falcon_h1": (5120, 16, ((4096, "bfloat16"), (512, "bfloat16"),
                             (512, "bfloat16"))),
    "phi4_flash": (5120, 16, ((5120, "bfloat16"),)),
    "toy_nemotron_h": (128, 16, ((64, "bfloat16"), (32, "bfloat16"),
                                 (32, "bfloat16"))),
    "toy_qwen3_next": (128, 32, ((64, "float32"), (64, "bfloat16"))),
    "one_part": (256, 16, ((256, "float32"),)),
    "narrow_parts_first": (1536, 16, ((512, "float32"), (1024, "bfloat16"))),
}


@pytest.mark.parametrize("pool", ["opens_inside_a_step", "one_row"])
@pytest.mark.parametrize("name", sorted(SPLITS))
def test_the_parts_a_caller_takes_leave_the_kernel_as_arrays(name, pool):
    """``split``: the channels side by side in ``x`` leave the kernel as
    an array each, in a dtype each — Nemotron-H's xs, B and C, Qwen3-Next's
    q with k in float32 and v rounded by the kernel, the toys' narrow
    ones — and each is those columns of the whole convolution, rounded
    once, bit for bit: a part's block waits in VMEM while the grid walks
    the other parts' lane tiles."""
    import jax.numpy as jnp
    channels, q, parts = SPLITS[name]
    first = first_of(pool)
    x, weight, bias = inputs(len(first), q, channels, 4, "bfloat16", seed=8)
    got = conv(x, weight, bias, first, activation="silu",
               out_dtype=tuple(dtype for _, dtype in parts),
               split=tuple(count for count, _ in parts))
    whole = conv(x, weight, bias, first, activation="silu")
    assert isinstance(got, tuple) and len(got) == len(parts)
    at = 0
    for mine, (count, dtype) in zip(got, parts):
        assert mine.dtype == jnp.dtype(dtype)
        assert mine.shape == x.shape[:2] + (count,)
        assert np.array_equal(
            np.asarray(mine.astype(jnp.float32)),
            np.asarray(whole[..., at:at + count].astype(dtype)
                       .astype(jnp.float32)))
        at += count


def test_the_first_row_starts_from_nothing_whatever_it_says():
    """Row 0 has no row before it: the clipped view of the history reads
    the row itself, and the kernel takes none of it."""
    x, weight, bias = inputs(3, 16, 256, 4, "bfloat16", seed=5)
    assert np.array_equal(
        np.asarray(conv(x, weight, bias, [False, False, True])),
        np.asarray(conv(x, weight, bias, [True, False, True])))


@pytest.mark.parametrize("rows,q,c,itemsize,want", [
    (64, 128, 6144, 2, (16, 512, 16)), (128, 128, 8192, 2, (16, 512, 16)),
    (112, 128, 4096, 2, (16, 512, 16)), (80, 128, 1024, 4, (16, 512, 8)),
    (3, 16, 96, 4, (3, 96, 8)), (7, 16, 384, 2, (7, 128, 16)),
    (12, 8, 768, 2, (12, 256, 8)), (24, 128, 1024, 2, (12, 512, 16)),
    (8, 16, 32, 2, (8, 32, 16))])
def test_the_tiles_follow_the_input(rows, q, c, itemsize, want):
    """Whole rows a step, a lane tile that divides the channels, the
    history one sublane tile of the input's dtype or the row whole."""
    from rnb_tpu.ops import ssd
    assert ssd._conv_tiles(rows, q, c, itemsize) == want


def test_a_longer_filter_than_the_history_is_refused():
    x, weight, bias = inputs(2, 8, 128, 10, "float32")
    with pytest.raises(AssertionError):
        conv(x, weight, bias, [True, False])


# -- the real shapes, compiled for a described v5e ------------------------------


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # a compile for a described chip cannot be read back from the
    # persistent cache: off for these tests, and as it was behind them
    # (the worker goes on to other files' tests)
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


#: caller -> the rows of its largest dispatch
REAL_ROWS = {"nemotron_h": 64, "qwen3_next": 128, "falcon_h1": 64,
             "phi4_flash": 128}


@pytest.mark.parametrize("caller", sorted(CALLERS))
def test_the_kernel_compiles_at_a_callers_shapes(one_chip, caller):
    """Mosaic takes the kernel at the real widths (nothing runs), one
    call for all the arrays the caller takes, and nothing is left for
    XLA: no float32 (tokens, channels) temporary, no slice or copy of
    the activations in front of the kernel or behind it."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import ssd
    channels, biased, _ = CALLERS[caller]
    rows, (_, _, parts) = REAL_ROWS[caller], SPLITS[caller]

    def of(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(
        lambda x, w, b, first: ssd.segment_conv1d(
            x, w, b if biased else None, first, activation="silu",
            out_dtype=tuple(dtype for _, dtype in parts),
            split=tuple(count for count, _ in parts))).lower(
        of((rows, 128, channels)), of((channels, 4)), of((channels,)),
        of((rows,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert ssd.CONV_KERNEL_NAME in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
    assert not re.findall(
        r"= \w+\[%d,128,\d+\]\S* (?:copy|slice|transpose)\(" % rows, text)
