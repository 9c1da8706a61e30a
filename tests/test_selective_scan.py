"""``rnb_tpu.ops.selective_scan``, Mamba-1's scan with a decay per
(channel, state), on the CPU in Pallas's interpret mode: the kernel
against the token-by-token recurrence across row boundaries and
``row_first`` resets, at the draw's extremes (``A = -16`` with steps of
0.1, ``A = -1`` with steps of 0.001) and at the draw itself, with
float32 and bfloat16 states; packing that is invisible; the memory
beside the gated output; the kernel against the slab form it replaced
(``tests/selective_scan_slabs.py``), to the bit; and the kernel and a
whole Mamba layer lowered and compiled at the published shape (5,120
channels, 16 states, 128 rows) for a described v5e (the topology inside
a fixture), where no array of the layer may be copied between layouts.
Nothing here needs the native decode library or a chip."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

Q, CHANNELS, STATES = 16, 256, 16

#: the rows that open a request, the last of them the last row
POOLS = {
    "three_requests_and_a_pad_row": [0, 3, 5, 7],
    "one_request": [0],
    "every_row_its_own": [0, 1, 2, 3],
    "first_row_unmarked": [2, 5],
}


def first_of(pool, rows=8):
    first = np.zeros(rows, bool)
    first[POOLS[pool]] = True
    return first


def draw(seed, rows, extreme=None, dtype="float32"):
    """A pool as a Mamba layer hands it to the scan: x and z in
    ``dtype``, steps log-uniform in [0.001, 0.1] and ``A = -(1 .. N)``
    (the draw), or every decay at one corner of it (``extreme``: (A,
    dt))."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)

    def n(*shape, dtype=jnp.float32):
        return jnp.asarray(rng.standard_normal(shape), dtype)
    a = -np.tile(np.arange(1, STATES + 1, dtype=np.float32), (CHANNELS, 1))
    dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1),
                            (rows, Q, CHANNELS))).astype(np.float32)
    if extreme is not None:
        a[:], dt[:] = extreme
    act = getattr(jnp, dtype)
    return (n(rows, Q, CHANNELS, dtype=act), jnp.asarray(dt),
            jnp.asarray(a), n(rows, Q, STATES), n(rows, Q, STATES),
            jnp.asarray(rng.uniform(0.5, 1.5, CHANNELS), jnp.float32),
            n(rows, Q, CHANNELS, dtype=act))


#: for a comparison to the bit between two programs: in interpret mode a
#: kernel's body is a CPU program, and fused, the CPU's compiler rounds
#: two bodies of the same operations apart (a multiply and an add
#: contracted in one and not in the other: 1e-6 of the values). Unfused,
#: every operation is rounded on its own, as the chip's vector unit does
UNFUSED = {"xla_disable_hlo_passes": "fusion,cpu-instruction-fusion"}


_UNFUSED_PROGRAMS = {}


def run(operands, first, unfused=False, slabs=False, **kwargs):
    """The kernel's outputs as float32 arrays (``slabs``: the slab
    form's, ``tests/selective_scan_slabs.py``); ``unfused`` compiles the
    program ``UNFUSED``, once a form, options and shapes."""
    import jax
    import jax.numpy as jnp
    if slabs:
        import selective_scan_slabs as form
    else:
        from rnb_tpu.ops import selective_scan as form
    args = (*operands, jnp.asarray(first))

    def scan(*args):
        return form.selective_scan(*args, interpret=True, **kwargs)
    if unfused:
        key = (slabs, str(sorted(kwargs.items())),
               tuple((a.shape, str(a.dtype)) for a in args))
        if key not in _UNFUSED_PROGRAMS:
            _UNFUSED_PROGRAMS[key] = jax.jit(scan).lower(*args).compile(
                compiler_options=UNFUSED)
        scan = _UNFUSED_PROGRAMS[key]
    out = scan(*args)
    return [np.asarray(o, np.float32) for o in
            (out if isinstance(out, tuple) else (out,))]


def recurrence(operands, first):
    import jax.numpy as jnp

    from rnb_tpu.ops import selective_scan as ss
    # the kernel's first row starts from zero whatever ``row_first`` says
    first = np.asarray(first).copy()
    first[0] = True
    return [np.asarray(o) for o in ss.recurrence(*operands,
                                                 jnp.asarray(first))]


EXTREMES = {"the_draw": None, "fast_decay": (-16.0, 0.1),
            "slow_decay": (-1.0, 0.001)}


@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("extreme", sorted(EXTREMES))
def test_the_kernel_is_the_recurrence(extreme, pool):
    """float32 operands: the kernel's gated output and its memory equal
    the token-by-token recurrence to float32 rounding, across row
    boundaries and resets (a state of 127 tokens' sums at a decay of
    exp(-0.001) a token: the slow corner is where rounding adds up)."""
    first = first_of(pool)
    operands = draw(1, 8, EXTREMES[extreme])
    (out, memory), (want, want_memory) = \
        run(operands, first, memory=True), recurrence(operands, first)
    scale = 1.0 + np.abs(want_memory).max()
    assert np.abs(memory - want_memory).max() < 2e-5 * scale
    assert np.abs(out - want).max() < 2e-5 * (1.0 + np.abs(want).max())


def test_the_memory_is_the_output_before_the_gate():
    first = first_of("three_requests_and_a_pad_row")
    operands = draw(2, 8)
    alone, = run(operands, first, unfused=True)
    out, memory = run(operands, first, unfused=True, memory=True)
    assert np.array_equal(alone, out)
    z = np.asarray(operands[6], np.float32)
    assert np.allclose(out, memory * z / (1 + np.exp(-z)), rtol=1e-5,
                       atol=1e-6)


def test_bfloat16_operands_round_once():
    """x and z in bfloat16, as the layer hands them: the kernel widens
    them in VMEM and rounds its outputs once."""
    first = first_of("three_requests_and_a_pad_row")
    operands = draw(3, 8, dtype="bfloat16")
    (out, memory), (want, want_memory) = \
        run(operands, first, memory=True), recurrence(operands, first)
    for got, ref in ((out, want), (memory, want_memory)):
        assert np.abs(got - ref).max() < 2 ** -8 * (1.0 + np.abs(ref).max())


def test_packing_is_invisible():
    """A request's rows give the same bits alone and behind another
    request: the states are zeroed at its first row."""
    operands = draw(4, 8)
    first = first_of("three_requests_and_a_pad_row")
    packed, = run(operands, first)
    alone, = run(tuple(o[3:5] if o.shape[0] == 8 else o for o in operands),
                 np.array([True, False]))
    assert np.array_equal(packed[3:5], alone)


def test_the_first_row_starts_from_zero_whatever_it_says():
    operands = draw(5, 4)
    said, = run(operands, np.array([True, False, True, False]))
    unsaid, = run(operands, np.array([False, False, True, False]))
    assert np.array_equal(said, unsaid)


@pytest.mark.parametrize("extreme", ["fast_decay", "slow_decay"])
def test_a_bfloat16_state_moves_the_result(extreme):
    """The control arm's states, rounded to bfloat16 between rows: the
    result moves and stays close — least where a state is gone in a few
    tokens (the fast corner: exp(-1.6) a token), most where it outlives
    the rows (the slow one)."""
    import jax.numpy as jnp
    first = first_of("one_request")
    operands = draw(6, 8, EXTREMES[extreme])
    exact, = run(operands, first)
    rounded, = run(operands, first, state_dtype=jnp.bfloat16)
    moved = np.abs(rounded - exact).max() / (1.0 + np.abs(exact).max())
    low, high = (1e-4, 2e-2) if extreme == "slow_decay" else (1e-6, 1e-3)
    assert low < moved < high, moved


def test_a_grid_step_is_a_row_of_all_the_channels():
    """A step takes the pool's own block, (Q tokens, the published 5,120
    channels): five registers a state, and a turn is the 8 sublanes of a
    float32 register."""
    from rnb_tpu.ops import selective_scan as ss
    assert ss.step_channels(5120) == 5120 == 5 * 8 * 128
    assert ss.step_channels(2 * 5120) == 5120
    assert ss.step_channels(256) == 256
    assert ss._UNROLL == 8
    assert 128 % ss._UNROLL == 0 and Q % ss._UNROLL == 0


# -- the slab form it replaced, to the bit ---------------------------------

#: (memory, the states' dtype between rows): a compiled pair each
TWINS = {"gated_alone": (False, "float32"),
         "with_the_memory": (True, "float32"),
         "bfloat16_states": (True, "bfloat16")}
#: the rows that open a request in a pool of four: one, two and three
#: requests, the last with a row of its own
REQUESTS = {"one": [0], "two": [0, 2], "three": [0, 1, 3]}


@pytest.mark.parametrize("requests", sorted(REQUESTS))
@pytest.mark.parametrize("extreme", sorted(EXTREMES))
@pytest.mark.parametrize("twin", sorted(TWINS))
def test_the_kernel_is_the_slab_form_to_the_bit(twin, extreme, requests):
    """bfloat16 x and z, as the layer hands them: the token loop's body
    kept its float32 operations in their order, so the gated output and
    the memory equal PR 59's kernel behind its copies bit for bit."""
    import jax.numpy as jnp
    memory, state_dtype = TWINS[twin]
    first = np.zeros(4, bool)
    first[REQUESTS[requests]] = True
    operands = draw(7, 4, EXTREMES[extreme], dtype="bfloat16")
    got, want = (run(operands, first, unfused=True, slabs=slabs,
                     memory=memory, state_dtype=jnp.dtype(state_dtype))
                 for slabs in (False, True))
    assert len(got) == len(want) == 1 + memory
    for new, old in zip(got, want):
        assert np.array_equal(new, old)


# -- the published shape, compiled for a described v5e --------------------


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


ROWS, CHANNELS_PUBLISHED = 128, 5120


def relayouts(text, elements):
    """The copies, transposes and reshapes (a bitcast is none) of a
    compiled program whose result has ``elements`` elements."""
    import re
    return [line.strip()[:160] for line in text.splitlines()
            for found in [re.search(
                r"= \w+\[([\d,]+)\]\S* (copy|transpose|reshape)\(", line)]
            if found and np.prod(
                [int(d) for d in found.group(1).split(",")]) == elements]


def test_the_kernel_compiles_at_the_published_shape(one_chip):
    """Mosaic takes the kernel at 5,120 channels of 16 states and 128
    rows of 128 tokens (nothing runs), layer 16's form with the memory:
    one custom call, the states' scratch, the turns and a row's blocks
    under the kernel's own limit on VMEM, and beside the call no copy,
    transpose or reshape of an operand or a result."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import selective_scan as ss
    rows, q, channels, states = ROWS, 128, CHANNELS_PUBLISHED, 16

    def of(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    f32 = jnp.float32
    compiled = jax.jit(
        lambda *operands: ss.selective_scan(*operands, memory=True)).lower(
        of((rows, q, channels)), of((rows, q, channels), f32),
        of((channels, states), f32), of((rows, q, states), f32),
        of((rows, q, states), f32), of((channels,), f32),
        of((rows, q, channels)), of((rows,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert ss.KERNEL_NAME in text
    assert relayouts(text, rows * q * channels) == []


def test_a_mamba_layer_copies_no_array_between_layouts(one_chip):
    """The whole mixer at the published widths, layer 16's form: the
    scan reads ``x`` where the convolution wrote it, ``z`` and the steps
    where their products did (the bias and the softplus are the step
    product's epilogue) and ``out_proj`` reads the gated result where
    the scan wrote it: two kernels, and no copy, transpose or reshape of
    a (rows, Q, 5,120) array. The slab form in its place is the proof
    that the search finds them."""
    import json

    import jax
    import jax.numpy as jnp
    import selective_scan_slabs as slabs

    from rnb_tpu.models.phi4_flash import network
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "phi4-mini-flash.json")) as f:
        cfg = network.Phi4FlashConfig.from_published(json.load(f))
    assert cfg.d_inner == CHANNELS_PUBLISHED
    hidden, di, n, rank = (cfg.hidden_size, cfg.d_inner, cfg.mamba_d_state,
                           cfg.dt_rank)
    f32 = jnp.float32

    def of(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    p = {"in_proj": of((hidden, 2 * di)), "conv_w": of((di, cfg.mamba_d_conv)),
         "conv_b": of((di,)), "x_proj": of((di, rank + 2 * n)),
         "dt_proj": of((rank, di)), "dt_bias": of((di,), f32),
         "a_log": of((di, n), f32), "d": of((di,), f32),
         "out_proj": of((di, hidden))}

    def compiled(scan):
        def layer(p, u, first):
            return network.mamba_mixer(cfg, p, u, first, memory=True)
        kept, network.selective_scan = network.selective_scan, scan
        try:
            return jax.jit(layer).lower(
                p, of((ROWS, cfg.chunk_size, hidden)),
                of((ROWS,), jnp.bool_)).compile().as_text()
        finally:
            network.selective_scan = kept
    elements = ROWS * cfg.chunk_size * di
    text = compiled(network.selective_scan)
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2
    assert relayouts(text, elements) == []
    assert len(relayouts(compiled(slabs), elements)) >= 5
