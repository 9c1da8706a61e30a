"""``ops/ssd.ssd_scan``, the state-space scan as one Pallas kernel, in
interpret mode at tiny shapes against the recurrence written out token
by token: both callers' forms (Nemotron-H's: steps and a skip term,
eight heads of 64 a group; lightning's: unit steps, a group a head of
128), pools whose requests span rows, share a pool and are parted by a
pad row, packing, the carried state's precision, the blocked
``jax.numpy`` form the kernel replaced (kept here as an oracle), and
the M block's gated norm as the kernel's last lines, and Falcon-H1's
shape (32 heads of 128 in 2 groups, a state of 256), and lightning's
form with the mixer's lines inside (``lightning_raw``: q and k as their
products wrote them, the head norms, the rotation, the scale and the
rounding the kernel's first lines, the output norm over all the heads
and the gate its last, two grid steps a row). Then, where a v5e
can be described, the compile of the kernel at the callers' real shapes
(the topology inside a fixture)."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

Q, N = 16, 16

#: name -> (heads, groups, P, steps and skip term?)
FORMS = {"mamba": (8, 1, 64, True), "mamba_two_groups": (16, 2, 64, True),
         "lightning": (2, 2, 128, False), "narrow_heads": (8, 2, 8, True)}

#: Falcon-H1's own shape, (heads, groups, P, N): a group is 16 heads of
#: 128 = 2,048 lanes, one group a grid step, a state of 256 x 2,048
FALCON_H1 = (32, 2, 128, 256)

#: name -> the rows that open a request (a pad row opens its own)
POOLS = {"one_row": [1], "a_request_over_rows": [1, 0, 0, 0],
         "two_requests": [1, 0, 0, 1, 0], "a_pad_row_between": [1, 0, 1, 1, 0],
         "every_row_its_own": [1, 1, 1]}


def inputs(form, rows, seed=0, dtype="bfloat16"):
    """-> (xs, dt | None, a, b, c, d | None) as ``ssd_scan`` takes them."""
    import jax.numpy as jnp
    if form == "falcon_h1":
        (heads, groups, p, n), full = FALCON_H1, True
    else:
        (heads, groups, p, full), n = FORMS[form], N
    rng = np.random.default_rng(seed)
    xs, b, c = (jnp.asarray(rng.standard_normal(shape), dtype)
                for shape in ((rows, Q, heads, p), (rows, Q, groups, n),
                              (rows, Q, groups, n)))
    a = jnp.asarray(-rng.uniform(0.05, 1.0, heads), jnp.float32)
    if not full:
        return xs, None, a, b, c, None
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (rows, Q, heads)), jnp.float32)
    return xs, dt, a, b, c, jnp.asarray(rng.standard_normal(heads),
                                        jnp.float32)


def recurrence(xs, dt, a, b, c, d, first):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t xs_t (x) B_t``, ``y_t = S_t C_t
    + D xs_t`` in float64, the state zero where ``first`` says so."""
    xs, b, c = (np.asarray(v.astype("float32"), np.float64)
                for v in (xs, b, c))
    rows, q, heads, p = xs.shape
    per = heads // b.shape[2]
    a = np.asarray(a, np.float64)
    dt = np.ones((rows, q, heads)) if dt is None else np.asarray(dt,
                                                                  np.float64)
    out = np.zeros(xs.shape)
    state = np.zeros((heads, p, b.shape[3]))
    for r in range(rows):
        if first[r]:
            state[:] = 0.0
        for t in range(q):
            bt, ct = (np.repeat(v[r, t], per, axis=0) for v in (b, c))
            state = np.exp(dt[r, t] * a)[:, None, None] * state \
                + (dt[r, t][:, None] * xs[r, t])[:, :, None] * bt[:, None, :]
            out[r, t] = np.einsum("hpn,hn->hp", state, ct)
            if d is not None:
                out[r, t] += np.asarray(d, np.float64)[:, None] * xs[r, t]
    return out


def blocked(xs, dt, a, b, c, d, row_first, state_dtype=None):
    """The blocked ``jax.numpy`` form that was ``ops/ssd.ssd_scan`` until
    PR 47: a row's tokens as one masked ``Q x Q`` product, the states
    carried by one ``rows x rows`` matrix of decays a head."""
    import jax.numpy as jnp
    from jax import lax
    state_dtype = state_dtype or jnp.float32
    highest = lax.Precision.HIGHEST
    rows, q, heads, p = xs.shape
    groups = b.shape[2]
    per = heads // groups
    xg = xs.reshape(rows, q, groups, per, p)
    la = jnp.broadcast_to(a, (1, q, heads)) if dt is None else dt * a
    cs = jnp.cumsum(la, axis=1)
    csg = cs.reshape(cs.shape[0], q, groups, per)
    cb = jnp.einsum("rign,rjgn->rgij", c, b,
                    preferred_element_type=jnp.float32)
    csh = csg.transpose(0, 2, 3, 1)
    tril = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(
        tril, csh[..., :, None] - csh[..., None, :], -jnp.inf))
    scores = cb[:, :, None] * decay
    to_end = jnp.exp(csg[:, -1:, :, :] - csg)
    if dt is not None:
        dtg = dt.reshape(rows, q, groups, per)
        scores = scores * dtg.transpose(0, 2, 3, 1)[..., None, :]
        to_end = to_end * dtg
    y = jnp.einsum("rghij,rjghp->righp", scores.astype(xs.dtype), xg,
                   preferred_element_type=jnp.float32)
    xw = xg.astype(jnp.float32) * to_end[..., None]
    state = jnp.einsum("rjghp,rjgn->rghpn", xw, b.astype(jnp.float32),
                       precision=highest)
    row_decay = jnp.broadcast_to(cs[:, -1, :], (rows, heads))
    cum = jnp.cumsum(row_decay, axis=0)
    seg = jnp.cumsum(row_first.astype(jnp.int32))
    idx = jnp.arange(rows)
    carry_ok = (idx[:, None] > idx[None, :]) \
        & (seg[:, None] == seg[None, :])
    log_m = (cum - row_decay)[:, None, :] - cum[None, :, :]
    m = jnp.exp(jnp.where(carry_ok[:, :, None], log_m, -jnp.inf))
    state = state.astype(state_dtype).astype(jnp.float32)
    incoming = jnp.einsum(
        "rqgh,qghpn->rghpn", m.reshape(rows, rows, groups, per), state,
        precision=highest)
    incoming = incoming.astype(state_dtype).astype(jnp.float32)
    y_in = jnp.einsum("rign,rghpn->righp", c.astype(jnp.float32),
                      incoming, precision=highest)
    y = y + y_in * jnp.exp(csg)[..., None]
    if d is not None:
        y = y + xg.astype(jnp.float32) \
            * d.reshape(groups, per)[None, None, :, :, None]
    return y.reshape(rows, q, heads, p)


def scan(args, first, **kwargs):
    import jax.numpy as jnp

    from rnb_tpu.ops import ssd
    return np.asarray(ssd.ssd_scan(*args, jnp.asarray(first, bool),
                                   interpret=True, **kwargs))


def worst(got, want):
    return np.abs(got - want).max() / (1.0 + np.abs(want).max())


# -- the kernel against the recurrence ------------------------------------------


@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_kernel_is_the_recurrence(form, pool):
    """The state is reset at ``row_first`` and nowhere else: a request
    that spans rows carries it, the next request and a pad row start
    from zero. The scores of a row are rounded to bfloat16 once, as the
    configuration states: 2e-2 of the largest output."""
    first = POOLS[pool]
    args = inputs(form, len(first), seed=len(first))
    got = scan(args, first)
    assert got.dtype == np.float32 and got.shape == args[0].shape
    assert worst(got, recurrence(*args, first)) < 2e-2


@pytest.mark.parametrize("gated", [False, True], ids=["plain", "gated_norm"])
@pytest.mark.parametrize("pool", ["a_request_over_rows", "a_pad_row_between"])
def test_falcon_h1s_shape_is_the_recurrence(pool, gated):
    """32 heads of 128 in 2 groups at a state of 256: a grid step is one
    group's 16 heads (``_groups_a_step``: a group is wider than
    ``_STEP_LANES``), its state 256 x 2,048 — against the recurrence
    token by token, without the gated norm and with it (the mean over a
    group's 2,048 columns)."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import ssd
    heads, groups, p, n = FALCON_H1
    assert ssd._groups_a_step(groups, heads // groups, p) == 1
    assert ssd._lane_tile(heads // groups, p) == (1, 128)
    first = POOLS[pool]
    args = inputs("falcon_h1", len(first), seed=53)
    assert args[3].shape == (len(first), Q, groups, n)
    want = recurrence(*args, first)
    if not gated:
        got = scan(args, first)
        assert got.dtype == np.float32
        assert worst(got, want) < 2e-2
        return
    rows, width = len(first), heads * p
    rng = np.random.default_rng(2)
    z = jnp.asarray(rng.standard_normal((rows, Q, width)), jnp.float32)
    weight = jnp.asarray(rng.uniform(0.5, 1.5, width), jnp.bfloat16)
    got = scan(args, first, gated_norm=(z, weight, 1e-5))
    assert got.dtype == args[0].dtype
    g = (want.reshape(rows, Q, width) * np.asarray(jax.nn.silu(z))) \
        .reshape(rows, Q, groups, -1)
    g = g / np.sqrt(np.mean(g * g, -1, keepdims=True) + 1e-5)
    want = g.reshape(rows, Q, width) * np.asarray(weight.astype(jnp.float32))
    got = np.asarray(got.astype(jnp.float32)).reshape(want.shape)
    # the within-row scores' rounding to bfloat16 and the result's own
    assert np.abs(got - want).max() < 3e-2 * np.abs(want).max()


@pytest.mark.parametrize("form", sorted(FORMS))
def test_float32_inputs_meet_the_recurrence_closely(form):
    """With float32 activations nothing is rounded to bfloat16 but the
    within-row products' passes on the CPU's default precision are
    float32 too: the kernel's algebra, to float32's last digits."""
    first = POOLS["two_requests"]
    args = inputs(form, len(first), seed=7, dtype="float32")
    assert worst(scan(args, first), recurrence(*args, first)) < 2e-5


@pytest.mark.parametrize("form", sorted(FORMS))
def test_packing_is_invisible(form):
    """A request alone equals the same request packed behind another
    and a pad row, bit for bit: nothing crosses ``row_first``."""
    alone = inputs(form, 2, seed=11)
    ahead = inputs(form, 3, seed=12)
    packed = tuple(
        None if mine is None else mine if mine.ndim == 1
        else np.concatenate([np.asarray(other.astype("float32")),
                             np.asarray(mine.astype("float32"))])
        .astype(mine.dtype)
        for other, mine in zip(ahead, alone))
    import jax.numpy as jnp
    packed = tuple(v if v is None else jnp.asarray(v) for v in packed)
    got = scan(packed, [1, 0, 1, 1, 0])[3:]
    assert np.array_equal(got, scan(alone, [1, 0]))


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_first_row_starts_from_zero_whatever_it_says(form):
    """Row 0 has nothing before it: a pool whose first row does not say
    it opens a request reads no state another step's heads left."""
    args = inputs(form, 3, seed=5)
    assert np.array_equal(scan(args, [0, 0, 1]), scan(args, [1, 0, 1]))


@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_bfloat16_state_moves_the_result(form):
    """``state_dtype`` still rounds the carried state: the lower
    precision control has something to fail on, and only where a state
    was carried."""
    import jax.numpy as jnp
    first = [1, 0, 0, 1]
    args = inputs(form, len(first), seed=3)
    exact = scan(args, first)
    rounded = scan(args, first, state_dtype=jnp.bfloat16)
    for row in (0, 3):
        assert np.array_equal(exact[row], rounded[row])
    moved = worst(rounded[1:3], exact[1:3])
    assert 1e-4 < moved < 5e-2, moved
    # with float32 activations the carried state's rounding is the
    # whole of the distance to the recurrence
    args = inputs(form, len(first), seed=3, dtype="float32")
    want = recurrence(*args, first)
    assert worst(scan(args, first, state_dtype=jnp.bfloat16), want) \
        > 100 * worst(scan(args, first), want)


@pytest.mark.parametrize("pool", ["a_request_over_rows", "a_pad_row_between"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_kernel_meets_the_blocked_form_it_replaced(form, pool):
    """The same recurrence in another association (the carry one
    multiply-add a row, not a ``rows x rows`` matrix of decays): float32
    rounding apart, the outputs agree."""
    import jax.numpy as jnp
    first = POOLS[pool]
    args = inputs(form, len(first), seed=21)
    want = np.asarray(blocked(*args, jnp.asarray(first, bool)))
    assert worst(scan(args, first), want) < 1e-5


@pytest.mark.parametrize("pool", ["one_row", "two_requests"])
@pytest.mark.parametrize("form", ["mamba", "mamba_two_groups",
                                  "narrow_heads"])
def test_the_gated_norm_is_the_kernels_last_lines(form, pool):
    """``gated_norm``: ``y silu(z)``, RMS-normed over each group's
    columns, times the weight, written in the activations' dtype — the
    same float32 lines on the scan's float32 output."""
    import jax
    import jax.numpy as jnp
    first = POOLS[pool]
    args = inputs(form, len(first), seed=9)
    rows, q, heads, p = args[0].shape
    groups = args[3].shape[2]
    rng = np.random.default_rng(1)
    z = jnp.asarray(rng.standard_normal((rows, q, heads * p)), jnp.float32)
    weight = jnp.asarray(rng.uniform(0.5, 1.5, heads * p), jnp.bfloat16)
    got = scan(args, first, gated_norm=(z, weight, 1e-5))
    assert got.dtype == args[0].dtype and got.shape == args[0].shape
    y = scan(args, first).reshape(rows, q, heads * p) \
        * np.asarray(jax.nn.silu(z))
    yg = y.reshape(rows, q, groups, -1)
    yg = yg / np.sqrt(np.mean(yg * yg, -1, keepdims=True) + 1e-5)
    want = yg.reshape(rows, q, heads * p) \
        * np.asarray(weight.astype(jnp.float32))
    got = np.asarray(got.astype(jnp.float32)).reshape(want.shape)
    # one rounding to bfloat16: 2^-8 of an element
    assert np.abs(got - want).max() <= 2.0 ** -8 * np.abs(want).max() + 1e-6


# -- lightning's form with the mixer's lines inside -------------------------------

#: (heads, P): 16 heads of 128 lanes are two grid steps of eight, so the
#: output norm's mean spans steps; q and k are ``N`` wide
RAW = (16, 128)
RAW_EPS = 1e-6


def raw_inputs(first, seed=0, dtype="bfloat16"):
    """What a lightning mixer hands the scan (PR 60): v in the
    activations' dtype, k, q and the gate float32 as their products
    wrote them, the three norms' weights, the decays, and the rotary
    tables of ``first``'s requests, positions restarting at each."""
    import jax.numpy as jnp

    from rnb_tpu.ops import banded
    heads, p = RAW
    rows = len(first)
    rng = np.random.default_rng(seed)
    row_start = np.maximum.accumulate(
        np.where(np.asarray(first, bool), np.arange(rows), 0))
    row_start[0] = 0
    inv_freq = (10000.0 ** (-np.arange(0, N, 2) / N)).astype(np.float32)
    cos, sin, _ = banded.band_tables(jnp.asarray(row_start, jnp.int32), Q,
                                     inv_freq)
    return dict(
        v=jnp.asarray(rng.standard_normal((rows, Q, heads, p)), dtype),
        k=jnp.asarray(rng.standard_normal((rows, Q, heads, N)) * 3.0,
                      jnp.float32),
        q=jnp.asarray(rng.standard_normal((rows, Q, heads, N)) * 0.3,
                      jnp.float32),
        gate=jnp.asarray(rng.standard_normal((rows, Q, heads * p)),
                         jnp.float32),
        a=jnp.asarray(-2.0 ** (-8.0 * np.arange(1, heads + 1) / heads),
                      jnp.float32),
        kw=jnp.asarray(rng.uniform(0.5, 1.5, N), dtype),
        qw=jnp.asarray(rng.uniform(0.5, 1.5, N), dtype),
        ow=jnp.asarray(rng.uniform(0.5, 1.5, heads * p), dtype),
        row_start=jnp.asarray(row_start, jnp.int32), inv_freq=inv_freq,
        cos=cos.reshape(rows, Q, N), sin=sin.reshape(rows, Q, N))


def raw_scan(x, first, lines="both", **kwargs):
    """The scan with the mixer's first lines, its last or both inside
    the kernel, the others as the mixer wrote them until PR 60:
    ``network.rms_norm``, ``rope.rotate``, the scale, the rounding; the
    norm over all the heads and the gate on the float32 ``y``."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.models.minicpm_sala.network import rms_norm
    from rnb_tpu.ops import rope, ssd
    f32, act = jnp.float32, x["v"].dtype
    rows, q, heads, p = x["v"].shape
    k, c, head_norm, out_norm = x["k"], x["q"], None, None
    if lines in ("first", "both"):
        head_norm = (x["kw"], x["qw"], RAW_EPS, N ** -0.5, x["cos"],
                     x["sin"])
    else:
        positions = rope.pool_positions(x["row_start"], q)
        k = rope.rotate(rms_norm(k, x["kw"], RAW_EPS, f32), positions,
                        x["inv_freq"]).astype(act)
        c = (rope.rotate(rms_norm(c, x["qw"], RAW_EPS, f32), positions,
                         x["inv_freq"]) * N ** -0.5).astype(act)
    if lines in ("last", "both"):
        out_norm = (x["gate"], x["ow"], RAW_EPS)
    y = ssd.ssd_scan(x["v"], None, x["a"], k, c, None,
                     jnp.asarray(first, bool), interpret=True,
                     head_norm=head_norm, out_norm=out_norm, **kwargs)
    if out_norm is None:
        assert y.dtype == f32
        y = rms_norm(y.reshape(rows, q, heads * p), x["ow"], RAW_EPS, f32)
        y = (y * jax.nn.sigmoid(x["gate"])).astype(act)
    assert y.dtype == act
    return np.asarray(y.astype(f32)).reshape(rows, q, heads * p)


def raw_recurrence(x, first):
    """The same lines in float64 around the recurrence token by token,
    from q and k as they come: nothing rounded on the way."""
    def normed(v, w):
        v = np.asarray(v, np.float64)
        v = v / np.sqrt(np.mean(v * v, -1, keepdims=True) + RAW_EPS)
        return v * np.asarray(w.astype("float32"), np.float64)

    def turned(v):
        cos, sin = (np.asarray(x[t], np.float64)[:, :, None, :]
                    for t in ("cos", "sin"))
        return v * cos + np.roll(v, N // 2, -1) * sin
    import jax.numpy as jnp
    k = turned(normed(x["k"], x["kw"]))
    c = turned(normed(x["q"], x["qw"])) * N ** -0.5
    y = recurrence(x["v"], None, x["a"], jnp.asarray(k, jnp.float32),
                   jnp.asarray(c, jnp.float32), None, first)
    y = y.reshape(y.shape[:2] + (-1,))
    y = y / np.sqrt(np.mean(y * y, -1, keepdims=True) + RAW_EPS)
    gate = np.asarray(x["gate"], np.float64)
    return y * np.asarray(x["ow"].astype("float32"), np.float64) \
        / (1.0 + np.exp(-gate))


@pytest.mark.parametrize("lines", ["first", "last", "both"])
@pytest.mark.parametrize("pool", sorted(POOLS))
def test_the_mixers_lines_inside_are_the_lines_outside(pool, lines):
    """``head_norm`` and ``out_norm``: the head norms, the rotation, the
    scale and the one rounding of q and k in front of the scan, the norm
    over all the heads (two grid steps here) and the gate behind it, are
    inside the kernel the float32 operations they were outside, in
    their order — a reduction's order apart (a head's mean of squares,
    the mean over every head's columns), which is an ulp of a float32
    and moves a bfloat16 value only across a rounding edge: an output
    itself by one bfloat16 step, or an element of q or k by one, which
    moves its token's outputs by less than that step at the top. No
    output further apart than 2^-7 of the largest, and no more than one
    in a hundred moved at all. Over the pools: a request that starts
    mid-pool restarts its positions and its state, a pad row lies
    between two."""
    first = POOLS[pool]
    x = raw_inputs(first, seed=60 + len(first))
    want = raw_scan(x, first, lines="none")
    got = raw_scan(x, first, lines=lines)
    assert np.abs(got - want).max() <= 2.0 ** -7 * np.abs(want).max()
    assert (got != want).mean() < 0.01
    if lines != "both":
        return
    # and the whole is the recurrence, with the scores' and the result's
    # roundings to bfloat16
    exact = raw_recurrence(x, first)
    assert np.abs(got - exact).max() < 3e-2 * np.abs(exact).max()


def test_float32_lines_inside_meet_the_recurrence_closely():
    """With float32 activations the first lines round nothing: the
    kernel with both sets of lines inside, to float32's last digits."""
    first = POOLS["two_requests"]
    x = raw_inputs(first, seed=7, dtype="float32")
    exact = raw_recurrence(x, first)
    assert worst(raw_scan(x, first), exact) < 2e-5


def test_packing_is_invisible_to_the_lines_inside():
    """A request alone equals the same request packed behind another and
    a pad row, bit for bit: its positions restart with its tables, its
    state at ``row_first``, and the output norm is a token's own."""
    import jax.numpy as jnp
    alone, packed = raw_inputs([1, 0], seed=11), raw_inputs(
        [1, 0, 1, 1, 0], seed=12)
    for name in ("v", "k", "q", "gate"):
        packed[name] = jnp.concatenate([packed[name][:3], alone[name]])
    for name in ("kw", "qw", "ow"):
        packed[name] = alone[name]
    assert np.array_equal(np.asarray(packed["cos"][3:]),
                          np.asarray(alone["cos"]))
    assert np.array_equal(raw_scan(packed, [1, 0, 1, 1, 0])[3:],
                          raw_scan(alone, [1, 0]))


def test_a_bfloat16_state_moves_the_lines_inside():
    """``state_dtype`` rounds every step's carried states under the
    (row, step) grid too, and only where a state was carried."""
    import jax.numpy as jnp
    first = [1, 0, 0, 1]
    x = raw_inputs(first, seed=3)
    exact = raw_scan(x, first)
    rounded = raw_scan(x, first, state_dtype=jnp.bfloat16)
    for row in (0, 3):
        assert np.array_equal(exact[row], rounded[row])
    assert 1e-4 < worst(rounded[1:3], exact[1:3]) < 5e-2


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
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


#: name -> (rows, Q, heads, groups, P, N, steps and skip term?)
REAL = {"nemotron_h": (64, 128, 64, 8, 64, 128, True),
        "lightning": (128, 128, 32, 32, 128, 128, False),
        "lightning_raw": (128, 128, 32, 32, 128, 128, False),
        "falcon_h1": (64, 128) + FALCON_H1[:3] + (256, True)}


@pytest.mark.parametrize("caller", sorted(REAL))
def test_the_kernel_compiles_at_a_callers_shapes(one_chip, caller):
    """Mosaic takes the kernel at the real widths (nothing runs), and
    nothing of ``Q x Q`` a head or of a state is left for XLA: the
    program's temporaries are the running sums' few bytes a token.
    ``lightning_raw`` is the form MiniCPM-SALA's mixer calls since PR
    60: q, k and the gate float32 as their products wrote them, a row's
    ``y``, the gate's sigmoid and all 32 heads' states in VMEM under
    the compiler's own limit, one bfloat16 array out."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu.ops import ssd
    rows, q, heads, groups, p, n, full = REAL[caller]

    def of(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    per_token = of((rows, q, heads), jnp.float32)
    per_head = of((heads,), jnp.float32)
    out_bytes = rows * q * heads * p * 4
    if caller == "lightning_raw":
        wide = of((rows, q, heads * p), jnp.float32)
        table = of((rows, q, n), jnp.float32)
        compiled = jax.jit(
            lambda xs, a, b, c, first, bw, cw, cos, sin, gate, w:
            ssd.ssd_scan(
                xs.reshape(rows, q, heads, p), None, a,
                b.reshape(rows, q, groups, n), c.reshape(rows, q, groups, n),
                None, first, head_norm=(bw, cw, 1e-6, n ** -0.5, cos, sin),
                out_norm=(gate, w, 1e-6)).reshape(rows, q, heads * p)).lower(
            of((rows, q, heads * p)), per_head, wide, wide,
            of((rows,), jnp.bool_), of((n,)), of((n,)), table, table, wide,
            of((heads * p,))).compile()
        assert ssd.KERNEL_NAME in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < out_bytes // 8
        return
    # the heads' lanes side by side on both sides, as the callers hold
    # them: the reshapes are the compiler's to cancel
    # (Nemotron-H's with its gated norm)
    compiled = jax.jit(
        lambda xs, dt, a, b, c, d, first, z, w: ssd.ssd_scan(
            xs.reshape(rows, q, heads, p), dt, a,
            b.reshape(rows, q, groups, n), c.reshape(rows, q, groups, n),
            d, first, gated_norm=(z, w, 1e-5) if full else None)
        .reshape(rows, q, heads * p)).lower(
        of((rows, q, heads * p)), per_token if full else None, per_head,
        of((rows, q, groups * n)), of((rows, q, groups * n)),
        per_head if full else None, of((rows,), jnp.bool_),
        of((rows, q, heads * p), jnp.float32) if full else None,
        of((heads * p,)) if full else None).compile()
    assert ssd.KERNEL_NAME in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < out_bytes // 8
