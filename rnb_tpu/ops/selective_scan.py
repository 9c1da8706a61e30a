"""Mamba-1's selective scan over a packed pool of rows, as one Pallas
TPU kernel: a decay *per (channel, state)*, which ``ops/ssd.py``'s
blocked form (one scalar decay a head across the state axis) cannot
express. Phi-4-mini-flash's Mamba layers are the caller
(``models/phi4_flash``).

Per channel ``c`` of ``C`` and state ``n`` of ``N``, over the tokens of
one request (``s = 0`` before its first)::

    s_t[c, n] = exp(dt_t[c] A[c, n]) s_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] s_t[c, n] + D[c] x_t[c]
    out_t[c]  = y_t[c] silu(z_t[c])

At the published shape that is 5,120 x 16 = 81,920 independent scalar
recurrences a token, none of them a matrix product: the work is the
vector unit's (six operations a state and token) and the transcendental
unit's (one exponential a state and token), and the kernel is laid out
for them.

*The form is the recurrence itself*, token by token. The factorised
within-row form (``exp(A S_t) sum_s exp(-A S_s) ...`` with ``S`` the
running sum of the steps, which would put the row's work on the matrix
unit) needs ``exp(+|A| S)``: at ``A = -16`` and steps of 0.1 a row of
128 tokens reaches ``exp(205)``, past float32, and the steps are data
(a softplus of a product), so no sub-block length is safe for every
dispatch. A log-depth scan along the tokens (seven doubling steps a row,
three operations and two shifted copies each) is five to eight times
the vector unit's work of the plain recurrence. The plain recurrence
needs neither: what it needs is that a token's operands are whole
registers, and that is the layout.

*Layout.* A register is 8 sublanes x 128 lanes. The kernel reads and
writes the pool's own arrays, ``(rows, Q, C)`` with the tokens on the
sublanes and the channels on the lanes, in blocks of a row's ``Q``
tokens: ``x`` where the convolution wrote it, ``z`` and the steps where
their products did, and ``out_proj`` reads the gated result where the
kernel wrote it — no array of a layer is copied between layouts in HBM
(``tests/test_selective_scan.py`` compiles a layer at the published
shape and finds none). The token loop wants the other layout: a token's
channels over *both* sublanes and lanes, ``(C / 128, 128)``, so that
state ``n`` of those channels is registers of its own, the token loop's
carry, and ``B_t[n]``, ``C_t[n]`` are *scalars*, read from SMEM and
broadcast by the multiply that uses them. So each group of ``_UNROLL`` =
8 tokens is *turned* in VMEM: the group's (8 tokens, C / 128 lane tiles)
block of ``x``, of the steps and of ``z`` is widened to float32 into a
scratch as it lies, and a token's row of it, ``C / 128`` lane tiles side
by side, is read back as that token's ``(C / 128, 128)`` registers, the
tiles under one another — one load with a sublane stride for every 8
tiles, on the load unit, beside the vector unit's work; the token's two
results go back the same way and the group's block is rounded and
stored whole. The token's operations are those of the slab form this
replaced (``tests/selective_scan_slabs.py``: PR 59's kernel behind XLA's
copies into ``(rows, Q, C / 128, 128)``), in its order: the two agree
to the bit, on the chip and unfused in interpret mode.

*The grid* is (channel block, row), the row axis innermost and
sequential: a step takes one row of ``Q`` tokens of ``_STEP_CHANNELS``
channels — all 5,120 of Phi-4-mini-flash's, five registers a state —
walks its tokens in order, and keeps the states in a float32 VMEM
scratch between rows; ``row_first`` is a scalar-prefetch operand
(``ops/ssd.ssd_scan``'s, the delta rules'): a row that opens a request
zeroes the scratch before it reads it. ``state_dtype`` is the precision
the states are carried in *between rows* (float32 in the program; the
control arm's bfloat16). The gate ``y silu(z)`` is the kernel's last
line; with ``memory=True`` it writes ``y`` itself beside it
(Phi-4-mini-flash's layer 16: the memory the Gated Memory Units read).
The steps come in after their softplus: the bias and the softplus are
the step product's epilogue in the compiled layer (one ``kOutput``
fusion, before this kernel and with it), not a pass of their own.

Off the TPU the same kernel runs in Pallas's interpret mode.

**Which unit bounds it, and what was tried** (``scripts/
selective_scan_sweep.py``; my chip runs, PR 61, one TPU v5 lite, the
published 5,120 channels x 16 states, layer 16's form with the memory;
ms a call, the kernel's custom call alone | every operation of the
jitted call). The kernel is bound by the vector unit: a state and token
cost a multiply for the exponent, the exponential, two multiplies and an
add for the update and a multiply and an add for the read-out — 9.6 G
vector operations and 1.34 G exponentials a layer at 128 rows — and two
broadcasts of a scalar, which a step of more channels shares among its
registers. It equals the recurrence on the chip at the draw and at both
of its corners (largest difference 0.0019 to 0.0028 of the values'
range, the outputs' one rounding to bfloat16) and the slab form to the
bit. At 128 rows (64 rows), by (channels a grid step, tokens a turn):

    the slab form (1,024, 8)   2.787 | 5.922   (1.418 | 2.815)
    the pool's    (1,024, 8)   3.103 | 3.114   (1.553 | 1.562)
                  (1,024, 16)  2.883 | 2.894   (1.443 | 1.452)
                  (5,120, 8)   2.652 | 2.663   (1.334 | 1.342)
                  (5,120, 16)  2.564 | 2.575   (1.290 | 1.299)

The slab form's copies were 3.1 ms a layer, more than its kernel; the
turns cost the kernel 0.32 ms at 1,024 channels a step (loads and stores
that wait on one another through the scratch), and a step of all the
channels takes that back and 0.13 more: **(5,120, 8) stands, 2.66 ms a
layer for 5.92**. 16 tokens a turn read 3% less for a body twice as
long to lower in each of a stack's programs: 8 stands. 2,048 channels do
not divide 5,120 (what PR 59's table gave as 2,048 was all 5,120 a
step, ``step_channels``' fallback); all 5,120 want 16.1 MiB of scoped
VMEM for a default of 16, hence ``_VMEM_LIMIT`` (the same time under a
limit of 18, 32 and 64 MiB). A turn written tile by tile (a scratch of
``(C / 128 tiles x 8 tokens, 128)`` rows, a token's register one load of
stride 8) lowers to the same kernel, 2.652, and its 200 more lines a
group cost 6 s of a cell's set-up (``setup_lower_s`` 29.8 -> 36.1: the
kernel is lowered into every row bucket's programs): the row form
stands. The recurrence's own bytes (x, z, y in bfloat16, the steps in
float32, B and C) are 1.03 ms at the HBM's rate: the kernel stands at
39% of that floor, which no kernel of these operations can be near (the
peaks' table has no vector-unit rate: ROADMAP D10 (bp)).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the kernel's name in the device's trace and in the scope table
KERNEL_NAME = "selective_scan"

_LANES = 128
#: channels a grid step takes: all of Phi-4-mini-flash's, five registers
#: of 8 sublanes x 128 lanes a state, so that a token's ``B_t[n]`` and
#: ``C_t[n]`` are broadcast once for the five (the sweep above)
_STEP_CHANNELS = 5120
#: tokens a turn, and the token loop's body holds one turn (its
#: unrolling): a float32 register's sublanes
_UNROLL = 8
#: the kernel's own limit on scoped VMEM: a row's five blocks of all the
#: channels, each in two buffers, are 15 MiB and the states, ``A`` and
#: the turns 1.1 more, for a default of 16
_VMEM_LIMIT = 20 << 20


def _kernel(first_ref, bc_ref, x_ref, dt_ref, z_ref, a_ref, d_ref, *refs,
            n: int, memory: bool, state_dtype):
    """One row of one channel block. ``bc_ref`` (1, Q * 2 N) float32 in
    SMEM: token t's ``B_t`` then ``C_t``; ``x_ref``, ``z_ref`` (Q, S
    128) in the activations' dtype and ``dt_ref`` float32, the pool's
    own layout: tokens on the sublanes, a block's ``S`` lane tiles side
    by side; ``a_ref`` (N, S, 128) ``A`` transposed, ``d_ref`` (S, 128);
    the outputs (Q, S 128): the gated result and, with ``memory``,
    ``y``; ``state_ref`` (N, S, 128) float32, carried; ``turn_ref`` (5,
    ``_UNROLL``, S 128) float32: where a group of tokens is turned —
    ``x``, the steps, ``z``, the gated result, ``y``."""
    outs, (state_ref, turn_ref) = refs[:-2], refs[-2:]
    assert len(outs) == 1 + memory
    f32 = jnp.float32
    qlen = x_ref.shape[0]
    slab = d_ref.shape[0]
    row = pl.program_id(1)

    @pl.when((row == 0) | (first_ref[row] != 0))
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    a = [a_ref[k] for k in range(n)]
    skip = d_ref[...]

    def turned(i, t):
        """Token ``t`` of the group's block ``i``: its row of S lane
        tiles side by side, read as its registers, the tiles under one
        another."""
        return turn_ref[i, pl.ds(t, 1)].reshape(slab, _LANES)

    def turn_back(i, t, value):
        turn_ref[i, pl.ds(t, 1)] = value.reshape(1, slab * _LANES)

    def token(t, bc, states):
        x, dt = turned(0, t), turned(1, t)
        u = dt * x
        y = skip * x
        out = []
        for k in range(n):
            s = jnp.exp(dt * a[k]) * states[k] + bc_ref[0, bc + k] * u
            y = y + bc_ref[0, bc + n + k] * s
            out.append(s)
        if memory:
            turn_back(4, t, y)
        z = turned(2, t)
        turn_back(3, t, y * (z * jax.nn.sigmoid(z)))
        return tuple(out)

    def tokens(group, states):
        first = pl.multiple_of(group * _UNROLL, _UNROLL)
        # the group's blocks as they lie, widened: (tokens, S 128)
        for i, ref in enumerate((x_ref, dt_ref, z_ref)):
            turn_ref[i] = ref[pl.ds(first, _UNROLL), :].astype(f32)
        # unrolled by hand: Mosaic's loops unroll whole or not at all
        for t in range(_UNROLL):
            states = token(t, (first + t) * 2 * n, states)
        for i, ref in enumerate(outs):
            ref[pl.ds(first, _UNROLL), :] = turn_ref[3 + i].astype(ref.dtype)
        return states

    assert qlen % _UNROLL == 0, qlen
    states = lax.fori_loop(0, qlen // _UNROLL, tokens,
                           tuple(state_ref[k] for k in range(n)))
    for k in range(n):
        # inside the kernel the pair of conversions is Mosaic's to lower,
        # and it keeps both (``ops/deltanet.py``)
        state_ref[k] = states[k].astype(state_dtype).astype(f32)


def step_channels(channels: int) -> int:
    """Channels a grid step: ``_STEP_CHANNELS`` where that divides them,
    else the whole of them (the tests' small widths)."""
    return _STEP_CHANNELS if channels % _STEP_CHANNELS == 0 else channels


# a function under ``jit`` of its own: a stack's Mamba layers call it
# with the same shapes, and the kernel is traced and lowered once for all
@functools.partial(jax.jit, static_argnames=(
    "memory", "state_dtype", "out_dtype", "interpret"))
def _scan_call(x, dt, a, b, c, d, z, row_first, *, memory, state_dtype,
               out_dtype, interpret):
    rows, q, channels = x.shape
    n = a.shape[1]
    f32 = jnp.float32
    block = step_channels(channels)
    slab = block // _LANES
    # the pool's own arrays and its layout: tokens on the sublanes,
    # channels on the lanes
    tokens = pl.BlockSpec((None, q, block), lambda i, r, _: (r, 0, i))
    bc = jnp.concatenate([b.astype(f32), c.astype(f32)], axis=-1) \
        .reshape(rows, 1, q * 2 * n)
    operands = (
        bc, x, dt.astype(f32), z,
        a.astype(f32).T.reshape(n, channels // _LANES, _LANES),
        d.astype(f32).reshape(channels // _LANES, _LANES))
    out = jax.ShapeDtypeStruct((rows, q, channels), out_dtype)
    return tuple(pl.pallas_call(
        functools.partial(_kernel, n=n, memory=memory,
                          state_dtype=state_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(channels // block, rows),
            in_specs=[
                pl.BlockSpec((None, 1, q * 2 * n), lambda i, r, _: (r, 0, 0),
                             memory_space=pltpu.SMEM),
                tokens, tokens, tokens,
                pl.BlockSpec((n, slab, _LANES), lambda i, r, _: (0, i, 0)),
                pl.BlockSpec((slab, _LANES), lambda i, r, _: (i, 0))],
            out_specs=[tokens] * (2 if memory else 1),
            scratch_shapes=[pltpu.VMEM((n, slab, _LANES), f32),
                            pltpu.VMEM((5, _UNROLL, block), f32)]),
        out_shape=[out] * (2 if memory else 1),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=rows * q * channels * (7 * n + 6),
            transcendentals=rows * q * channels * (n + 1),
            bytes_accessed=sum(v.size * v.dtype.itemsize for v in operands)
            + (2 if memory else 1) * x.size * jnp.dtype(out_dtype).itemsize),
        interpret=interpret, name=KERNEL_NAME,
    )(row_first.astype(jnp.int32), *operands))


def selective_scan(x, dt, a, b, c, d, z, row_first, *, memory: bool = False,
                   state_dtype=jnp.float32, interpret: bool = False):
    """The scan of one Mamba-1 layer over a packed pool, gate included.

    ``x`` (rows, Q, C) behind the convolution and its SiLU; ``dt`` (rows,
    Q, C) float32, after its softplus; ``a`` (C, N) float32, negative;
    ``b``, ``c`` (rows, Q, N); ``d`` (C,); ``z`` (rows, Q, C) the gate's
    input; ``row_first`` (rows,) bool: the rows that open a request.
    -> (rows, Q, C) in ``x``'s dtype: ``y silu(z)``; with ``memory`` a
    pair, ``y`` (the scan's output with the skip term, before the gate)
    second. ``state_dtype`` is the precision the states are carried in
    between rows (the control arm's); ``interpret`` runs the kernel in
    interpret mode (a device that is no TPU)."""
    with jax.named_scope("scan"):
        outs = _scan_call(x, dt, a, b, c, d, z, row_first,
                          memory=bool(memory),
                          state_dtype=jnp.dtype(state_dtype),
                          out_dtype=jnp.dtype(x.dtype),
                          interpret=bool(interpret))
    return outs if memory else outs[0]


def recurrence(x, dt, a, b, c, d, z, row_first):
    """The same, token by token in plain ``jax.numpy`` (float32; the
    tests' and the sweep's twin). -> (out, y), both float32."""
    rows, q, channels = x.shape
    f32 = jnp.float32
    flat = lambda v: v.astype(f32).reshape(rows * q, -1)    # noqa: E731
    first = jnp.repeat(row_first, q) \
        & (jnp.arange(rows * q) % q == 0)

    def step(s, inp):
        x_t, dt_t, b_t, c_t, opens = inp
        s = jnp.where(opens, 0.0, s)
        s = jnp.exp(dt_t[:, None] * a) * s \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, s @ c_t + d * x_t
    _, y = lax.scan(step, jnp.zeros(a.shape, f32),
                    (flat(x), flat(dt), flat(b), flat(c), first))
    y = y.reshape(rows, q, channels)
    return y * jax.nn.silu(z.astype(f32)), y
