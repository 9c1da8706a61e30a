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

*Layout.* A register is 8 sublanes x 128 lanes. The channels are laid
over *both*: the operands are read as ``(T, C / 128, 128)``, so a token's
1,024 channels are one register, and state ``n`` of those channels is
one register too: a step's 16 states of 1,024 channels are 16 registers,
the token loop's carry, and ``B_t[n]``, ``C_t[n]`` are *scalars*, read
from SMEM and broadcast by the multiply that uses them. With the tokens
on the sublanes (the pool's own layout) every token would instead need
a row of ``dt`` and ``x`` spread over the sublanes and a column of ``B``
over the lanes. The price is a relayout of ``x``, ``dt``, ``z`` in
front of the kernel and of ``y`` behind it, XLA's copies (the sweep
below has what they cost: as much as the kernel).

*The grid* is (channel block, row), the row axis innermost and
sequential: a step takes one row of ``Q`` tokens of ``_STEP_CHANNELS``
channels, walks its tokens in order with the states in registers,
and keeps them in a float32 VMEM scratch between rows; ``row_first`` is
a scalar-prefetch operand (``ops/ssd.ssd_scan``'s, the delta rules'): a
row that opens a request zeroes the scratch before it reads it.
``state_dtype`` is the precision the states are carried in *between
rows* (float32 in the program; the control arm's bfloat16). The gate
``y silu(z)`` is the kernel's last line; with ``memory=True`` it writes
``y`` itself beside it (Phi-4-mini-flash's layer 16: the memory the
Gated Memory Units read).

Off the TPU the same kernel runs in Pallas's interpret mode.

**Which unit bounds it, and what was tried** (``scripts/
selective_scan_sweep.py``; my chip runs, PR 59, one TPU v5 lite, the
published 5,120 channels x 16 states, layer 16's form with the memory;
ms a call, the kernel's custom call alone | every operation of the
jitted call, the relayouts with it). The kernel is bound by the vector
unit: a state and token cost a multiply for the exponent, the
exponential, two multiplies and an add for the update and a multiply
and an add for the read-out — 9.6 G vector operations and 1.34 G
exponentials a layer at 128 rows — and it equals the recurrence on the
chip at the draw and at both of its corners (largest difference 0.0019
to 0.0028 of the values' range, the outputs' one rounding to bfloat16).
At (channels a grid step, tokens the loop's body holds), 128 rows:
(1,024, 4) 2.98 | 6.11; **(1,024, 8) 2.79 | 5.92**; (1,024, 16) 2.70 |
5.83 (3% for a body twice as long to compile: 8 stands); 2,048 channels
a step — two registers a state, 32 carried — are refused at 128 rows
(16.05 MiB of scoped VMEM for a limit of 16) and read 1.28 / 1.21 /
1.17 | 2.68 / 2.61 / 2.57 at 64 rows for 1.51 / 1.42 / 1.37 | 2.91 /
2.82 / 2.77 at 1,024: a tenth, not taken for a limit raised by hand. The
recurrence's own bytes (x, z, y in bfloat16, the steps in float32, B and
C) are 1.03 ms at the HBM's rate: the kernel stands at 37% of that
floor, which no kernel of these operations can be near (the peaks'
table has no vector-unit rate: ROADMAP D10 (bp)). **The relayouts cost
as much as the kernel** (3.1 ms a layer: XLA's copies of x, z and the
float32 steps into the slab layout and of y out of it, shuffles of
sublanes that run far under the memory's rate): a kernel that reads the
pool's own layout and turns a row's (8 tokens, 8 lane tiles) blocks in
VMEM is what is left to win here.
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
#: channels a grid step takes: a register of 8 sublanes x 128 lanes a
#: state, so that a step's 16 states are the token loop's 16 carried
#: registers
_STEP_CHANNELS = 1024
#: tokens the token loop's body holds (its unrolling)
_UNROLL = 8


def _kernel(first_ref, bc_ref, x_ref, dt_ref, z_ref, a_ref, d_ref, *refs,
            n: int, memory: bool, state_dtype):
    """One row of one channel block. ``bc_ref`` (1, Q * 2 N) float32 in
    SMEM: token t's ``B_t`` then ``C_t``; ``x_ref``, ``z_ref`` (Q, S,
    128) in the activations' dtype and ``dt_ref`` float32, a token's
    channels a slab of ``S`` sublanes; ``a_ref`` (N, S, 128) ``A``
    transposed, ``d_ref`` (S, 128); the outputs (Q, S, 128): the gated
    result and, with ``memory``, ``y``; ``state_ref`` (N, S, 128)
    float32, carried."""
    if memory:
        o_ref, m_ref, state_ref = refs
    else:
        (o_ref, state_ref), m_ref = refs, None
    f32 = jnp.float32
    qlen = x_ref.shape[0]
    row = pl.program_id(1)

    @pl.when((row == 0) | (first_ref[row] != 0))
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    a = [a_ref[k] for k in range(n)]
    skip = d_ref[...]

    def token(t, states):
        x, dt = x_ref[t].astype(f32), dt_ref[t]
        u = dt * x
        y = skip * x
        out = []
        for k in range(n):
            s = jnp.exp(dt * a[k]) * states[k] \
                + bc_ref[0, t * 2 * n + k] * u
            y = y + bc_ref[0, t * 2 * n + n + k] * s
            out.append(s)
        if m_ref is not None:
            m_ref[t] = y.astype(m_ref.dtype)
        z = z_ref[t].astype(f32)
        o_ref[t] = (y * (z * jax.nn.sigmoid(z))).astype(o_ref.dtype)
        return tuple(out)

    def tokens(group, states):
        # unrolled by hand: Mosaic's loops unroll whole or not at all
        for t in range(_UNROLL):
            states = token(group * _UNROLL + t, states)
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

    def slabs(v):
        """(rows, Q, C) -> (rows, Q, C / 128, 128): a token's channels
        over sublanes and lanes."""
        return v.reshape(rows, q, channels // _LANES, _LANES)
    tokens = pl.BlockSpec((None, q, slab, _LANES),
                          lambda i, r, _: (r, 0, i, 0))
    bc = jnp.concatenate([b.astype(f32), c.astype(f32)], axis=-1) \
        .reshape(rows, 1, q * 2 * n)
    operands = (
        bc, slabs(x), slabs(dt.astype(f32)), slabs(z),
        a.astype(f32).T.reshape(n, channels // _LANES, _LANES),
        d.astype(f32).reshape(channels // _LANES, _LANES))
    out = jax.ShapeDtypeStruct((rows, q, channels // _LANES, _LANES),
                               out_dtype)
    outs = pl.pallas_call(
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
            scratch_shapes=[pltpu.VMEM((n, slab, _LANES), f32)]),
        out_shape=[out] * (2 if memory else 1),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=rows * q * channels * (7 * n + 6),
            transcendentals=rows * q * channels * (n + 1),
            bytes_accessed=sum(v.size * v.dtype.itemsize for v in operands)
            + (2 if memory else 1) * x.size * jnp.dtype(out_dtype).itemsize),
        interpret=interpret, name=KERNEL_NAME,
    )(row_first.astype(jnp.int32), *operands)
    return tuple(o.reshape(rows, q, channels) for o in outs)


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
