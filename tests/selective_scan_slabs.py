"""Mamba-1's selective scan as the tree ran it in PRs 59 and 60, kept for
the tests that hold PR 61's kernel to it bit for bit and for
``scripts/selective_scan_sweep.py``'s timings beside it: the *slab* form.
The operands are reshaped ``(rows, Q, C) -> (rows, Q, C / 128, 128)`` in
HBM so that a token's 1,024 channels are one register of the kernel's
block, which changes the tiled layout of the last two axes: XLA copies
``x``, ``z``, the float32 steps in front of the kernel and each output
behind it, in every layer (3.1 ms a layer at 128 rows for a kernel of
2.79: my chip runs, PR 59). The token loop's body is the one
``rnb_tpu.ops.selective_scan`` still has. Nothing in ``rnb_tpu`` imports
this."""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the kernel's name in the device's trace and in the scope table
KERNEL_NAME = "selective_scan_slabs"

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
