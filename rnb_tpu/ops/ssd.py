"""The Mamba-2 state-space scan over a packed pool of rows, in its
blocked (SSD) form, as one Pallas TPU kernel that keeps a row's ``Q x
Q`` arrays and the carried state in VMEM (lightning linear attention is
a case of it), and the causal depthwise convolution in front of it as
a second kernel — both with the state reset where a request's first row
starts.

A *row* is one chunk of ``Q`` consecutive tokens (the configuration's
``chunk_size``); a request occupies consecutive rows of the pool and
``row_first[r]`` says that row ``r`` opens a request (a pad row opens
one of its own). Per head, with state ``S`` (``P`` x ``N``)::

    S_t = exp(dt_t A) S_{t-1} + dt_t xs_t (x) B_t
    y_t = S_t C_t + D xs_t

Linear attention with a constant decay a head (lightning attention:
``S_t = lambda S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t``) is the same
recurrence with unit steps and no skip term: ``dt = None``, ``A = log
lambda``, ``xs = v``, ``B = k``, ``C = q`` (scaled by the caller), ``D
= None``, one group a head.

Inside a row, with ``cs`` the running sum of the log decays ``dt A``
over the row's tokens and ``S_in`` the state the row receives::

    y_i   = sum_{j <= i} (C_i . B_j) exp(cs_i - cs_j) dt_j xs_j
            + exp(cs_i) C_i S_in^T + D xs_i
    S_out = exp(cs_Q) S_in + sum_j exp(cs_Q - cs_j) dt_j xs_j (x) B_j

*The kernel.* The grid is (head group, row): a grid step takes one row
of ``_STEP_LANES`` lanes of ``xs`` — whole groups, a group's ``per = H
/ G`` heads sharing its ``C . B^T`` — and the row axis is innermost and
sequential, so a step's heads walk the pool's rows in order with their
states (float32, transposed: ``N`` x a group's ``per P`` lanes) in a
VMEM scratch that lives from one grid step to the next. ``row_first``
is a scalar-prefetch operand: a step whose row opens a request zeroes
the scratch before it reads it. A step reads its heads' ``xs`` and its
groups' ``B``, ``C`` in the activations' dtype as they lie in the pool
(a token a sublane, the heads side by side along the lanes), and in
float32 the running sums — formed outside, a few bytes a token and
head, in both orientations the kernel reads them, with a head's sum at
the row's end over its lanes — and, where given, the steps and ``D``.
In VMEM it forms ``C . B^T`` once a group, a head's decay triangle, the
scores times ``dt_j`` rounded to the activations' dtype, their product
with ``xs``, what the incoming state gives each token and the skip
term, writes ``y`` once, and carries the state: one multiply-add of the
state a row (the ``rows x rows`` matrix of decays a head that carried
the states before PR 47 was the same recurrence in another association,
26 GFLOP of ``highest`` product a block, with every row's state in
HBM). Nothing of ``Q x Q`` a head, and no state, reaches HBM.

Lanes: heads narrower than 128 lanes (Nemotron-H's 64) share a lane
tile, and a head's scores multiply the tile with the other heads' lanes
zeroed — the matrix unit's columns are 128 either way — so every slice
of ``xs``, ``y`` and the state starts at a multiple of 128 and nothing
is shifted along the lanes; a head's per-token factors reach the tile's
lanes by a broadcast and a select. With unit steps the running sums are
one block a step, fetched once, and the triangle is formed in registers
each step (the exponentials are the transcendental unit's, beside a
step whose time is the matrix unit's and the memory's).

*The gated norm* (``gated_norm``, Nemotron-H's M block): ``y silu(z)``,
RMS-normed over each group's ``per P`` columns, times the norm's weight
— the lines that follow the scan in a Mamba-2 block — run on ``y`` while
a step holds it: a norm group's columns are the columns of one of the
step's groups. The kernel reads the gate in float32 and writes the
activations' dtype; as XLA's
fusions behind a kernel that wrote float32 the same lines cost four
passes over a float32 (rows, Q, H P) array and a transposing copy of it
(11.3 ms of a 146 ms dispatch; my chip run, PR 47).

*Lightning attention's lines* (``head_norm``, ``out_norm``,
MiniCPM-SALA's mixer; PR 60): q and k come float32 as their products
wrote them and a step's first lines are, a group's (Q, N) slice at a
time in VMEM, the head's RMS norm, the rotation ``x cos + roll(x, N /
2) sin`` (the tables (rows, Q, N) float32, one pair a dispatch for
every layer), the scale on q and the one rounding to the activations'
dtype — ``ops/banded.py``'s ``_first_lines``, the float32 operations
of ``rms_norm`` and ``ops/rope.rotate`` in their order — so that ``C .
B^T`` and both state products read the values they read when XLA's
passes wrote them. The output norm's mean spans all the heads and a
step holds eight, so for this caller the grid is (row, step): every
step's groups keep states of their own in the scratch (32 heads: 2
MiB), a step leaves its float32 ``y`` and the sigmoid of its gate block
in two more (2 MiB each), and the row's last step forms ``y
rsqrt(mean(y^2) + eps) w sigmoid(gate)`` over the row's (Q, H P) and
writes it once in the activations' dtype: ``y`` is never rounded before
the norm and never reaches HBM. A caller that hands neither (the two
Mamba-2 callers) lowers to the program it had. Alone on the v5e
(``scripts/ssd_sweep.py --only=lightning``, the device's time a layer
at 128 rows from q, k and the gate in float32 to ``o``'s operand; my
chip runs, PR 60): the passes around the kernel 11.818 ms, 1.337 of it
the kernel; the lines inside under the (row, step) grid 2.405 ms, all
of it the kernel (its bytes, 8 MB a row, are 1.31 ms), 324 of 67 M
bfloat16 outputs another value, none by more than one step; the other
sound form — the (step, row) grid as it was, the kernel writing float32
``y`` and each head's sum of squares, one fused pass behind it for the
norm and the gate — 3.150 ms, 2.193 of it the kernel (a variant of this
module measured once and not kept: ``y``'s float32 round trip is 0.65
ms at the HBM's rate). 1.6-1.9 s to compile and first run either way.

Decays, cumulative sums and states are float32; the products that read
or build a state take float32 operands at ``highest`` precision, so
that a state is never rounded to bfloat16 on its way through the matrix
unit. The within-row products take their inputs in the activations'
dtype and accumulate in float32.

*The convolution* (``segment_conv1d``; in front of Nemotron-H's and
Falcon-H1's scans and of Qwen3-Next's and Kimi-Linear's delta rules): K taps a channel, a bias, the SiLU. One
kernel reads the activations once in their own dtype, forms the taps,
the bias and the activation in float32 in VMEM and writes once, each of
the arrays its caller takes (``split``: Nemotron-H's xs, B and C;
Qwen3-Next's q with k in float32 and v in the activations' dtype) as an
array of its own in the dtype the caller rounds it to; as XLA's fusions
the same lines were four float32 passes over the (tokens, channels)
array with three shifted copies of it, and the caller's SiLU, slices
and rounding passes of their own. The grid is (step of ``_CONV_ROWS``
whole rows, lane tile); the kernel's body takes a row of ``_CONV_CHUNK``
lanes at a time — 16 float32 registers of ``x``, the row before's last
sublane tile on top — and a tap is a sublane roll of that. Alone on the
v5e (``scripts/ssd_sweep.py``, the device's time; my chip runs, PR 48),
Nemotron-H's block (64 rows of 6,144 channels; its bytes are 0.25 ms) |
Qwen3-Next's layer (128 rows of 8,192; 0.82 ms): the passes with the
slices behind them 2.333 | 8.084 ms; the kernel at 16 rows x 512 lanes
a step 0.413 | 1.121; over rows a step 4 / 8 / 16 at 512 lanes 0.437 /
0.417 / 0.413 | 1.239 / 1.147 / 1.121, over lanes 512 / 1,024 / 2,048
at 16 rows 0.413 / 0.444 / 0.444 | 1.121 / 1.148 / -; the body over 128
/ 256 / 512 lanes at once, at 8 rows x 1,024 lanes, 0.439 / 0.428 /
0.478 | 1.152 / 1.166 / 1.242 (a row of 1,024 lanes whole, the first
form: the vector unit's registers spill). A call a part, each over its
own channels of ``x``, ran 0.345 | 1.050 at 8 x 1,024 (the store behind
a branch on the part costs the body its straight line) and set a stage
up 3 s slower, warm, for 1.5 s as one call: a stage's set-up grows with
the kernel bodies its programs hold, three a block or one. Falcon-H1's
branch (64 rows of 5,120 channels in parts of 4,096, 512 and 512, a
bias; my chip run, PR 53): the passes 1.932 ms, the kernel 0.346-0.347
at 8 or 16 rows x 512 or 1,024 lanes a step (its bytes are 0.20 ms).

Alone on the v5e (``scripts/ssd_sweep.py``, the device's time; my chip
run, PR 47): Nemotron-H's block (64 rows, 64 heads of 64 in 8 groups)
2.159 ms as XLA's fusions -> 0.893 ms, 0.758 of it the kernel and the
rest the running sums; a lightning layer (128 rows, 32 heads of 128)
7.291 -> 1.338 ms. Falcon-H1's branch (my chip run, PR 53: 64 rows, 32
heads of 128 in 2 groups, N 256 — a *group* is 16 heads = 2,048 lanes,
over ``_STEP_LANES``, so a grid step is one group: 16 heads unrolled, a
state of 256 x 2,048 float32 = 2 MiB of scratch, ``C . B^T`` over 256
columns, and the gated norm's mean over the step's whole 2,048 columns,
which is why a group is not split over steps): 2.884 ms as the blocked
``jnp`` form -> 1.292 ms, 1.180 of it the kernel (1.231 with the gated
norm as its last lines), under the compiler's own scoped-VMEM limit of
16 MiB, 1.0-2.7 s to compile and first run; both groups in one step
(4,096 lanes) are refused there (17.14 MiB) and under a limit of 64 MiB
(the sweep's ``--vmem-mib``) run 1.150 ms (-2.5%) for 5.4 s of compile:
one group a step stays, under the compiler's own limit. The recurrence's own bytes (x, z, y in bfloat16, B, C, the steps)
are 0.268 ms at the HBM's pace and its operations (5 P N a token and
head) 0.218 ms at the matrix unit's: the kernel stands at 22% of that
floor; what it spends is the two ``highest`` products of a row's state
(``C S`` and ``B^T (x . decay)``: 2 x 256 x 128 x 2,048 multiply-adds a
row and group in float32, six bfloat16 passes each).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rnb_tpu.ops.banded import _first_lines

_HIGHEST = lax.Precision.HIGHEST

#: the kernel's name in the device's trace and in the scope table
KERNEL_NAME = "ssd_scan"

#: lanes of ``xs`` a grid step takes (heads x P): two of Nemotron-H's
#: groups (16 heads of 64), eight of lightning's heads of 128. The
#: device's time a call on the v5e (``scripts/ssd_sweep.py``; my chip run,
#: PR 47), Nemotron-H's shapes | lightning's: 512 lanes 0.982 | 1.488 ms,
#: 1024 0.893 | 1.338, 2048 0.868 | 1.293, 4096 0.846 | 1.272 — past 1024
#: the kernel gains 3-5% for a compile time that doubles with the lanes
#: (the body is unrolled a head: 0.9 s, 1.5 s, 3.2 s here). A group
#: wider than this (Falcon-H1's 16 heads of 128) is a step of its own
_STEP_LANES = 1024

#: the convolution's kernel in the device's trace
CONV_KERNEL_NAME = "segment_conv1d"

#: rows and lanes of ``x`` a grid step of the convolution takes
_CONV_ROWS = 16
_CONV_LANES = 512
#: lanes of a row the kernel's body holds at once: a row's 128 tokens of
#: 128 lanes are 16 float32 registers
_CONV_CHUNK = 128


def _dot(a, b):
    return jnp.dot(a, b, precision=_HIGHEST,
                   preferred_element_type=jnp.float32)


def _scores(a, b):
    """``a b^T``, operands as they come, float32 accumulation."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _conv_kernel(first_ref, x_ref, before_ref, w_ref, *refs, activation,
                 edges):
    """One step's rows of one lane tile. ``x_ref`` (R, Q, lanes);
    ``before_ref`` (history, lanes), the last tokens of the row before
    the step's first; ``w_ref`` (K, lanes) float32, ``w_ref[K-1]`` on
    the current token; with a bias ``b_ref`` (1, lanes) float32; an
    output a part, the part's lane tiles ``[edges[p], edges[p + 1])`` of
    the grid's: a step writes the one its tile lies in."""
    b_ref = refs[0] if len(refs) == len(edges) else None
    o_refs = refs[len(refs) - len(edges) + 1:]
    f32 = jnp.float32
    rows, qlen, width = x_ref.shape
    history, taps = before_ref.shape[0], w_ref.shape[0]
    step, tile = pl.program_id(0), pl.program_id(1)
    chunk = width if width % _CONV_CHUNK else _CONV_CHUNK

    def one_row(r, _):
        row = step * rows + r
        # the tokens before the row's first: none where the row opens a
        # request (the pool's first row has none whatever it says)
        opens = (row == 0) | (first_ref[row] != 0)
        for at in range(0, width, chunk):
            lanes = slice(at, at + chunk)
            x = x_ref[r, :, lanes].astype(f32)
            before = jnp.where(
                r == 0, before_ref[:, lanes],
                x_ref[jnp.maximum(r - 1, 0), qlen - history:, lanes])
            before = jnp.where(opens, 0.0, before.astype(f32))
            both = jnp.concatenate([before, x], axis=0)
            out = x * w_ref[taps - 1:taps, lanes]
            if b_ref is not None:
                out = out + b_ref[:, lanes]
            for k in range(1, taps):
                out = out + pltpu.roll(both, k, 0)[history:] \
                    * w_ref[taps - 1 - k:taps - k, lanes]
            if activation == "silu":
                out = jax.nn.silu(out)
            for lo, hi, o_ref in zip(edges, edges[1:], o_refs):
                @pl.when((tile >= lo) & (tile < hi))
                def _(o_ref=o_ref):
                    o_ref[r, :, lanes] = out.astype(o_ref.dtype)
        return 0

    lax.fori_loop(0, rows, one_row, 0)


def _conv_tiles(rows: int, q: int, c: int, itemsize: int):
    """(rows a grid step, lanes a grid step, tokens of history): the
    largest divisor of the pool's rows up to ``_CONV_ROWS``; the widest
    halving of ``_CONV_LANES`` that divides the channels ``c`` (of
    several parts, their common divisor), else all of them; the input's
    last sublane tile of a row (8 tokens of 32 bits, 16 of 16), else the
    row whole."""
    step_rows = max(r for r in range(1, min(rows, _CONV_ROWS) + 1)
                    if rows % r == 0)
    lanes = _CONV_LANES
    while lanes > 128 and c % lanes:
        lanes //= 2
    history = 32 // itemsize
    return (step_rows, lanes if c % lanes == 0 else c,
            history if q % history == 0 else q)


def segment_conv1d(x, weight, bias, row_first, activation=None,
                   out_dtype=jnp.float32, interpret: bool = False,
                   split=None):
    """Causal depthwise convolution along the packed token axis, as one
    kernel: one read of ``x`` in its own dtype, the taps, the bias and
    the activation in float32 in VMEM, one write in ``out_dtype``.

    ``x`` (rows, Q, C); ``weight`` (C, K) with ``weight[:, K-1]`` on
    the current token (the cross-correlation a ``Conv1d`` with left
    padding K-1 computes); ``bias`` (C,) or None. The K-1 tokens of
    history are zero at the start of every request. ``activation``:
    None or "silu", on the float32 sum. -> ``out_dtype`` (rows, Q, C).

    ``split``: None, or the channel counts of the arrays the caller
    takes, side by side in ``x`` — the kernel writes each as an array of
    its own, in its own ``out_dtype`` where that is a tuple (nothing is
    sliced or rounded in HBM behind the kernel). -> a tuple of arrays.

    The grid is (step of whole rows, lane tile): a step's tokens are
    sublanes and its channels lanes, as the pool lies; the tokens before
    a step's first row come from a second view of ``x`` (the last
    sublane tile of the row before), and a row that opens a request
    (``row_first``, scalar prefetch) reads zeros for them. A tap is a
    sublane roll of the row with that history on top. A part's output
    block stays in VMEM over the lane tiles of the other parts (the lane
    tiles are the grid's inner, sequential axis) and goes to HBM once."""
    assert activation in (None, "silu"), activation
    parts = (weight.shape[0],) if split is None else tuple(split)
    assert sum(parts) == weight.shape[0] == x.shape[2], (parts, x.shape)
    dtypes = out_dtype if isinstance(out_dtype, tuple) \
        else (out_dtype,) * len(parts)
    with jax.named_scope("conv"):
        outs = _conv_call(x, weight, bias, row_first, activation=activation,
                          parts=parts, interpret=interpret,
                          out_dtypes=tuple(jnp.dtype(d) for d in dtypes))
    return outs[0] if split is None else tuple(outs)


# a function under ``jit`` of its own: a stack's blocks call it with the
# same shapes, and the kernel is traced and lowered once for all of them
@functools.partial(jax.jit, static_argnames=(
    "activation", "parts", "out_dtypes", "interpret"))
def _conv_call(x, weight, bias, row_first, *, activation, parts, out_dtypes,
               interpret):
    rows, q, c = x.shape
    taps = weight.shape[1]
    step_rows, lanes, history = _conv_tiles(
        rows, q, math.gcd(*parts), x.dtype.itemsize)
    assert taps - 1 <= history, (taps, history)
    f32 = jnp.float32
    edges = tuple(sum(parts[:p]) // lanes for p in range(len(parts) + 1))
    operands = [x, x, weight.astype(f32).T]
    specs = [
        pl.BlockSpec((step_rows, q, lanes), lambda s, i, _: (s, 0, i)),
        pl.BlockSpec((None, history, lanes), lambda s, i, _: (
            jnp.maximum(s * step_rows - 1, 0), q // history - 1, i)),
        pl.BlockSpec((taps, lanes), lambda s, i, _: (0, i))]
    if bias is not None:
        operands.append(bias.astype(f32)[None, :])
        specs.append(pl.BlockSpec((1, lanes), lambda s, i, _: (0, i)))
    return pl.pallas_call(
        functools.partial(_conv_kernel, activation=activation, edges=edges),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // step_rows, c // lanes),
            in_specs=specs,
            # a part's block while the grid walks the other parts' lane
            # tiles: its first before them, its last behind them
            out_specs=[pl.BlockSpec(
                (step_rows, q, lanes), lambda s, i, _, lo=lo, hi=hi: (
                    s, 0, jnp.clip(i - lo, 0, hi - lo - 1)))
                for lo, hi in zip(edges, edges[1:])]),
        out_shape=[jax.ShapeDtypeStruct((rows, q, width), dtype)
                   for width, dtype in zip(parts, out_dtypes)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret, name=CONV_KERNEL_NAME,
    )(row_first.astype(jnp.int32), *operands)


def _lane_tile(per: int, p: int):
    """(heads, lanes) of one lane tile of a group's ``per * p`` lanes of
    ``xs``: as many whole heads as 128 lanes hold (two of Nemotron-H's
    64, one of lightning's 128), so that every slice the kernel takes of
    ``xs``, of the state and of ``y`` starts at a multiple of the tile
    and none is shifted along the lanes."""
    heads = max(1, min(per, 128 // p))
    while per % heads:
        heads -= 1
    return heads, heads * p


def _groups_a_step(groups: int, per: int, p: int) -> int:
    """Groups a grid step: a group's heads are one step's at least (they
    share ``c . b^T``); groups of fewer lanes than ``_STEP_LANES`` go
    side by side, independent chains of products."""
    want = max(1, _STEP_LANES // (per * p))
    return max(g for g in range(1, groups + 1)
               if groups % g == 0 and g <= want)


def _of_tile(columns, first: int, count: int, p: int, shape):
    """The lane tile's factor: head ``first + k``'s (Q, 1) or (1, 1)
    entry of ``columns`` over the tile's lanes ``[k p, (k + 1) p)``."""
    out = jnp.broadcast_to(columns[first], shape)
    if count > 1:
        lane = lax.broadcasted_iota(jnp.int32, shape, 1)
        for k in range(1, count):
            out = jnp.where(lane >= k * p, columns[first + k], out)
    return out


def _kernel(first_ref, *refs, per: int, p: int, n: int, steps_dt: bool,
            skip: bool, eps, state_dtype, turn=None, out_eps=None):
    """One row of one step's groups. ``x_ref`` (Q, heads * P); ``b_ref``,
    ``c_ref`` (Q, groups * N); ``cs_ref`` (Q, heads) the running sums of
    the log decays, a token a sublane, ``cs_row_ref`` (heads, Q) the
    same, a token a lane, ``end_ref`` (1, heads * P) a head's sum at the
    row's last token over its lanes; with steps ``dt_ref``, ``dt_row_ref``
    likewise; with a skip term ``d_ref`` (1, heads * P), a head's ``D``
    over its lanes; with a gated norm (``eps`` not None) ``z_ref`` (Q,
    heads * P) float32 and ``w_ref`` (1, heads * P) the norm's weight;
    ``state_ref`` (groups, N, per * P) float32, carried: a head's state
    transposed, ``S^T``.

    With ``turn`` ((eps, scale): ``ssd_scan``'s ``head_norm``) ``b_ref``
    and ``c_ref`` are float32 as their products wrote them, ``bw_ref``,
    ``cw_ref`` (1, N) the head norms' weights in float32 and ``cos_ref``,
    ``sin_ref`` (Q, N) the row's rotary tables: ``ops/banded.py``'s
    :func:`_first_lines` on each group's slice. With ``out_eps``
    (``ssd_scan``'s ``out_norm``) the grid is (row, step): ``g_ref`` (Q,
    heads * P) float32 the step's gate, ``ow_ref`` (1, all heads * P) the
    norm's weight, ``o_ref`` (Q, all heads * P) the row whole,
    ``state_ref`` every step's groups, ``y_ref`` and ``gate_ref``
    (steps, Q, heads * P) float32 the row's ``y`` and the gate's sigmoid
    as the steps leave them; the row's last step norms and writes."""
    refs = iter(refs)
    x_ref, b_ref, c_ref, cs_ref, cs_row_ref, end_ref = (
        next(refs) for _ in range(6))
    dt_ref, dt_row_ref = (next(refs), next(refs)) if steps_dt \
        else (None, None)
    d_ref = next(refs) if skip else None
    z_ref, w_ref = (next(refs), next(refs)) if eps is not None \
        else (None, None)
    if turn is not None:
        bw_ref, cw_ref, cos_ref, sin_ref = (next(refs) for _ in range(4))
    if out_eps is not None:
        g_ref, ow_ref, o_ref, state_ref, y_ref, gate_ref = refs
    else:
        o_ref, state_ref = refs
    f32 = jnp.float32
    qlen = x_ref.shape[0]
    act = x_ref.dtype
    # with an output norm over all the heads a row's steps are the inner
    # axis, and each step's groups keep states of their own
    row = pl.program_id(1 if out_eps is None else 0)
    step = None if out_eps is None else pl.program_id(1)
    step_groups = b_ref.shape[1] // n

    @pl.when((row == 0) | (first_ref[row] != 0))
    def _():
        if step is None:
            state_ref[...] = jnp.zeros_like(state_ref)
        else:
            state_ref[pl.ds(step * step_groups, step_groups)] = jnp.zeros(
                (step_groups,) + state_ref.shape[1:], f32)

    tile_heads, lanes = _lane_tile(per, p)
    token = lax.broadcasted_iota(jnp.int32, (qlen, qlen), 0)
    other = lax.broadcasted_iota(jnp.int32, (qlen, qlen), 1)
    own = lax.broadcasted_iota(jnp.int32, (qlen, lanes), 1) // p

    def side_by_side(parts):
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 1)

    for group in range(step_groups):
        b = b_ref[:, group * n:(group + 1) * n]
        c = c_ref[:, group * n:(group + 1) * n]
        if turn is not None:
            b = _first_lines(b, bw_ref[...], cos_ref[...], sin_ref[...],
                             turn[0], act)
            c = _first_lines(c, cw_ref[...], cos_ref[...], sin_ref[...],
                             turn[0], act, turn[1])
        cb = _scores(c, b)                                   # (Q, Q)
        heads = range(group * per, (group + 1) * per)
        tiles = range(heads.start, heads.stop, tile_heads)
        of_group = slice(heads.start * p, heads.stop * p)
        x = x_ref[:, of_group]                               # (Q, per P)
        cs = {j: cs_ref[:, j:j + 1] for j in heads}          # (Q, 1)
        # a row's own tokens: (C_i . B_j) exp(cs_i - cs_j) dt_j, j <= i,
        # rounded to the activations' dtype, times x; a lane tile's
        # heads each against the tile with the others' lanes zeroed
        ys = []
        for at in tiles:
            xt = x[:, (at - heads.start) * p:(at - heads.start) * p + lanes]
            y = None
            for k in range(tile_heads):
                scores = cb * jnp.exp(jnp.where(
                    token >= other, cs[at + k] - cs_row_ref[at + k:at + k + 1],
                    -jnp.inf))
                if steps_dt:
                    scores = scores * dt_row_ref[at + k:at + k + 1]
                mine = xt if tile_heads == 1 else jnp.where(
                    own == k, xt, jnp.zeros_like(xt))
                part = jnp.dot(scores.astype(act), mine,
                               preferred_element_type=f32)
                y = part if y is None else y + part
            ys.append(y)
        xf = x.astype(f32)
        slot = group if step is None else step * step_groups + group
        state = state_ref[slot]                              # (N, per P)
        since = side_by_side([_of_tile(cs, at, tile_heads, p, (qlen, lanes))
                              for at in tiles])
        last = end_ref[:, of_group]                          # (1, per P)
        # what the incoming state gives each token of the row
        y = side_by_side(ys) + _dot(c.astype(f32), state) * jnp.exp(since)
        if skip:
            y = y + xf * d_ref[:, of_group]
        if eps is not None:
            z = z_ref[:, of_group]
            y = y * (z * jax.nn.sigmoid(z))
            y = y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps) \
                * w_ref[:, of_group]
        if out_eps is None:
            o_ref[:, of_group] = y.astype(o_ref.dtype)
        else:
            y_ref[step, :, of_group] = y
        # the carry: one multiply-add of the state a row
        to_end = jnp.exp(last - since)
        if steps_dt:
            dts = {j: dt_ref[:, j:j + 1] for j in heads}
            to_end = to_end * side_by_side([
                _of_tile(dts, at, tile_heads, p, (qlen, lanes))
                for at in tiles])
        state = jnp.exp(last) * state + _dot(b.astype(f32).T, xf * to_end)
        # inside the kernel the pair of conversions is Mosaic's to lower,
        # and it keeps both (``ops/deltanet.py``)
        state_ref[slot] = state.astype(state_dtype).astype(f32)

    if out_eps is None:
        return
    gate_ref[step] = jax.nn.sigmoid(g_ref[...])

    @pl.when(step == y_ref.shape[0] - 1)
    def _():
        width = y_ref.shape[2]
        ys = [y_ref[s] for s in range(y_ref.shape[0])]
        squares = sum(jnp.sum(y * y, -1, keepdims=True) for y in ys)
        scale = lax.rsqrt(squares / o_ref.shape[1] + out_eps)
        for s, y in enumerate(ys):
            lanes = slice(s * width, (s + 1) * width)
            o_ref[:, lanes] = (y * scale * ow_ref[:, lanes]
                               * gate_ref[s]).astype(o_ref.dtype)


def ssd_scan(xs, dt, a, b, c, d, row_first, state_dtype=jnp.float32,
             interpret: bool = False, gated_norm=None, head_norm=None,
             out_norm=None):
    """The scan of one block over a packed pool.

    ``xs`` (rows, Q, H, P); ``dt`` (rows, Q, H) float32, after its
    softplus, or None for unit steps; ``a`` (H,) float32, negative;
    ``b``, ``c`` (rows, Q, G, N), head h reading group h // (H // G);
    ``d`` (H,) float32 or None for no skip term; ``row_first`` (rows,)
    bool. -> float32 (rows, Q, H, P).

    ``gated_norm``: None, or (``z``, ``weight``, ``eps``), the Mamba-2
    block's gated RMS norm as the kernel's last lines, on ``y`` while
    it is in VMEM: ``g = y silu(z)``, ``g rsqrt(mean(g^2) + eps) weight``
    with the mean over each group's ``H / G * P`` columns, in float32,
    -> ``xs``'s dtype. ``z`` (rows, Q, H P) float32; ``weight`` (H P,).

    ``head_norm``: None, or (``b_weight``, ``c_weight``, ``eps``,
    ``c_scale``, ``cos``, ``sin``), lightning attention's lines in front
    of the scan as the kernel's first: ``b`` and ``c`` come float32 as
    their products wrote them, and a step's block goes, in VMEM, through
    each group's RMS norm over its ``N`` columns (weights (N,)), the
    rotation ``x cos + roll(x, N / 2) sin`` (``cos``, ``sin`` float32
    (rows, Q, N), the sign in the table: ``ops/banded.band_tables``),
    ``c`` times ``c_scale``, and one rounding to ``xs``'s dtype — the
    float32 operations of an RMS norm and ``ops/rope.rotate`` in their
    order.

    ``out_norm``: None, or (``gate``, ``weight``, ``eps``), lightning
    attention's lines behind the scan as the kernel's last: ``y
    rsqrt(mean(y^2) + eps) weight sigmoid(gate)`` with the mean over all
    ``H P`` columns, in float32 on the float32 ``y``, -> ``xs``'s dtype.
    ``gate`` (rows, Q, H P) float32; ``weight`` (H P,). The mean spans
    every step's heads, so the grid is (row, step): a row's ``y`` and
    every step's states stay in VMEM and the row's last step writes it.

    ``state_dtype`` is the precision the states are carried in
    between rows: float32 in the program; the lower-precision control
    of the tests passes bfloat16. ``interpret`` runs the kernel in
    interpret mode (a device that is no TPU)."""
    rows, q, heads, p = xs.shape
    groups, n = b.shape[2:]
    per = heads // groups
    f32 = jnp.float32
    by_row = out_norm is not None
    with jax.named_scope("scan"):
        together = _groups_a_step(groups, per, p)
        steps, mine = groups // together, together * per

        def of_step(x):
            """(lead, Q, H) -> (lead, steps, Q, a step's heads) and its
            transpose: the two orientations the kernel reads."""
            x = x.reshape(x.shape[0], q, steps, mine).transpose(0, 2, 1, 3)
            return x, x.transpose(0, 1, 3, 2)

        def at(index):
            """A block's index from (step, row) under the grid's order."""
            if by_row:
                return lambda r, i, _: index(i, r)
            return lambda i, r, _: index(i, r)

        def columns(width):
            return pl.BlockSpec((None, q, width), at(lambda i, r: (r, 0, i)))

        def small(*block, row=lambda r: r):
            return pl.BlockSpec((None, None) + block,
                                at(lambda i, r: (row(r), i, 0, 0)))
        # log decay a token; with unit steps the same in every row: one
        # block a step, which the pipeline fetches once
        la = jnp.broadcast_to(a.astype(f32), (1, q, heads)) if dt is None \
            else dt.astype(f32) * a.astype(f32)
        cs = jnp.cumsum(la, axis=1)
        lead = (lambda r: r) if dt is not None else (lambda r: 0)
        operands = [xs.reshape(rows, q, heads * p),
                    b.reshape(rows, q, groups * n),
                    c.reshape(rows, q, groups * n), *of_step(cs),
                    jnp.repeat(cs[:, -1, :], p, axis=1)
                    .reshape(-1, steps, 1, mine * p)]
        specs = [columns(mine * p), columns(together * n),
                 columns(together * n), small(q, mine, row=lead),
                 small(mine, q, row=lead), small(1, mine * p, row=lead)]
        if dt is not None:
            operands += of_step(dt.astype(f32))
            specs += [small(q, mine), small(mine, q)]
        of_heads = pl.BlockSpec((1, mine * p), at(lambda i, r: (0, i)))
        if d is not None:
            operands.append(jnp.repeat(d.astype(f32), p)[None, :])
            specs.append(of_heads)
        eps = turn = out_eps = None
        if gated_norm is not None:
            z, weight, eps = gated_norm
            operands += [z, weight.astype(f32)[None, :]]
            specs += [columns(mine * p), of_heads]
        if head_norm is not None:
            b_weight, c_weight, head_eps, c_scale, cos, sin = head_norm
            turn = (head_eps, c_scale)
            operands += [b_weight.astype(f32)[None, :],
                         c_weight.astype(f32)[None, :], cos, sin]
            specs += [pl.BlockSpec((1, n), at(lambda i, r: (0, 0)))] * 2 \
                + [pl.BlockSpec((None, q, n), at(lambda i, r: (r, 0, 0)))] * 2
        out_specs, scratch = columns(mine * p), [
            pltpu.VMEM((together, n, per * p), f32)]
        out_shape = jax.ShapeDtypeStruct(
            (rows, q, heads * p),
            f32 if eps is None and not by_row else xs.dtype)
        if by_row:
            gate, weight, out_eps = out_norm
            operands += [gate, weight.astype(f32)[None, :]]
            specs += [columns(mine * p), pl.BlockSpec(
                (1, heads * p), at(lambda i, r: (0, 0)))]
            out_specs = pl.BlockSpec((None, q, heads * p),
                                     at(lambda i, r: (r, 0, 0)))
            scratch = [pltpu.VMEM((groups, n, per * p), f32),
                       pltpu.VMEM((steps, q, mine * p), f32),
                       pltpu.VMEM((steps, q, mine * p), f32)]
        out = pl.pallas_call(
            functools.partial(_kernel, per=per, p=p, n=n,
                              steps_dt=dt is not None, skip=d is not None,
                              eps=eps, state_dtype=state_dtype,
                              turn=turn, out_eps=out_eps),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(rows, steps) if by_row else (steps, rows),
                in_specs=specs, out_specs=out_specs,
                scratch_shapes=scratch),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary") if by_row
                else ("parallel", "arbitrary")),
            interpret=interpret, name=KERNEL_NAME,
        )(row_first.astype(jnp.int32), *operands)
    return out.reshape(rows, q, heads, p)
