"""The delta rule over a packed pool of rows in its blocked (WY / UT)
form, under two gates: a *scalar* a value head a token
(:func:`gated_delta_rule`: Gated DeltaNet, Qwen3-Next) and a *vector*,
one decay a key channel (:func:`channel_gated_delta_rule`: Kimi Delta
Attention, Kimi-Linear). Each is one Pallas TPU kernel that keeps a
row's arrays and the carried state in VMEM; the state is reset where a
request's first row starts.

*What the two share*: the row (``Q`` tokens, a power of two) as the
block, the unit-triangular system and its solve
(:func:`unit_lower_inverse`), the grid (head group, row) with the rows
innermost and in order, the head group's states in a VMEM scratch that
lives from one grid step to the next, ``row_first`` as a scalar-prefetch
operand that zeroes the scratch where a request opens, float32 decays,
steps, solve and states, one product helper that keeps every bfloat16
part its operands hold (below), the first and last lines around the
rule (below), ``state_dtype`` for the control arm, and ``interpret``
for a device that is no TPU. *Where
they part*: the scalar rule multiplies a ``Q x Q`` decay triangle onto
``k k^T`` and ``q k^T`` *after* the products, and two value heads share
one key head's scores; under the vector gate the decay stands *inside*
the sum over the key channels, every head has its own ``q`` and ``k``,
the running sums are ``(Q, Dk)`` a head and not ``(Q, 1)``, and the
scores are assembled from pieces whose exponents are all at or under
zero (below).

A *row* is one chunk of ``Q`` consecutive tokens; a request occupies
consecutive rows of the pool and ``row_first[r]`` says that row ``r``
opens a request (a pad row opens one of its own). Per value head, with
state ``S`` (``Dk`` x ``Dv``, zero at a request's first token), a decay
``alpha_t`` in (0, 1] and a step ``beta_t`` in (0, 1)::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

(Gated DeltaNet, Yang et al., arXiv:2412.06464.) The transition is a
*matrix*, ``alpha_t (I - beta_t k_t k_t^T)``: transitions do not
commute, so the closed form ``ops/ssd.py`` carries its states with (one
``rows x rows`` matrix of scalar decays a head) does not exist here.
Under the vector gate ``alpha_t`` is ``Diag(alpha_t)``, one decay a key
channel (Kimi Linear, arXiv:2510.26692)::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T

Inside a row the rule is blocked as the paper blocks it. With ``g`` the
running sum of ``log alpha`` inside the row and ``L`` the strictly lower
triangle of ``beta_i (k_i . k_j) exp(g_i - g_j)``, the tokens' effective
updates are one unit-triangular system, ``T = (I + L)^-1``::

    v_new = T beta (v - (exp(g) k) S_in)   what each token writes: its
                                           value less what the incoming
                                           state holds of it already
    o     = (exp(g) q) S_in + tril((q . k) exp(g_i - g_j)) v_new
    S_out = exp(g_Q) S_in + (exp(g_Q - g) k)^T v_new

(the paper's ``U - W S_in`` with ``U = T (beta v)`` and ``W = T (beta
exp(g) k)`` is the first line with ``T`` taken out of both terms: one
product with ``T`` where there were two, and ``(exp(g) k) S_in`` waits
on nothing of the solve.)

(under the vector gate ``g`` is ``(Q, Dk)``, ``exp(g) k`` a product a
channel, and the pair's decay lies inside the dot: ``sum_d k_id k_jd
exp(g_id - g_jd)``.)

``T`` is computed by forward substitution (:func:`unit_lower_inverse`):
row by row inside diagonal blocks of 32 (of which the rows from ``a`` on
hold zeros from column ``a + 7`` on: those columns are neither spread
for them nor taken off them), and block by block above them (the
inverse of ``[[A, 0], [B, D]]`` is ``[[A^-1, 0], [-D^-1 B A^-1,
D^-1]]``: a level of the doubling writes the rows of the odd blocks
alone, so it multiplies those ``Q / 2`` rows - against ``lower``, of
which it keeps ``B``'s columns, then against the inverses at hand - and
leaves the even blocks' rows, whose products would be zeros, as they
are). It is exact, and not the Neumann series, whose terms grow where
neighbouring keys are alike.

*The kernel.* The grid is (head group, row): a grid step takes one row
of ``_KEY_HEADS`` key heads with the value heads that read them; the
row axis is innermost and sequential, so a head group walks the pool's
rows in order with its states in a VMEM scratch that lives from one
grid step to the next. ``row_first`` is a scalar-prefetch operand: a
step whose row opens a request zeroes the scratch before it reads it.
A step reads its heads' ``q``, ``k``, ``v`` (a key head once for its
value heads), the running sums and the steps, forms ``k . k``, ``q .
k``, the decay triangle, ``L`` and ``T`` in VMEM, applies the
equations above against the state at hand, the carry in its sequential
form (the last line: one product), and writes ``o``. Nothing of ``Q x
Q`` or ``Q x Dk`` a head reaches HBM. The heads of a step are
independent chains, unrolled side by side, stage by stage, with the
step's loads in front of them and its stores behind (a store between
two heads' chains holds the second's loads back: PERF.md section 6,
PR 58). Only the scalar gate's running sum ``g`` (a few bytes a
token and head) is formed outside, and it and the steps are handed over
in both the orientations the kernel reads them: a token a sublane,
where they scale rows, and a token a lane, where they scale columns.

*What stands around the rule in both mixers is the kernels' first and
last lines*, on a head's ``(Q, D)`` slice while it is in VMEM. In
front (:func:`_rounded_qk`): ``q`` and ``k`` are two column ranges of
the float32 array the convolution wrote, un-normalised; the kernel
divides a head's slice by its L2 norm in float32, scales ``q`` by
``Dk^-1/2`` and rounds once to the activations' dtype (``v``'s). Behind
(:func:`_gated_norm`): a head's float32 result through the head's RMS
norm, times the output gate (``silu`` or ``sigmoid`` of a pre-activation
read in float32 as its product wrote it), rounded once on the store:
the kernel's result is the operand of the mixer's last product.
Between the convolution and that product no array with a head axis
exists in HBM.

*A product's parts.* Decays, steps, ``T`` and states are float32, and
the matrix unit multiplies bfloat16: a product (:func:`_product`) takes
each operand as its bfloat16 *parts* (:func:`_parts`) - a float32
operand as three (the value rounded, the remainder rounded, the rest:
24 bits in three times 8, split by hand once an operand however many
products it enters), an operand that arrives in bfloat16 as the one it
is - and issues one bfloat16 pass with float32 accumulation a pair of
parts it keeps: of two float32 operands the six of nine whose orders
add up to less than three (what ``highest`` keeps; the three dropped
lie 2^-24 under the product), of a float32 and a bfloat16 operand all
three, which is that product to the bit of its float32 accumulation,
and of two bfloat16 operands the one. The choice is by the operand's
dtype and nothing else: with float32 activations (the tests') every
product is six passes. No float32 operand loses a part.

*Where the steps and decays stand.* A scale a token stands where it
costs no part: the steps scale ``T``'s columns (``T * beta_j``, ahead of
the one split ``T`` gets), and under the scalar gate the decays scale a
product's rows behind it - ``exp(g_i) (k S_in)``, ``exp(g_i) (q
S_in)`` - or the float32 operand beside ``k`` - ``k^T (exp(g_Q - g)
v_new)`` - so that ``q`` and ``k`` enter in the activations' dtype as
their one rounding left them: one part, three passes. Under the vector
gate the decays are a channel's and stay on ``k`` and ``q``. A head and
row takes 46 passes of 128^3 under the vector gate (the levels 4, the
solve 12, ``k S_in`` 6, ``v_new`` 6, ``o`` 12, the carry 6) and 34 a
value head under the scalar one (the scores 1, the solve 12, ``k S_in``
3, ``v_new`` 6, ``o`` 3 + 6, the carry 3): :func:`_passes`, which the
call's cost estimate reads and a test holds to the kernels' traced
bodies; they were 74 and 61 with every product whole and at
``highest``. What a pass costs on the v5e is less the matrix unit's
time than the vector unit's around it - an operand's split is seven
passes over it, a product's sum five - so an operand is split once and
no row is split that a product does not read (PERF.md section 6, PR
58).

*The vector gate's scores.* ``exp(g_i - g_j)`` a channel cannot be
split as ``exp(g_i) exp(-g_j)``: where a channel fades in a few tokens
``exp(-g_j)`` overflows float32 inside one row. Written as ``(k_i
exp(g_i - g_n)) . (k_j exp(g_n - g_j))`` it is exact, and both factors
are at most one, wherever the reference point ``n`` lies between the
pair (``j < n <= i``). The kernel takes the pairs of a row level by
level of a binary cut: at the level of blocks of ``B`` tokens (``Q / 2``
down to ``_PAIR_BASE``) the pairs with ``i`` in the second and ``j`` in
the first half of one block of ``2 B`` take ``n`` at the second half's
first token, *every* such block at once in one product
(:func:`_level`): ``exp(g_i - g_n)`` and ``k_i``, ``q_i`` times it are
formed for the second halves' rows alone and stacked, ``k``'s over
``q``'s, as one left operand of ``Q`` rows; ``k_j exp(g_n - g_j)`` for
the first halves' rows alone, laid where those tokens lie with zero
rows between; the pairs of two different blocks are masked away. The
pairs inside a block of ``_PAIR_BASE`` are formed offset by offset on
the vector unit, ``sum_d k_id k_(i-s)d exp(g_id - g_(i-s)d)`` for ``s``
= 1 .. ``_PAIR_BASE`` - 1 by a sublane roll, in float32. The running
sums themselves are shifted additions on the vector unit inside the
kernel (:func:`_running_sums`; the caller hands over ``log alpha`` as
it is: ``(tokens, heads x Dk)`` float32 read once, and no ``g`` in
HBM), and the state is kept transposed (``Dv x Dk``), so that a
channel's decay over the row scales a lane and needs ``g_Q`` in one
orientation only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the kernel's name in the device's trace and in the scope table
KERNEL_NAME = "gated_delta_rule"

#: the block the triangular solve substitutes row by row; larger blocks
#: are merged from their halves. On the v5e 31 columns on the vector
#: unit cost less than the level of merges they replace, 63 cost more
#: (PERF.md section 6, PR 41: a level was two whole products then; since
#: PR 58 it is two over half the rows, and the substitution is the
#: solve's larger part)
_SOLVE_BASE = 32

#: the vector gate's kernel in the device's trace and in the scope table
KDA_KERNEL_NAME = "channel_gated_delta_rule"

#: key heads a grid step (with the value heads that read them): the
#: body is unrolled a head, and past two the kernel gains 1-3% for a
#: compile time that doubles with the heads (the same readings)
_KEY_HEADS = 2


_BF16 = jnp.bfloat16

#: the bfloat16 parts of a float32 operand (:func:`_parts`)
_WHOLE = 3

#: the contractions the kernels take: ``a b``, ``a b^T``, ``a^T b``
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _parts(x):
    """The bfloat16 parts a product takes ``x`` in, largest first. An
    operand that arrives in bfloat16 is its one part. A float32 operand
    is three: ``x`` rounded, the remainder rounded, the remainder of
    that (24 bits in three times 8: the parts add up to ``x`` to the
    bit). Split once an operand, whatever the products it enters."""
    if x.dtype == _BF16:
        return (x,)
    x = x.astype(jnp.float32)
    high = x.astype(_BF16)
    rest = x - high.astype(jnp.float32)
    mid = rest.astype(_BF16)
    return high, mid, (rest - mid.astype(jnp.float32)).astype(_BF16)


def _terms(left: int, right: int):
    """The (part of a, part of b) pairs a product of ``left`` by
    ``right`` parts keeps, smallest first: those whose orders add up to
    less than the longer operand's parts - the 6 of 9 that ``highest``
    keeps of two float32 operands (what it drops lies 2^-24 under the
    product), all 3 where one operand is one part, the 1 of two."""
    keep = max(left, right)
    return sorted(((i, j) for i in range(left) for j in range(right)
                   if i + j < keep), key=lambda at: -sum(at))


def _product(a, b, dims=_NN):
    """The product of two operands given as their parts
    (:func:`_parts`): one bfloat16 pass of the matrix unit a term of
    :func:`_terms`, accumulated in float32, the smallest terms summed
    first."""
    total = None
    for i, j in _terms(len(a), len(b)):
        term = lax.dot_general(a[i], b[j], dims,
                               preferred_element_type=jnp.float32)
        total = term if total is None else total + term
    return total


def _rows(x, block: int, odd: bool):
    """The rows of every second block of ``block`` rows of ``x``,
    stacked: the odd blocks' or the even blocks'. ``block`` a multiple
    of the dtype's sublane tile: a selection of whole tiles."""
    return jnp.concatenate([x[i:i + block] for i in range(
        block if odd else 0, x.shape[0], 2 * block)], axis=0)


def _interleaved(even, odd, block: int):
    """:func:`_rows` undone: blocks of ``block`` rows of ``even`` and
    ``odd`` in turn."""
    return jnp.concatenate([
        x[i:i + block] for i in range(0, even.shape[0], block)
        for x in (even, odd)], axis=0)


def _spread_columns(packed, base: int, columns: int):
    """``packed`` (r, C), C // ``base`` groups of ``base`` lanes -> the
    first ``columns`` of the arrays (r, C) in whose ``j``-th every lane
    of a group holds the group's lane ``j``. A tree over the bits of
    ``j``, highest first: a level halves the lanes a value may still
    come from, by one rotation to each side and a select, on the lanes'
    own unit (2 ``base`` - 2 rotations for all the columns); a branch
    that leads to no column under ``columns`` is not built."""
    size = packed.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, packed.shape, 1)
    level = [(0, packed)]
    bit = base // 2
    while bit:
        high = (lane & bit) != 0
        level = [branch for first, x in level for branch in (
            # bit clear: the lanes that have it set read ``bit`` below
            (first, jnp.where(high, pltpu.roll(x, bit, 1), x)),
            (first + bit, jnp.where(high, x, pltpu.roll(x, size - bit, 1))))
            if branch[0] < columns]
        bit //= 2
    return [x for _, x in level]


#: the ``eps`` under the root of a head's L2 norm, as both families
#: publish it
_L2_EPS = 1e-6


def _unit(x):
    """A head's (Q, Dk) float32 slice over its L2 norm."""
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + _L2_EPS)


def _rounded_qk(q_ref, k_ref, columns, act):
    """The rule's first lines on one head: its ``columns`` of ``q_ref``
    and ``k_ref`` (float32, as the convolution wrote them) over their L2
    norms, ``q`` times ``Dk^-1/2``, each rounded once to ``act``."""
    scale = (columns.stop - columns.start) ** -0.5
    return ((_unit(q_ref[:, columns]) * scale).astype(act),
            _unit(k_ref[:, columns]).astype(act))


def _gated_norm(o, z, weight, eps: float, activation: str):
    """The rule's last lines on one head: ``o`` (Q, Dv) float32 through
    the head's RMS norm (``weight`` (1, Dv), as stored), times the
    output gate, ``activation`` of the pre-activation ``z`` (Q, Dv), all
    in float32."""
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * weight
    gate = jax.nn.sigmoid(z)
    return o * (z * gate if activation == "silu" else gate)


def unit_lower_inverse(lower):
    """``(I + lower)^-1`` for ``lower`` (C, C) float32, strictly lower
    triangular (zero on and over the diagonal); C a power of two. ->
    (C, C), unit lower triangular. One of :func:`unit_lower_inverses`."""
    return unit_lower_inverses([lower])[0]


def unit_lower_inverses(lowers):
    """``(I + lower)^-1`` of every ``lower`` (C, C) of a list - a grid
    step's heads - as :func:`unit_lower_inverse` states it. Written for
    the kernel's body: whole arrays in VMEM, the lanes the minor axis.

    The diagonal blocks of ``_SOLVE_BASE`` row by row, all of them and
    of every array at once in a packed form (:func:`_substituted`: one
    chain of operations for the step, which is traced and lowered
    once); then, with the inverses of the diagonal blocks of b in ``x``
    (block-diagonal), the blocks of 2b, level by level
    (:func:`_merged`). ``lower`` and ``x`` are split into their parts
    once: ``lower`` for every level, and of ``x`` a level splits only
    the rows it wrote."""
    size = lowers[0].shape[0]
    base = min(size, _SOLVE_BASE)
    blocks = size // base
    lane = lax.broadcasted_iota(jnp.int32, (base, size), 1)
    packed = []
    for lower in lowers:
        rows = jnp.zeros((base, size), lower.dtype)
        for i in range(blocks):
            rows = rows + jnp.where(
                lane // base == i, lower[i * base:(i + 1) * base], 0.0)
        packed.append(rows)
    at = lax.broadcasted_iota(jnp.int32, (size, size), 0) // base
    to = lax.broadcasted_iota(jnp.int32, (size, size), 1) // base
    inverses = []
    for lower, solved in zip(lowers, _substituted(packed)):
        x = jnp.where(at == to, jnp.concatenate([solved] * blocks, axis=0),
                      0.0)
        if base < size:
            xs, parts = _parts(x), _parts(lower)
            block = base
            while block < size:
                x, xs = _merged(x, xs, parts, block)
                block *= 2
        inverses.append(x)
    return inverses


#: the rows the substitution keeps together: a float32 tile's
_SLAB = 8


def _substituted(packed):
    """The inverses of the unit lower triangular blocks whose strict
    lower triangles the arrays of ``packed`` hold, each (b, C): row
    ``a`` of every block in row ``a``, a block's columns where they lie
    -> the same form. Column ``j`` of the substitution takes
    ``lower[a, j] * row_j`` off every row ``a > j``: one multiply-add,
    the column spread over its block's lanes. Taken slab by slab of
    ``_SLAB`` rows, the arrays' slabs stacked: the rows from ``at`` on
    hold zeros from column ``at + _SLAB - 1`` on, so those columns are
    neither spread for them nor taken off them."""
    base, size = packed[0].shape
    rows = min(base, _SLAB)
    shape = (rows * len(packed), size)
    lane = lax.broadcasted_iota(jnp.int32, shape, 1) % base
    row = lax.broadcasted_iota(jnp.int32, shape, 0) % rows
    slabs = range(0, base, rows)
    solved = [jnp.where(lane == row + at, 1.0, 0.0).astype(packed[0].dtype)
              for at in slabs]
    columns = [_spread_columns(
        jnp.concatenate([x[at:at + rows] for x in packed], axis=0), base,
        at + rows - 1) for at in slabs]
    for j in range(base - 1):
        # row j of every array, over the rows of its slab
        pivot = jnp.concatenate([
            jnp.broadcast_to(solved[j // rows][i + j % rows:i + j % rows + 1],
                             (rows, size))
            for i in range(0, shape[0], rows)], axis=0)
        for slab, of_slab in enumerate(columns):
            if j < len(of_slab):
                solved[slab] = solved[slab] - of_slab[j] * pivot
    return [jnp.concatenate([x[i:i + rows] for x in solved], axis=0)
            for i in range(0, shape[0], rows)]


def _merged(x, xs, lowers, block: int):
    """One level of the doubling: ``x`` (C, C) holds the inverses of the
    diagonal blocks of ``block``, ``xs`` its parts, ``lowers`` the parts
    of the whole strictly lower triangle -> the inverses of the blocks
    of ``2 block`` and their parts. Only the odd blocks' rows change
    (by ``-D^-1 B A^-1`` in the columns of the even block before them),
    so only they are multiplied: C / 2 rows against ``lower`` - of which
    the product keeps the columns of the row's even block, ``B`` - and
    then against ``x``."""
    size = x.shape[0]
    late = tuple(_rows(part, block, True) for part in xs)
    row = lax.broadcasted_iota(jnp.int32, (size // 2, size), 0) // block
    column = lax.broadcasted_iota(jnp.int32, (size // 2, size), 1) // block
    under = jnp.where(column == 2 * row, _product(late, lowers), 0.0)
    merged = _rows(x, block, True) - _product(_parts(under), xs)
    return (_interleaved(_rows(x, block, False), merged, block),
            tuple(_interleaved(_rows(old, block, False), new, block)
                  for old, new in zip(xs, _parts(merged))))


def _kernel(first_ref, q_ref, k_ref, v_ref, g_ref, b_ref, g_row_ref,
            b_row_ref, z_ref, w_ref, o_ref, state_ref, *, per: int, dk: int,
            dv: int, eps: float, activation: str, state_dtype):
    """One row of one head group. ``q_ref``, ``k_ref`` (Q, heads *
    Dk) float32, as the convolution wrote them; ``v_ref`` (Q, heads *
    per * Dv) in the activations' dtype; ``g_ref``, ``b_ref`` (Q, value
    heads) the running sums and the steps, a token a sublane;
    ``g_row_ref``, ``b_row_ref`` (value heads, Q) both again, a token a
    lane; ``z_ref`` (Q, heads * per * Dv) float32 the output gate
    before its activation, ``w_ref`` (1, Dv) the head norm's weight;
    ``o_ref`` as ``v_ref``; ``state_ref`` (value heads, Dk, Dv) float32,
    carried."""
    f32 = jnp.float32
    qlen = q_ref.shape[0]
    act = v_ref.dtype

    @pl.when(first_ref[pl.program_id(1)] != 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    token = lax.broadcasted_iota(jnp.int32, (qlen, qlen), 0)
    other = lax.broadcasted_iota(jnp.int32, (qlen, qlen), 1)
    weight = w_ref[...]
    heads = range(k_ref.shape[1] // dk * per)
    of_head = [slice(j * dv, (j + 1) * dv) for j in heads]
    # the step's loads stand first and its stores last, the heads stage
    # by stage between them (the module's docstring). q and k stay in
    # ``act`` up to the products: one part each
    keys = [tuple(_parts(x) for x in _rounded_qk(
        q_ref, k_ref, slice(head * dk, (head + 1) * dk), act))
        for head in range(len(heads) // per)]
    scores = [(_product(k, k, _NT), _product(q, k, _NT)) for q, k in keys]
    gates = [z_ref[:, of_head[j]] for j in heads]
    carried = [state_ref[j] for j in heads]
    states = [_parts(x) for x in carried]
    # what a token's value holds that the incoming state does not: the
    # decay a token scales the product's rows, k enters as it is
    fresh = [v_ref[:, of_head[j]].astype(f32) - jnp.exp(g_ref[:, j:j + 1])
             * _product(keys[j // per][1], states[j]) for j in heads]
    decays = [jnp.exp(jnp.where(
        token >= other, g_ref[:, j:j + 1] - g_row_ref[j:j + 1, :], -jnp.inf))
        for j in heads]
    # the steps scale T's columns
    solved = [t * b_row_ref[j:j + 1, :] for j, t in zip(
        heads, unit_lower_inverses([jnp.where(
            token > other,
            b_ref[:, j:j + 1] * scores[j // per][0] * decays[j], 0.0)
            for j in heads]))]
    results = []
    for j in heads:
        q, k = keys[j // per]
        g = g_ref[:, j:j + 1]                                # (Q, 1)
        end = g[qlen - 1:qlen]                               # (1, 1)
        v_new = _product(_parts(solved[j]), _parts(fresh[j]))
        o = jnp.exp(g) * _product(q, states[j]) + _product(
            _parts(scores[j // per][1] * decays[j]), _parts(v_new))
        state = jnp.exp(jnp.broadcast_to(end, (1, dv))) * carried[j] \
            + _product(k, _parts(jnp.exp(end - g) * v_new), _TN)
        results.append((_gated_norm(o, gates[j], weight, eps, activation),
                        state))
    for j, (o, state) in zip(heads, results):
        o_ref[:, of_head[j]] = o.astype(o_ref.dtype)
        # inside the kernel the pair of conversions is Mosaic's to
        # lower, and it keeps both (XLA on the v5e drops such a pair
        # and keeps the excess precision: my chip run, PR 39)
        state_ref[j] = state.astype(state_dtype).astype(f32)


def gated_delta_rule(qk, v, log_alpha, beta, z, norm_weight, row_first, *,
                     key_heads: int, eps: float, activation: str,
                     state_dtype=jnp.float32, interpret: bool = False):
    """The rule of one layer over a packed pool, from the convolution's
    result to the operand of the mixer's last product.

    ``qk`` (rows, Q, 2 Hk Dk) float32: every key head's ``q``, then
    every key head's ``k``, as the convolution wrote them (the kernel
    normalises, scales ``q`` and rounds: the module's docstring); ``v``
    (rows, Q, Hv Dv) in the activations' dtype, value head h reading key
    head h // (Hv // Hk); ``log_alpha`` (rows, Q, Hv) float32, <= 0;
    ``beta`` (rows, Q, Hv) float32; ``z`` (rows, Q, Hv Dv) float32, the
    output gate before its ``activation`` ("silu" or "sigmoid");
    ``norm_weight`` (Dv,) the head norm's, with ``eps``; ``row_first``
    (rows,) bool; ``key_heads`` Hk; Q a power of two. -> (rows, Q, Hv
    Dv) in ``v``'s dtype.

    ``state_dtype`` is the precision the states are carried in between
    rows: float32 in the program; the lower-precision control passes
    bfloat16. ``interpret`` runs the kernel in interpret mode (a device
    that is no TPU)."""
    assert activation in ("silu", "sigmoid"), activation
    return _rule_call(qk, v, log_alpha, beta, z, norm_weight, row_first,
                      key_heads=key_heads, eps=float(eps),
                      activation=activation,
                      state_dtype=jnp.dtype(state_dtype), interpret=interpret)


def _passes(qlen: int, dk: int, dv: int, act, *, channel: bool,
            per: int = 1) -> dict:
    """The matrix unit's work a value head and row, as the kernels'
    bodies issue it: product -> (multiply-adds of one pass over the
    product's shape, passes). ``act`` the activations' dtype, ``channel``
    the vector gate's kernel, ``per`` the value heads that share a key
    head's score products. A merge of the solve is two products over
    the odd blocks' Q / 2 rows; an operand in ``act`` is one part where
    that is bfloat16 (:func:`_terms`); the vector gate's running sums
    are the vector unit's."""
    given = 1 if jnp.dtype(act) == _BF16 else _WHOLE
    merges = max(0, (qlen // _SOLVE_BASE).bit_length() - 1)
    levels = max(0, (qlen // min(qlen, _PAIR_BASE)).bit_length() - 1)
    held = _WHOLE if channel else given    # what multiplies k or q there

    def passes(left: int, right: int) -> int:
        return len(_terms(left, right))
    return {
        "scores": (levels * qlen * dk * qlen if channel
                   else 2 * qlen * dk * qlen / per, passes(given, given)),
        "solve": (merges * 2 * (qlen // 2) * qlen * qlen,
                  passes(_WHOLE, _WHOLE)),
        "k, state": (qlen * dk * dv, passes(held, _WHOLE)),
        "v_new": (qlen * qlen * dv, passes(_WHOLE, _WHOLE)),
        "o, state": (qlen * dk * dv, passes(held, _WHOLE)),
        "o, v_new": (qlen * qlen * dv, passes(_WHOLE, _WHOLE)),
        "carry": (qlen * dk * dv, passes(held, _WHOLE))}


def _cost(passes: dict, rows: int, heads: int, qlen: int, operands,
          out) -> pl.CostEstimate:
    """What a call costs, for the compiler that schedules around it: it
    overlaps its own copies (the residual stream's prefetch in front of
    ``o``'s product, for one) with a custom call only as far as it
    knows how long the call runs. The bfloat16 passes of
    :func:`_passes`, which follows the kernels' bodies; the vector
    unit's work is left out, the exponentials one a pair of tokens."""
    return pl.CostEstimate(
        flops=int(2 * sum(size * count for size, count in passes.values())
                  * rows * heads),
        transcendentals=rows * heads * qlen * qlen,
        bytes_accessed=sum(x.size * x.dtype.itemsize
                           for x in operands + (out,)))


def _of_group(*block):
    """Head group ``i``'s block of row ``r`` of a (rows, groups, ...)
    array."""
    return pl.BlockSpec((None, None) + block, lambda i, r, _: (r, i, 0, 0))


def _columns(qlen: int, width: int, offset: int = 0):
    """Head group ``i``'s ``width`` columns of row ``r`` of a (rows, Q,
    columns) array, ``offset`` blocks in."""
    return pl.BlockSpec((None, qlen, width),
                        lambda i, r, _: (r, 0, offset + i))


# a function under ``jit`` of its own: a stack's layers call it with the
# same shapes, and the kernel is traced and lowered once for all of them
@functools.partial(jax.jit, static_argnames=(
    "key_heads", "eps", "activation", "state_dtype", "interpret"))
def _rule_call(qk, v, log_alpha, beta, z, norm_weight, row_first, *,
               key_heads, eps, activation, state_dtype, interpret):
    rows, qlen, hv = beta.shape
    hk, dk, dv = key_heads, qk.shape[2] // (2 * key_heads), v.shape[2] // hv
    per = hv // hk
    heads = min(_KEY_HEADS, hk)
    groups, values = hk // heads, heads * per
    f32 = jnp.float32
    # a head group's value heads side by side: (rows, groups, Q, values)
    g = jnp.cumsum(log_alpha.astype(f32), axis=1) \
        .reshape(rows, qlen, groups, values).transpose(0, 2, 1, 3)
    b = beta.astype(f32).reshape(rows, qlen, groups, values) \
        .transpose(0, 2, 1, 3)
    operands = (qk, v, g, b, g.transpose(0, 1, 3, 2),
                b.transpose(0, 1, 3, 2), z.astype(f32),
                norm_weight.astype(f32)[None, :])
    out = jax.ShapeDtypeStruct((rows, qlen, hv * dv), v.dtype)
    return pl.pallas_call(
        functools.partial(_kernel, per=per, dk=dk, dv=dv, eps=eps,
                          activation=activation, state_dtype=state_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(groups, rows),
            in_specs=[_columns(qlen, heads * dk),
                      _columns(qlen, heads * dk, groups),
                      _columns(qlen, values * dv), _of_group(qlen, values),
                      _of_group(qlen, values), _of_group(values, qlen),
                      _of_group(values, qlen), _columns(qlen, values * dv),
                      pl.BlockSpec((1, dv), lambda i, r, _: (0, 0))],
            out_specs=_columns(qlen, values * dv),
            scratch_shapes=[pltpu.VMEM((values, dk, dv), f32)]),
        out_shape=out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=_cost(
            _passes(qlen, dk, dv, v.dtype, channel=False, per=per), rows, hv,
            qlen, operands, out),
        interpret=interpret, name=KERNEL_NAME,
    )(row_first.astype(jnp.int32), qk, *operands)


# -- the gate a channel (Kimi Delta Attention) ----------------------------

#: the block inside which the vector gate's pairs are formed offset by
#: offset on the vector unit; larger blocks pair through the matrix
#: unit, a level a whole product (PERF.md section 6, PR 49)
_PAIR_BASE = 8

#: heads a grid step of the vector gate's kernel (every head has its
#: own keys)
_KDA_HEADS = 2


#: the rows a running sum is first taken inside: a float32 tile's
_SUM_ROWS = 8


def _running_sums(x):
    """``x`` (Q, C) float32 summed down its rows, every row its own and
    all above it, by shifted additions on the vector unit (a product
    with a triangle of ones, three passes and a split, read 0.4 ms a
    layer slower: PERF.md section 6, PR 58). In two steps, for the
    sake of the *differences* the scores take: inside groups of
    ``_SUM_ROWS`` rows, where the sums are small; then every group's
    total, held by all its rows alike, summed over the groups before
    it. Two tokens of a group share the second term to the bit, so
    their difference carries the rounding of one addition, as a sum
    taken token by token would."""
    size = x.shape[0]
    group = min(size, _SUM_ROWS)
    at = lax.broadcasted_iota(jnp.int32, x.shape, 0) % group
    shift = 1
    while shift < group:
        x = x + jnp.where(at >= shift, pltpu.roll(x, shift, 0), 0.0)
        shift *= 2
    # a group's total from its last row up to all its rows
    total = jnp.where(at == group - 1, x, 0.0)
    while shift > 1:
        shift //= 2
        total = total + pltpu.roll(total, size - shift, 0)

    def down(y, rows):
        # whole tiles: zeros above, the rows ``rows`` up below
        return jnp.concatenate(
            [jnp.zeros_like(y[:rows]), y[:size - rows]], axis=0)
    before, shift = down(total, group) if group < size else 0.0, group
    while shift < size - group:
        before = before + down(before, shift)
        shift *= 2
    return x + before


def _level(qf, kf, g, block: int, score_dtype):
    """The pairs of one level of the cut: token ``i`` in the second half
    and ``j`` in the first of one block of ``2 block``, the reference
    point the second half's first token. -> (kk, qk) of the second
    halves' rows, (Q / 2, Q) each, zero off the level's own pairs.

    Only the rows a factor is used in are formed: ``exp(g_i - g_n)``,
    ``k_i`` and ``q_i`` times it for the second halves (the odd blocks
    of ``block``), ``k_j exp(g_n - g_j)`` for the first halves; ``k``'s
    and ``q``'s rows stacked are one left operand, Q rows, against the
    first halves' keys in their own places (zero rows between them)."""
    qlen, dk = g.shape
    half = qlen // 2
    ref = jnp.concatenate([jnp.broadcast_to(g[n:n + 1], (block, dk))
                           for n in range(block, qlen, 2 * block)], axis=0)
    late = jnp.exp(jnp.minimum(_rows(g, block, True) - ref, 0.0))
    early = _rows(kf, block, False) \
        * jnp.exp(jnp.minimum(ref - _rows(g, block, False), 0.0))
    left = jnp.concatenate([_rows(kf, block, True) * late,
                            _rows(qf, block, True) * late], axis=0)
    right = _interleaved(early, jnp.zeros_like(early), block)
    both = _product(_parts(left.astype(score_dtype)),
                    _parts(right.astype(score_dtype)), _NT)
    row = lax.broadcasted_iota(jnp.int32, (half, qlen), 0) // block
    column = lax.broadcasted_iota(jnp.int32, (half, qlen), 1) // block
    owns = column == 2 * row
    return (jnp.where(owns, both[:half], 0.0),
            jnp.where(owns, both[half:], 0.0))


def channel_scores(qf, kf, g, score_dtype):
    """``sum_d x_id k_jd exp(g_id - g_jd)`` for ``x`` = ``k`` (``i > j``)
    and ``x`` = ``q`` (``i >= j``), zero elsewhere: -> two (Q, Q)
    float32. ``qf``, ``kf``, ``g`` (Q, Dk) float32, ``g`` the running
    sums of ``log alpha`` (non-increasing down the tokens). No exponent
    is over zero (the module's docstring). The levels' products take
    their operands in ``score_dtype`` (the activations': one part in
    bfloat16, three in the tests' float32) and accumulate in float32, as
    the scalar rule's two score products do."""
    qlen = g.shape[0]
    base = min(qlen, _PAIR_BASE)
    kk, qk = _offsets(qf, kf, g, base)
    zeros = jnp.zeros((qlen // 2, qlen), jnp.float32)
    block = qlen // 2
    while block >= base:
        # a level writes the odd blocks' rows, and no two levels a pair
        of_k, of_q = _level(qf, kf, g, block, score_dtype)
        kk = kk + _interleaved(zeros, of_k, block)
        qk = qk + _interleaved(zeros, of_q, block)
        block //= 2
    return kk, qk


def _offsets(qf, kf, g, base: int):
    """The pairs inside blocks of ``base`` tokens, offset by offset on
    the vector unit, in float32: token i against token i - s of its own
    block by a sublane roll; a token against itself is ``qk``'s
    diagonal. -> (kk, qk), (Q, Q) each, zero elsewhere."""
    qlen = g.shape[0]
    token = lax.broadcasted_iota(jnp.int32, (qlen, qlen), 0)
    other = lax.broadcasted_iota(jnp.int32, (qlen, qlen), 1)
    inside = lax.broadcasted_iota(jnp.int32, (qlen, 1), 0) % base
    kk = jnp.zeros((qlen, qlen), jnp.float32)
    qk = jnp.where(token == other, jnp.sum(qf * kf, -1, keepdims=True), 0.0)
    for s in range(1, base):
        past = pltpu.roll(kf, s, 0) \
            * jnp.exp(jnp.minimum(g - pltpu.roll(g, s, 0), 0.0))
        here = (token - other == s) & (inside >= s)
        kk = kk + jnp.where(here, jnp.sum(kf * past, -1, keepdims=True), 0.0)
        qk = qk + jnp.where(here, jnp.sum(qf * past, -1, keepdims=True), 0.0)
    return kk, qk


def _kda_kernel(first_ref, q_ref, k_ref, v_ref, a_ref, b_ref, b_row_ref,
                z_ref, w_ref, o_ref, state_ref, *, dk: int, dv: int,
                eps: float, activation: str, state_dtype):
    """One row of one head group. ``q_ref``, ``k_ref`` (Q, heads * Dk)
    float32, as the convolution wrote them; ``v_ref`` (Q, heads * Dv) in
    the activations' dtype; ``a_ref`` (Q, heads * Dk) float32 the ``log
    alpha`` of every channel, ``b_ref`` (Q, heads) the steps, a token a
    sublane, ``b_row_ref`` (heads, Q) the steps again, a token a lane;
    ``z_ref`` (Q, heads * Dv) float32 the output gate before its
    activation, ``w_ref`` (1, Dv) the head norm's weight; ``o_ref`` as
    ``v_ref``; ``state_ref`` (heads, Dv, Dk) float32, carried, a head's
    state transposed."""
    f32 = jnp.float32
    qlen = q_ref.shape[0]
    act = v_ref.dtype

    @pl.when(first_ref[pl.program_id(1)] != 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    weight = w_ref[...]
    heads = range(k_ref.shape[1] // dk)
    of_keys = [slice(head * dk, (head + 1) * dk) for head in heads]
    of_values = [slice(head * dv, (head + 1) * dv) for head in heads]
    # the step's loads stand first and its stores last, the heads stage
    # by stage between them (as in the scalar rule's kernel)
    keys = [tuple(x.astype(f32) for x in _rounded_qk(
        q_ref, k_ref, of_keys[head], act)) for head in heads]
    sums = [_running_sums(a_ref[:, of_keys[head]]) for head in heads]
    gates = [z_ref[:, of_values[head]] for head in heads]
    carried = [state_ref[head] for head in heads]                # (Dv, Dk)
    states = [_parts(x) for x in carried]
    # what a token's value holds that the incoming state does not
    fresh = [v_ref[:, of_values[head]].astype(f32) - _product(
        _parts(jnp.exp(sums[head]) * keys[head][1]), states[head], _NT)
        for head in heads]
    scores = [channel_scores(*keys[head], sums[head], act) for head in heads]
    # the steps scale T's columns
    solved = [_parts(t * b_row_ref[head:head + 1, :]) for head, t in zip(
        heads, unit_lower_inverses([
            b_ref[:, head:head + 1] * scores[head][0] for head in heads]))]
    results = []
    for head in heads:
        (qf, kf), g = keys[head], sums[head]                     # g (Q, Dk)
        end = g[qlen - 1:qlen]                                   # (1, Dk)
        v_new = _parts(_product(solved[head], _parts(fresh[head])))
        o = _product(_parts(qf * jnp.exp(g)), states[head], _NT) \
            + _product(_parts(scores[head][1]), v_new)
        state = carried[head] * jnp.exp(end) + _product(
            v_new, _parts(kf * jnp.exp(end - g)), _TN)
        results.append((_gated_norm(o, gates[head], weight, eps, activation),
                        state))
    for head, (o, state) in zip(heads, results):
        o_ref[:, of_values[head]] = o.astype(o_ref.dtype)
        state_ref[head] = state.astype(state_dtype).astype(f32)


def channel_gated_delta_rule(qk, v, log_alpha, beta, z, norm_weight,
                             row_first, *, eps: float, activation: str,
                             state_dtype=jnp.float32,
                             interpret: bool = False):
    """The rule of one layer over a packed pool, the gate a channel,
    from the convolution's result to the operand of the mixer's last
    product.

    ``qk`` (rows, Q, 2 H Dk) float32: every head's ``q``, then every
    head's ``k``, as the convolution wrote them; ``v`` (rows, Q, H Dv)
    in the activations' dtype; ``log_alpha`` (rows, Q, H Dk) float32, <=
    0, a token's own (not summed); ``beta`` (rows, Q, H) float32; ``z``,
    ``norm_weight``, ``eps``, ``activation``, ``row_first``,
    ``state_dtype`` and ``interpret`` as :func:`gated_delta_rule` takes
    them; Q a power of two. -> (rows, Q, H Dv) in ``v``'s dtype."""
    assert activation in ("silu", "sigmoid"), activation
    return _kda_call(qk, v, log_alpha, beta, z, norm_weight, row_first,
                     eps=float(eps), activation=activation,
                     state_dtype=jnp.dtype(state_dtype), interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "eps", "activation", "state_dtype", "interpret"))
def _kda_call(qk, v, log_alpha, beta, z, norm_weight, row_first, *, eps,
              activation, state_dtype, interpret):
    rows, qlen, heads = beta.shape
    dk, dv = qk.shape[2] // (2 * heads), v.shape[2] // heads
    step = min(_KDA_HEADS, heads)
    groups = heads // step
    f32 = jnp.float32
    b = beta.astype(f32).reshape(rows, qlen, groups, step) \
        .transpose(0, 2, 1, 3)
    operands = (qk, v, log_alpha.astype(f32), b, b.transpose(0, 1, 3, 2),
                z.astype(f32), norm_weight.astype(f32)[None, :])
    out = jax.ShapeDtypeStruct((rows, qlen, heads * dv), v.dtype)
    return pl.pallas_call(
        functools.partial(_kda_kernel, dk=dk, dv=dv, eps=eps,
                          activation=activation, state_dtype=state_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(groups, rows),
            in_specs=[_columns(qlen, step * dk),
                      _columns(qlen, step * dk, groups),
                      _columns(qlen, step * dv), _columns(qlen, step * dk),
                      _of_group(qlen, step), _of_group(step, qlen),
                      _columns(qlen, step * dv),
                      pl.BlockSpec((1, dv), lambda i, r, _: (0, 0))],
            out_specs=_columns(qlen, step * dv),
            scratch_shapes=[pltpu.VMEM((step, dv, dk), f32)]),
        out_shape=out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=_cost(_passes(qlen, dk, dv, v.dtype, channel=True),
                            rows, heads, qlen, operands, out),
        interpret=interpret, name=KDA_KERNEL_NAME,
    )(row_first.astype(jnp.int32), qk, *operands)
