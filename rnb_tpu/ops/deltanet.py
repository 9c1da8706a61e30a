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
steps, solve and states at ``highest`` precision, the first and last
lines around the rule (below), ``state_dtype`` for the control arm, and
``interpret`` for a device that is no TPU. *Where
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

    U = T (beta v)            what each token writes, before the
    W = T (beta exp(g) k)     incoming state's part is taken off
    v_new = U - W S_in
    o     = (exp(g) q) S_in + tril((q . k) exp(g_i - g_j)) v_new
    S_out = exp(g_Q) S_in + (exp(g_Q - g) k)^T v_new

(under the vector gate ``g`` is ``(Q, Dk)``, ``exp(g) k`` a product a
channel, and the pair's decay lies inside the dot: ``sum_d k_id k_jd
exp(g_id - g_jd)``.)

``T`` is computed by forward substitution (:func:`unit_lower_inverse`):
row by row inside diagonal blocks of 32, and block by block above them
(the inverse of ``[[A, 0], [B, D]]`` is ``[[A^-1, 0], [-D^-1 B A^-1,
D^-1]]``: a level of the doubling is two whole ``Q x Q`` products). It
is exact, and not the Neumann series, whose terms grow where
neighbouring keys are alike.

*The kernel.* The grid is (head group, row): a grid step takes one row
of ``_KEY_HEADS`` key heads with the value heads that read them; the
row axis is innermost and sequential, so a head group walks the pool's
rows in order with its states in a VMEM scratch that lives from one
grid step to the next. ``row_first`` is a scalar-prefetch operand: a
step whose row opens a request zeroes the scratch before it reads it.
A step reads its heads' ``q``, ``k``, ``v`` (a key head once for its
value heads), the running sums and the steps, forms ``k . k``, ``q .
k``, the decay triangle, ``L``, ``T``, ``U``, ``W`` in VMEM, applies the
equations above against the state at hand, the carry in its sequential
form (the last line: one product), and writes ``o``. Nothing of ``Q x
Q`` or ``Q x Dk`` a head reaches HBM. The heads of a step are
independent chains of products, unrolled side by side so that the
matrix unit has another head's product to run while one waits on its
own result. Only the running sum ``g`` (a few bytes a token and head)
is formed outside, in both the orientations the kernel reads it.

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

Decays, steps, ``T`` and states are float32; every product that reads
or builds ``T`` or a state takes float32 operands at ``highest``
precision, so that none is rounded to bfloat16 on its way through the
matrix unit. The two score products (``k . k``, ``q . k``) take their
inputs in the activations' dtype and accumulate in float32.

*The vector gate's scores.* ``exp(g_i - g_j)`` a channel cannot be
split as ``exp(g_i) exp(-g_j)``: where a channel fades in a few tokens
``exp(-g_j)`` overflows float32 inside one row. Written as ``(k_i
exp(g_i - g_n)) . (k_j exp(g_n - g_j))`` it is exact, and both factors
are at most one, wherever the reference point ``n`` lies between the
pair (``j < n <= i``). The kernel takes the pairs of a row level by
level of a binary cut: at the level of blocks of ``B`` tokens (``Q / 2``
down to ``_PAIR_BASE``) the pairs with ``i`` in the second and ``j`` in
the first half of one block of ``2 B`` take ``n`` at the second half's
first token, *every* such block at once in one whole ``Q x Dk x Q``
product (a token's factor is clamped to one where it is on the wrong
side, and the pairs the level does not own are masked away); the pairs
inside a block of ``_PAIR_BASE`` are formed offset by offset on the
vector unit, ``sum_d k_id k_(i-s)d exp(g_id - g_(i-s)d)`` for ``s`` = 1
.. ``_PAIR_BASE`` - 1 by a sublane roll, in float32. The running sums
themselves are one product with a triangle of ones inside the kernel
(the caller hands over ``log alpha`` as it is: ``(tokens, heads x Dk)``
float32 read once, and no ``g`` in HBM), and the state is kept
transposed (``Dv x Dk``), so that a channel's decay over the row
scales a lane and needs ``g_Q`` in one orientation only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = lax.Precision.HIGHEST

#: the kernel's name in the device's trace and in the scope table
KERNEL_NAME = "gated_delta_rule"

#: the block the triangular solve substitutes row by row; larger blocks
#: are merged from their halves. On the v5e 31 columns on the vector
#: unit cost less than the level of merges (two whole products) they
#: replace, 63 cost more (PERF.md section 6, PR 41)
_SOLVE_BASE = 32

#: the vector gate's kernel in the device's trace and in the scope table
KDA_KERNEL_NAME = "channel_gated_delta_rule"

#: key heads a grid step (with the value heads that read them): the
#: body is unrolled a head, and past two the kernel gains 1-3% for a
#: compile time that doubles with the heads (the same readings)
_KEY_HEADS = 2


def _dot(a, b):
    return jnp.dot(a, b, precision=_HIGHEST,
                   preferred_element_type=jnp.float32)


def _scores(a, b):
    """``a b^T``, operands as they come, float32 accumulation."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _spread_columns(packed):
    """``packed`` (b, C), C // b groups of ``b`` lanes -> b arrays (b,
    C): in array ``j`` every lane of a group holds the group's lane
    ``j``. A tree over the bits of ``j``, highest first: a level halves
    the lanes a value may still come from, by one rotation to each side
    and a select (2 b - 2 rotations in all, on the lanes' own unit)."""
    base, size = packed.shape
    lane = lax.broadcasted_iota(jnp.int32, packed.shape, 1)
    level = [packed]
    bit = base // 2
    while bit:
        high = (lane & bit) != 0
        level = [half for x in level for half in (
            # bit clear: the lanes that have it set read ``bit`` below
            jnp.where(high, pltpu.roll(x, bit, 1), x),
            jnp.where(high, x, pltpu.roll(x, size - bit, 1)))]
        bit //= 2
    return level


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
    (C, C), unit lower triangular. Written for the kernel's body: whole
    arrays in VMEM, the lanes the minor axis.

    The diagonal blocks of ``_SOLVE_BASE`` row by row, all of them at
    once in a packed form (b, C): row ``a`` holds row ``a`` of every
    block, a block's columns where they lie. Column ``j`` of the
    substitution takes ``lower[a, j] * row_j`` off every row ``a > j``:
    one multiply-add of the packed array, the column spread over its
    block's lanes. Then, with the inverses of the diagonal blocks of b
    in ``x`` (block-diagonal), the blocks of 2b: ``x - x (lower *
    under_b) x``, where ``under_b`` keeps each pair's lower-left block:
    whole ``C x C`` products and not products of the blocks."""
    size = lower.shape[0]
    base = min(size, _SOLVE_BASE)
    blocks = size // base
    lane = lax.broadcasted_iota(jnp.int32, (base, size), 1)
    packed = jnp.zeros((base, size), lower.dtype)
    for i in range(blocks):
        packed = packed + jnp.where(
            lane // base == i, lower[i * base:(i + 1) * base], 0.0)
    row = lax.broadcasted_iota(jnp.int32, (base, size), 0)
    solved = jnp.where(lane % base == row, 1.0, 0.0).astype(lower.dtype)
    for j, column in enumerate(_spread_columns(packed)[:-1]):
        solved = solved - column * solved[j:j + 1]
    at = lax.broadcasted_iota(jnp.int32, (size, size), 0) // base
    to = lax.broadcasted_iota(jnp.int32, (size, size), 1) // base
    x = jnp.where(at == to, jnp.concatenate([solved] * blocks, axis=0), 0.0)
    while base < size:
        under = (at == to + 1) & (at % 2 == 1)
        x = x - _dot(_dot(x, jnp.where(under, lower, 0.0)), x)
        at, to = at // 2, to // 2
        base *= 2
    return x


def _kernel(first_ref, q_ref, k_ref, v_ref, g_ref, b_ref, g_row_ref, z_ref,
            w_ref, o_ref, state_ref, *, per: int, dk: int, dv: int,
            eps: float, activation: str, state_dtype):
    """One row of one head group. ``q_ref``, ``k_ref`` (Q, heads *
    Dk) float32, as the convolution wrote them; ``v_ref`` (Q, heads *
    per * Dv) in the activations' dtype; ``g_ref``, ``b_ref`` (Q, value
    heads) the running sums and the steps, a token a sublane;
    ``g_row_ref`` (value heads, Q) the sums again, a token a lane;
    ``z_ref`` (Q, heads * per * Dv) float32 the output gate before its
    activation, ``w_ref`` (1, Dv) the head norm's weight; ``o_ref`` as
    ``v_ref``; ``state_ref`` (value heads, Dk, Dv) float32, carried."""
    f32 = jnp.float32
    qlen = q_ref.shape[0]
    act = v_ref.dtype

    @pl.when(first_ref[pl.program_id(1)] != 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    token = lax.broadcasted_iota(jnp.int32, (qlen, qlen), 0)
    other = lax.broadcasted_iota(jnp.int32, (qlen, qlen), 1)
    weight = w_ref[...]
    for head in range(k_ref.shape[1] // dk):
        q, k = _rounded_qk(q_ref, k_ref, slice(head * dk, (head + 1) * dk),
                           act)
        kk, qk = _scores(k, k), _scores(q, k)
        kf, qf = k.astype(f32), q.astype(f32)
        for j in range(head * per, (head + 1) * per):
            of_head = slice(j * dv, (j + 1) * dv)
            g = g_ref[:, j:j + 1]                            # (Q, 1)
            b = b_ref[:, j:j + 1]
            g_row = g_row_ref[j:j + 1, :]                    # (1, Q)
            end = g[qlen - 1:qlen]                           # (1, 1)
            decay = jnp.exp(jnp.where(token >= other, g - g_row, -jnp.inf))
            t = unit_lower_inverse(
                jnp.where(token > other, b * kk * decay, 0.0))
            u = _dot(t, b * v_ref[:, of_head].astype(f32))
            w = _dot(t, (b * jnp.exp(g)) * kf)
            state = state_ref[j]
            v_new = u - _dot(w, state)
            o = _dot(qf * jnp.exp(g), state) + _dot(qk * decay, v_new)
            o_ref[:, of_head] = _gated_norm(
                o, z_ref[:, of_head], weight, eps, activation) \
                .astype(o_ref.dtype)
            state = jnp.exp(jnp.broadcast_to(end, (1, dv))) * state \
                + lax.dot_general(
                    kf * jnp.exp(end - g), v_new, (((0,), (0,)), ((), ())),
                    precision=_HIGHEST, preferred_element_type=f32)
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


def _cost(rows: int, qlen: int, heads: int, dk: int, dv: int, operands,
          out) -> pl.CostEstimate:
    """What a call costs, for the compiler that schedules around it: it
    overlaps its own copies (the residual stream's prefetch in front of
    ``o``'s product, for one) with a custom call only as far as it
    knows how long the call runs. The ten float32 products a head and
    row that both kernels share - the solve's merged levels, ``U``,
    ``W``, the three against the state and ``qk v_new`` - at the six
    passes ``highest`` takes; the single-pass score products and the
    vector unit's work are left out, the exponentials one a pair of
    tokens."""
    levels = max(0, (qlen // _SOLVE_BASE).bit_length() - 1)
    products = 2 * levels * qlen ** 3 + qlen * qlen * (dk + 2 * dv) \
        + 3 * qlen * dk * dv
    return pl.CostEstimate(
        flops=2 * 6 * products * rows * heads,
        transcendentals=rows * heads * qlen * qlen,
        bytes_accessed=sum(x.size * x.dtype.itemsize
                           for x in operands + (out,)))


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

    def of_group(*block):
        return pl.BlockSpec((None, None) + block,
                            lambda i, r, _: (r, i, 0, 0))
    operands = (qk, v, g, b, g.transpose(0, 1, 3, 2), z.astype(f32),
                norm_weight.astype(f32)[None, :])
    out = jax.ShapeDtypeStruct((rows, qlen, hv * dv), v.dtype)
    return pl.pallas_call(
        functools.partial(_kernel, per=per, dk=dk, dv=dv, eps=eps,
                          activation=activation, state_dtype=state_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(groups, rows),
            in_specs=[_columns(qlen, heads * dk),
                      _columns(qlen, heads * dk, groups),
                      _columns(qlen, values * dv), of_group(qlen, values),
                      of_group(qlen, values), of_group(values, qlen),
                      _columns(qlen, values * dv),
                      pl.BlockSpec((1, dv), lambda i, r, _: (0, 0))],
            out_specs=_columns(qlen, values * dv),
            scratch_shapes=[pltpu.VMEM((values, dk, dv), f32)]),
        out_shape=out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=_cost(rows, qlen, hv, dk, dv, operands, out),
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


def _dot_nt(a, b):
    """``a b^T``, float32 operands at ``highest``."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           precision=_HIGHEST,
                           preferred_element_type=jnp.float32)


def channel_scores(qf, kf, g, score_dtype):
    """``sum_d x_id k_jd exp(g_id - g_jd)`` for ``x`` = ``k`` (``i > j``)
    and ``x`` = ``q`` (``i >= j``), zero elsewhere: -> two (Q, Q)
    float32. ``qf``, ``kf``, ``g`` (Q, Dk) float32, ``g`` the running
    sums of ``log alpha`` (non-increasing down the tokens). No exponent
    is over zero (the module's docstring). The levels' products take
    their operands in ``score_dtype`` (the activations') and accumulate
    in float32, as the scalar rule's two score products do."""
    qlen, dk = g.shape
    base = min(qlen, _PAIR_BASE)
    # float32 operands (the tests') at ``highest``
    product = _dot_nt if score_dtype == jnp.float32 else _scores
    token = lax.broadcasted_iota(jnp.int32, (qlen, qlen), 0)
    other = lax.broadcasted_iota(jnp.int32, (qlen, qlen), 1)
    kk = jnp.zeros((qlen, qlen), jnp.float32)
    qk = jnp.where(token == other, jnp.sum(qf * kf, -1, keepdims=True), 0.0)
    block = qlen // 2
    while block >= base:
        # every token's reference: its block of 2 B's second half's first
        ref = jnp.concatenate([
            jnp.broadcast_to(g[n:n + 1], (2 * block, dk))
            for n in range(block, qlen, 2 * block)], axis=0)
        late = jnp.exp(jnp.minimum(g - ref, 0.0))
        early = (kf * jnp.exp(jnp.minimum(ref - g, 0.0))) \
            .astype(score_dtype)
        owns = (token // block == other // block + 1) \
            & ((token // block) % 2 == 1)
        kk = kk + jnp.where(
            owns, product((kf * late).astype(score_dtype), early), 0.0)
        qk = qk + jnp.where(
            owns, product((qf * late).astype(score_dtype), early), 0.0)
        block //= 2
    inside = lax.broadcasted_iota(jnp.int32, (qlen, 1), 0) % base
    for s in range(1, base):
        # token i against token i - s of its own block of ``base``
        past = pltpu.roll(kf, s, 0) \
            * jnp.exp(jnp.minimum(g - pltpu.roll(g, s, 0), 0.0))
        here = (token - other == s) & (inside >= s)
        kk = kk + jnp.where(here, jnp.sum(kf * past, -1, keepdims=True), 0.0)
        qk = qk + jnp.where(here, jnp.sum(qf * past, -1, keepdims=True), 0.0)
    return kk, qk


def _kda_kernel(first_ref, q_ref, k_ref, v_ref, a_ref, b_ref, z_ref, w_ref,
                o_ref, state_ref, *, dk: int, dv: int, eps: float,
                activation: str, state_dtype):
    """One row of one head group. ``q_ref``, ``k_ref`` (Q, heads * Dk)
    float32, as the convolution wrote them; ``v_ref`` (Q, heads * Dv) in
    the activations' dtype; ``a_ref`` (Q, heads * Dk) float32 the ``log
    alpha`` of every channel, ``b_ref`` (Q, heads) the steps; ``z_ref``
    (Q, heads * Dv) float32 the output gate before its activation,
    ``w_ref`` (1, Dv) the head norm's weight; ``o_ref`` as ``v_ref``;
    ``state_ref`` (heads, Dv, Dk) float32, carried, a head's state
    transposed."""
    f32 = jnp.float32
    qlen = q_ref.shape[0]
    act = v_ref.dtype

    @pl.when(first_ref[pl.program_id(1)] != 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    token = lax.broadcasted_iota(jnp.int32, (qlen, qlen), 0)
    other = lax.broadcasted_iota(jnp.int32, (qlen, qlen), 1)
    ones = jnp.where(token >= other, 1.0, 0.0).astype(f32)
    weight = w_ref[...]
    for head in range(k_ref.shape[1] // dk):
        of_keys = slice(head * dk, (head + 1) * dk)
        of_values = slice(head * dv, (head + 1) * dv)
        qf, kf = (x.astype(f32)
                  for x in _rounded_qk(q_ref, k_ref, of_keys, act))
        g = _dot(ones, a_ref[:, of_keys])                      # (Q, Dk)
        b = b_ref[:, head:head + 1]                            # (Q, 1)
        end = g[qlen - 1:qlen]                                 # (1, Dk)
        kk, qk = channel_scores(qf, kf, g, act)
        t = unit_lower_inverse(b * kk)
        u = _dot(t, b * v_ref[:, of_values].astype(f32))
        w = _dot(t, (b * jnp.exp(g)) * kf)
        state = state_ref[head]                                # (Dv, Dk)
        v_new = u - _dot_nt(w, state)
        o = _dot_nt(qf * jnp.exp(g), state) + _dot(qk, v_new)
        o_ref[:, of_values] = _gated_norm(
            o, z_ref[:, of_values], weight, eps, activation) \
            .astype(o_ref.dtype)
        state = state * jnp.exp(end) + lax.dot_general(
            v_new, kf * jnp.exp(end - g), (((0,), (0,)), ((), ())),
            precision=_HIGHEST, preferred_element_type=f32)
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
    operands = (qk, v, log_alpha.astype(f32), b, z.astype(f32),
                norm_weight.astype(f32)[None, :])
    out = jax.ShapeDtypeStruct((rows, qlen, heads * dv), v.dtype)
    return pl.pallas_call(
        functools.partial(_kda_kernel, dk=dk, dv=dv, eps=eps,
                          activation=activation, state_dtype=state_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(groups, rows),
            in_specs=[_columns(qlen, step * dk),
                      _columns(qlen, step * dk, groups),
                      _columns(qlen, step * dv), _columns(qlen, step * dk),
                      pl.BlockSpec((None, None, qlen, step),
                                   lambda i, r, _: (r, i, 0, 0)),
                      _columns(qlen, step * dv),
                      pl.BlockSpec((1, dv), lambda i, r, _: (0, 0))],
            out_specs=_columns(qlen, step * dv),
            scratch_shapes=[pltpu.VMEM((step, dv, dk), f32)]),
        out_shape=out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=_cost(rows, qlen, heads, dk, dv, operands, out),
        interpret=interpret, name=KDA_KERNEL_NAME,
    )(row_first.astype(jnp.int32), qk, *operands)
