"""The gated delta rule over a packed pool of rows, in its blocked (WY /
UT) form, with the state reset where a request's first row starts.

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

Inside a row the rule is blocked as the paper blocks it. With ``g`` the
running sum of ``log alpha`` inside the row and ``L`` the strictly lower
triangle of ``beta_i (k_i . k_j) exp(g_i - g_j)``, the tokens' effective
updates are one unit-triangular system, ``T = (I + L)^-1``::

    U = T (beta v)            what each token writes, before the
    W = T (beta exp(g) k)     incoming state's part is taken off
    v_new = U - W S_in
    o     = (exp(g) q) S_in + tril((q . k) exp(g_i - g_j)) v_new
    S_out = exp(g_Q) S_in + (exp(g_Q - g) k)^T v_new

``T`` is computed by forward substitution (:func:`unit_lower_inverse`):
row by row inside diagonal blocks of 16, and block by block above them
(the inverse of ``[[A, 0], [B, D]]`` is ``[[A^-1, 0], [-D^-1 B A^-1,
D^-1]]``: a level of the doubling is two batched products). It is
exact, and not the Neumann series, whose terms grow where neighbouring
keys are alike.

Across rows a row maps its incoming state affinely, ``S_out = M S_in +
B`` with ``M = exp(g_Q) I - (exp(g_Q - g) k)^T W`` and ``B = (exp(g_Q -
g) k)^T U``, both computed for every row at once; what is sequential is
one ``lax.scan`` over the rows that applies them (one batched ``Dk x
Dk`` by ``Dk x Dv`` product a step, the state zeroed where
``row_first``) and hands every row its incoming state; ``v_new`` and
``o`` are again computed for all rows at once.

Decays, steps, ``T`` and states are float32; every product that reads
or builds ``T`` or a state runs at ``highest`` precision, so that none
is rounded to bfloat16 on its way through the matrix unit: seventeen
batched 128 x 128 products a layer, 0.9 ms each on the v5e (``high``
read the same time: my chip runs, PR 39). The two
score products (``k . k``, ``q . k``) take their inputs in the
activations' dtype and accumulate in float32.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

_HIGHEST = lax.Precision.HIGHEST


#: the block the triangular solve substitutes row by row; larger blocks
#: are merged from their halves
_SOLVE_BASE = 16


def _substitute(lower, size: int):
    """``(I + lower)^-1`` by forward substitution, unrolled: row i of
    the inverse is ``e_i - sum_{j<i} lower[i, j] row_j``. ``lower`` (c *
    c, B): entry (i, j) is row ``i * c + j``, the batch on the minor
    axis, so that every term is a multiply-add of (c, B) arrays over
    whole lanes; c small. -> (c, c, B).

    (With the batch in front, ``lower[:, i, j]`` lowered to 120 gathers
    a layer, 28 ms; as rank-1 updates of a (c, c, B) array the compiler
    laid the two c's innermost and every step moved the array padded
    eightfold, 17 ms: my chip runs, PR 39.)"""
    eye = jnp.eye(size, dtype=lower.dtype)
    rows = []
    for i in range(size):
        row = jnp.broadcast_to(eye[i][:, None], (size, lower.shape[1]))
        for j in range(i):
            at = i * size + j
            row = row - lower[at:at + 1, :] * rows[j]
        rows.append(row)
    return jnp.stack(rows)


def unit_lower_inverse(lower):
    """``(I + lower)^-1`` for ``lower`` (B, C, C) float32, strictly
    lower triangular (what lies on or over the diagonal is not read);
    C a power of two. -> (B, C, C), unit lower triangular.

    The diagonal blocks of ``_SOLVE_BASE`` row by row; then, with the
    inverses of the diagonal blocks of b in ``x`` (block-diagonal), the
    blocks of 2b: ``x - x (lower * under_b) x``, where ``under_b`` keeps
    each pair's lower-left block. Whole ``C x C`` products and not
    products of the blocks: batched products of 32 and 64 columns ran
    at a fortieth of the matrix unit's rate on the v5e (my chip runs,
    PR 39: 45 ms a level a layer, where a whole product takes 0.9)."""
    batch, size = lower.shape[:2]
    base = min(size, _SOLVE_BASE)
    blocks = size // base
    diagonal = jnp.stack([
        lower[:, i * base:(i + 1) * base, i * base:(i + 1) * base]
        for i in range(blocks)])                     # (blocks, B, b, b)
    solved = _substitute(diagonal.transpose(2, 3, 0, 1).reshape(
        base * base, blocks * batch), base)
    solved = solved.reshape(base, base, blocks, batch).transpose(2, 3, 0, 1)
    # block-diagonal: block i's rows, zeros on both sides of it
    x = jnp.concatenate([
        jnp.pad(solved[i], ((0, 0), (0, 0),
                            (i * base, size - (i + 1) * base)))
        for i in range(blocks)], axis=1)
    at = jnp.arange(size)
    while base < size:
        under = (at[:, None] // base == at[None, :] // base + 1) \
            & (at[:, None] // base % 2 == 1)
        x = x - jnp.matmul(
            jnp.matmul(x, jnp.where(under, lower, 0.0), precision=_HIGHEST),
            x, precision=_HIGHEST)
        base *= 2
    return x


def gated_delta_rule(q, k, v, log_alpha, beta, row_first,
                     state_dtype=jnp.float32):
    """The rule of one layer over a packed pool.

    ``q``, ``k`` (rows, Q, Hk, Dk), as the rule reads them (normalised,
    ``q`` scaled); ``v`` (rows, Q, Hv, Dv), value head h reading key
    head h // (Hv // Hk); ``log_alpha`` (rows, Q, Hv) float32, <= 0;
    ``beta`` (rows, Q, Hv) float32; ``row_first`` (rows,) bool; Q a
    power of two. -> float32 (rows, Q, Hv, Dv).

    ``state_dtype`` is the precision the states are carried in between
    rows: float32 in the program; the lower-precision control passes
    bfloat16."""
    rows, qlen, hk, dk = k.shape
    hv, dv = v.shape[2:]
    per = hv // hk
    f32 = jnp.float32
    # heads first, a head's tokens and columns the two minor axes
    qh = q.transpose(0, 2, 1, 3)                     # (rows, Hk, Q, Dk)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.reshape(rows, qlen, hk, per, dv).transpose(0, 2, 3, 1, 4)

    def heads_first(x):                              # (rows, Hk, per, Q)
        return x.astype(f32).reshape(rows, qlen, hk, per) \
            .transpose(0, 2, 3, 1)
    g = jnp.cumsum(heads_first(log_alpha), axis=-1)
    b = heads_first(beta)
    on_or_under = jnp.tril(jnp.ones((qlen, qlen), bool))
    decay = jnp.exp(jnp.where(on_or_under,
                              g[..., :, None] - g[..., None, :], -jnp.inf))
    kk = jnp.einsum("rhid,rhjd->rhij", kh, kh, preferred_element_type=f32)
    qk = jnp.einsum("rhid,rhjd->rhij", qh, kh, preferred_element_type=f32)
    under = jnp.tril(jnp.ones((qlen, qlen), bool), -1)
    lower = jnp.where(under, b[..., :, None] * kk[:, :, None] * decay, 0.0)
    t = unit_lower_inverse(lower.reshape(-1, qlen, qlen)) \
        .reshape(lower.shape)

    kf = kh.astype(f32)[:, :, None]                  # (rows, Hk, 1, Q, Dk)
    u = jnp.matmul(t, b[..., None] * vh.astype(f32), precision=_HIGHEST)
    w = jnp.matmul(t, (b * jnp.exp(g))[..., None] * kf,
                   precision=_HIGHEST)               # (rows, Hk, per, Q, Dk)
    to_end = kf * jnp.exp(g[..., -1:] - g)[..., None]
    end = jnp.exp(g[..., -1])                        # (rows, Hk, per)
    carry_m = end[..., None, None] * jnp.eye(dk, dtype=f32) - jnp.einsum(
        "rhpik,rhpil->rhpkl", to_end, w, precision=_HIGHEST)
    carry_b = jnp.einsum("rhpik,rhpiv->rhpkv", to_end, u,
                         precision=_HIGHEST)

    def through(x):
        # reduce_precision and not a pair of conversions: the v5e's
        # compiler drops the pair and keeps the excess precision (the
        # arm then read the float32 logits bit for bit: my chip run,
        # PR 39)
        if state_dtype == f32:
            return x
        info = jnp.finfo(state_dtype)
        return lax.reduce_precision(x, info.nexp, info.nmant)

    def step(state, row):
        first, m, add = row
        state = jnp.where(first, 0.0, state)
        return through(jnp.matmul(m, state, precision=_HIGHEST) + add), \
            state
    _, incoming = lax.scan(step, jnp.zeros((hk, per, dk, dv), f32),
                           (row_first, carry_m, carry_b))

    v_new = u - jnp.matmul(w, incoming, precision=_HIGHEST)
    out = jnp.matmul(qh.astype(f32)[:, :, None] * jnp.exp(g)[..., None],
                     incoming, precision=_HIGHEST) \
        + jnp.matmul(qk[:, :, None] * decay, v_new, precision=_HIGHEST)
    # (rows, Hk, per, Q, Dv) -> (rows, Q, Hv, Dv)
    return out.transpose(0, 3, 1, 2, 4).reshape(rows, qlen, hv, dv)
