"""The Mamba-2 state-space scan over a packed pool of rows, in its
blocked (SSD) form (lightning linear attention is a case of it), and the causal depthwise convolution in front of
it — both with the state reset where a request's first row starts.

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
= None``, one group a head. Its decays do not depend on the row, so
they are computed once a head and not once a row.

The blocked form computes each row's own tokens as one masked
``Q x Q`` product (the decays between two tokens of a row are
``exp`` of a difference of cumulative sums), each row's end state as
one product, carries states across the rows of a request by one
``rows x rows`` matrix of decays per head, and adds what the incoming
state gives each token. Decays, cumulative sums and states are
float32; the products that read or build a state run at ``highest``
precision, so that a state is never rounded to bfloat16 on its way
through the matrix unit. The within-row products take their inputs
in the activations' dtype and accumulate in float32.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

_HIGHEST = lax.Precision.HIGHEST


def segment_conv1d(x, weight, bias, row_first):
    """Causal depthwise convolution along the packed token axis.

    ``x`` (rows, Q, C); ``weight`` (C, K) with ``weight[:, K-1]`` on
    the current token (the cross-correlation a ``Conv1d`` with left
    padding K-1 computes); ``bias`` (C,). The K-1 tokens of history
    are zero at the start of every request. -> float32 (rows, Q, C)."""
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


def ssd_scan(xs, dt, a, b, c, d, row_first, state_dtype=jnp.float32):
    """The scan of one block over a packed pool.

    ``xs`` (rows, Q, H, P); ``dt`` (rows, Q, H) float32, after its
    softplus, or None for unit steps; ``a`` (H,) float32, negative;
    ``b``, ``c`` (rows, Q, G, N), head h reading group h // (H // G);
    ``d`` (H,) float32 or None for no skip term; ``row_first`` (rows,)
    bool. -> float32 (rows, Q, H, P).

    ``state_dtype`` is the precision the states are carried in
    between rows: float32 in the program; the lower-precision control
    of the tests passes bfloat16."""
    rows, q, heads, p = xs.shape
    groups = b.shape[2]
    per = heads // groups
    xg = xs.reshape(rows, q, groups, per, p)
    # log decay a token: with unit steps the same in every row, and
    # everything made of it keeps a leading axis of one
    la = jnp.broadcast_to(a, (1, q, heads)) if dt is None else dt * a
    cs = jnp.cumsum(la, axis=1)                    # (rows | 1, Q, H)
    lead = cs.shape[0]
    csg = cs.reshape(lead, q, groups, per)

    # a row's own tokens: (C_i . B_j) exp(cs_i - cs_j) dt_j, j <= i;
    # the two token axes are the minor ones, one Q x Q tile a head
    cb = jnp.einsum("rign,rjgn->rgij", c, b,
                    preferred_element_type=jnp.float32)
    csh = csg.transpose(0, 2, 3, 1)                # (rows | 1, G, per, Q)
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

    # each row's end state from its own tokens
    xw = xg.astype(jnp.float32) * to_end[..., None]
    state = jnp.einsum("rjghp,rjgn->rghpn", xw, b.astype(jnp.float32),
                       precision=_HIGHEST)         # (rows, G, per, P, N)

    # states carried across the rows of a request: row r receives
    # sum over earlier rows q of its request of
    # exp(sum of the row decays strictly between) * state_q
    row_decay = jnp.broadcast_to(cs[:, -1, :], (rows, heads))
    cum = jnp.cumsum(row_decay, axis=0)
    seg = jnp.cumsum(row_first.astype(jnp.int32))
    idx = jnp.arange(rows)
    carry_ok = (idx[:, None] > idx[None, :]) \
        & (seg[:, None] == seg[None, :])           # (rows r, rows q)
    log_m = (cum - row_decay)[:, None, :] - cum[None, :, :]
    m = jnp.exp(jnp.where(carry_ok[:, :, None], log_m, -jnp.inf))
    state = state.astype(state_dtype).astype(jnp.float32)
    incoming = jnp.einsum(
        "rqgh,qghpn->rghpn", m.reshape(rows, rows, groups, per), state,
        precision=_HIGHEST)
    incoming = incoming.astype(state_dtype).astype(jnp.float32)

    # what the incoming state gives each token of the row
    y_in = jnp.einsum("rign,rghpn->righp", c.astype(jnp.float32),
                      incoming, precision=_HIGHEST)
    y = y + y_in * jnp.exp(csg)[..., None]
    if d is not None:
        y = y + xg.astype(jnp.float32) \
            * d.reshape(groups, per)[None, None, :, :, None]
    return y.reshape(rows, q, heads, p)
