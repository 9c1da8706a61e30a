"""Causal grouped-query attention over a packed pool of rows, confined
to requests, as one flash kernel over the whole pool. Multi-head
attention is the case of one query head a key-value head, and latent
attention (MLA) in its expanded form the case of values narrower than
queries and keys.

The pool holds ``rows`` of ``Q`` tokens; a request is a run of
consecutive rows and ``row_start[r]`` is the first row of row r's
request (a pad row is a request of its own). A query attends to the
keys of its own request at or before it: the causal triangle over the
pool's tokens, cut by a segment id a token (its request's first row).

The kernel is JAX's Pallas splash attention in its multi-query form
(``jax.experimental.pallas.ops.tpu.splash_attention``), mapped over
the key-value heads: scores, running maximum and sum stay in VMEM in
float32, blocks above the diagonal are skipped, and the time of a
dispatch does not depend on what it packs: 4.6 ms at 64 rows on the
v5e (PR 28, my chip runs), where a ``jax.numpy`` loop over the
distance between query row and key row, each band's scores through
HBM, took 5.1 ms with requests of 4 rows and 63.8 ms with one of 64.
Off the TPU the same kernel runs in Pallas's interpret mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash, splash_attention_mask as masks)

_LANES = 128
#: query block, key block, keys a step of the inner loop: read on the
#: v5e at 8,192 tokens (PR 28): (1024, 1024, 512) 4.6 ms, (512, 512,
#: 512) 5.7, (1024, 1024, 1024) 5.1, (256, 512, 256) 10.1; 2,048 by
#: 2,048 runs out of VMEM. The fastest at 2,048 to 6,144 tokens too
_BLOCK, _BLOCK_COMPUTE = 1024, 512


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def packed_attention(q, k, v, row_start, interpret: bool = False):
    """``q`` (rows, Q, Hq, D), already scaled (``D ** -0.5``, or the
    family's own); ``k`` (rows, Q, Hk, D) and ``v`` (rows, Q, Hk, Dv),
    each kv head serving Hq // Hk query heads (Dv may differ from D:
    latent attention's 192 / 128); ``row_start`` (rows,) int32.
    -> (rows, Q, Hq, Dv) in q's dtype.

    The kernel wants whole blocks of tokens and whole lanes of D and
    Dv: a pool or a head narrower than that (the tests' sizes, a
    192-wide q and k) is padded with tokens that are requests of their
    own and zero columns."""
    rows, qlen, hq, _ = q.shape
    hk, dim_v = k.shape[2], v.shape[3]
    per = hq // hk
    tokens = rows * qlen
    block = min(_BLOCK, _round_up(tokens, _LANES))
    padded = _round_up(tokens, block)

    def heads_first(x, heads):
        dim = x.shape[-1]
        x = x.reshape((tokens,) + heads + (dim,))
        x = jnp.pad(x, ((0, padded - tokens),) + ((0, 0),) * len(heads)
                    + ((0, _round_up(dim, _LANES) - dim),))
        return jnp.moveaxis(x, 0, -2)

    segment = jnp.concatenate([
        jnp.repeat(row_start.astype(jnp.int32), qlen),
        rows + jnp.arange(padded - tokens, dtype=jnp.int32)])
    kernel = splash.make_splash_mqa_single_device(
        masks.MultiHeadMask([masks.CausalMask((padded, padded))] * per),
        block_sizes=splash.BlockSizes(
            block_q=block, block_kv=block,
            block_kv_compute=min(_BLOCK_COMPUTE, block)),
        interpret=interpret)
    out = jax.vmap(kernel, in_axes=(0, 0, 0, None))(
        heads_first(q, (hk, per)), heads_first(k, (hk,)),
        heads_first(v, (hk,)), splash.SegmentIds(segment, segment))
    out = jnp.moveaxis(out, -2, 0)[:tokens, ..., :dim_v]
    return out.reshape(rows, qlen, hq, dim_v)
