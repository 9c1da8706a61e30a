"""Block-selected sparse attention over a packed pool of rows (InfLLM-V2,
arXiv:2509.24663): every query chooses ``topk`` blocks of ``block_size``
keys of its own request and attends, causally, to the keys of those
blocks only. Requests shorter than ``dense_len`` attend to all their
keys; both kinds share one pool, one selection and one kernel.

The pool holds ``rows`` of ``Q`` tokens; a request is a run of
consecutive rows (``row_start[r]``: the first row of row r's request; a
pad row is a request of its own) and ``Q`` is a multiple of
``block_size``, so a request's blocks are whole blocks of the pool.

**Selection** (:func:`select_blocks`, float32 statistics). Compressed
keys are the means of ``kernel_size`` consecutive keys every
``kernel_stride``; a query sees the whole windows of its request that
end at or before it. A head's scores over them go through a softmax,
the ``Hq // Hk`` heads that read one key-value head are summed, and a
block's score is the best of the windows that overlap it. The request's
first ``init_blocks`` blocks and the blocks that hold the last
``window_size`` keys are chosen whatever their score; the others are
the best-scoring among the blocks at or before the query, ``topk`` in
all (a tie goes to the lower block, as ``lax.top_k`` orders; the
ranks are counted, not sorted). Nothing
is approximated: every query gets exactly its own blocks, as a mask
``(tokens, Hk, pool blocks)``.

**Attention** (:func:`masked_attention`) is one Pallas flash kernel,
``block_sparse_attention``: a tile of queries times the query heads of
one key-value head against a tile of keys, scores and running maximum
and sum in VMEM in float32. The block mask of the query tile is widened
to keys on the matrix unit (mask x a 0/1 matrix that repeats a block's
column ``block_size`` times), tiles in which no query of the tile chose
any block (other requests, the future) are skipped from a table in
scalar memory. It does not skip inside a tile: under seeded random
weights neighbouring queries choose nearly independent blocks and the
union over a tile of 128 queries is nearly every causal block (PERF.md
section 6, PR 35), so the kernel's time is that of dense causal
attention over each request.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Mapping

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: queries and keys a tile of the kernel (the query tile times the
#: heads of a group is the matrix's rows: 2,048 at 16 heads)
_TILE_Q, _TILE_K = 128, 512
#: queries a step of the selection: its scores are (step, Hq, windows)
_SELECT_STEP = 1024
_MASKED = -1e30
KERNEL_NAME = "block_sparse_attention"


@dataclasses.dataclass(frozen=True)
class SparseConfig:
    """The sizes of the selection, under the names of the family's
    published ``sparse_config``."""

    kernel_size: int
    kernel_stride: int
    block_size: int
    init_blocks: int
    window_size: int
    topk: int
    dense_len: int

    @staticmethod
    def from_mapping(config: Mapping) -> "SparseConfig":
        out = SparseConfig(**{f.name: int(config[f.name])
                              for f in dataclasses.fields(SparseConfig)})
        if out.block_size % out.kernel_stride \
                or out.kernel_size % out.kernel_stride \
                or out.kernel_size > out.block_size:
            raise ValueError("kernel_stride divides kernel_size and "
                             "block_size, and a window is no longer than "
                             "a block: %r" % (out,))
        if out.init_blocks + out.window_size // out.block_size + 1 \
                >= out.topk:
            raise ValueError("the forced blocks (initial and local) fill "
                             "topk: nothing is left to choose: %r" % (out,))
        return out


def _token_table(row_start, row_tokens, qlen: int):
    """Per token of the pool: (the first token of its request, the
    request's valid tokens, whether it is a valid token)."""
    first_row = row_start.astype(jnp.int32)
    # a request's valid tokens: the sum over its rows
    same = first_row[:, None] == first_row[None, :]
    length = jnp.sum(jnp.where(same, row_tokens[None, :], 0), axis=1)
    start = jnp.repeat(first_row * qlen, qlen)
    valid = (jnp.arange(qlen)[None, :] < row_tokens[:, None]).reshape(-1)
    return start, jnp.repeat(length.astype(jnp.int32), qlen), valid


def compress_keys(k, sparse: SparseConfig):
    """``k`` (T, Hk, D) -> (T // kernel_stride, Hk, D) in k's dtype:
    window w is the mean of the ``kernel_size`` keys from w x
    ``kernel_stride`` on (the pool's last windows, which reach past its
    end, are seen by no query)."""
    tokens, groups, dim = k.shape
    stride, size = sparse.kernel_stride, sparse.kernel_size
    # sums of stride keys, then size // stride of those, shifted
    part = k.astype(jnp.float32) \
        .reshape(tokens // stride, stride, groups, dim).sum(axis=1)
    total = part
    for shift in range(1, size // stride):
        total = total + jnp.pad(part[shift:],
                                ((0, shift), (0, 0), (0, 0)))
    return (total / size).astype(k.dtype)


def block_scores(q, compressed, start, sparse: SparseConfig, lo):
    """Float32 (count, Hk, pool blocks): the score each of the queries
    from ``lo`` on gives each block (0 for a block none of whose windows
    it sees). ``q`` (count, Hk, per, D) scaled, ``compressed`` (W, Hk,
    D), ``start`` (T,) the first token of each token's request."""
    count, groups = q.shape[:2]
    stride, size = sparse.kernel_stride, sparse.kernel_size
    windows = compressed.shape[0]
    win_first = jnp.arange(windows, dtype=jnp.int32) * stride
    at = lo + jnp.arange(count, dtype=jnp.int32)
    scores = jnp.einsum("tghd,wgd->tghw", q, compressed,
                        preferred_element_type=jnp.float32)
    seen = (lax.dynamic_slice_in_dim(start, lo, count)[:, None]
            == start[::stride][None, :]) \
        & (win_first[None, :] + size - 1 <= at[:, None])   # (count, W)
    scores = jnp.where(seen[:, None, None, :], scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - jnp.where(jnp.isfinite(top), top, 0.0))
    share = e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)
    summed = share.sum(axis=2)                             # (count, Hk, W)
    # a block's score: the best of its own windows and of the windows
    # of the block before that reach into it
    per_block = sparse.block_size // stride
    reach = size // stride - 1
    by_block = summed.reshape(count, groups, windows // per_block,
                              per_block)
    own = by_block.max(-1)
    if reach:
        before = by_block[..., per_block - reach:].max(-1)
        own = jnp.maximum(own, jnp.pad(
            before[..., :-1], ((0, 0), (0, 0), (1, 0))))
    return own


def choose(scores, start, length, sparse: SparseConfig, lo):
    """Bool (count, Hk, pool blocks): the blocks the queries from ``lo``
    on attend to, from their block scores (count, Hk, pool blocks)."""
    size = sparse.block_size
    count, _, blocks = scores.shape
    at = lo + jnp.arange(count, dtype=jnp.int32)
    mine = lax.dynamic_slice_in_dim(start, lo, count)
    block = jnp.arange(blocks, dtype=jnp.int32)
    block_start = start[::size]
    allowed = (block_start[None, :] == mine[:, None]) \
        & (block[None, :] <= at[:, None] // size)          # (count, B)
    local = jnp.maximum(at - (sparse.window_size - 1), mine) // size
    forced = (block[None, :] < mine[:, None] // size + sparse.init_blocks) \
        | (block[None, :] >= local[:, None])
    dense = lax.dynamic_slice_in_dim(length, lo, count) < sparse.dense_len
    ranked = jnp.where(forced[:, None, :], jnp.inf, scores)
    ranked = jnp.where(allowed[:, None, :], ranked, -jnp.inf)
    # a block's rank among the query's: how many come before it (a tie
    # goes to the lower block, as ``lax.top_k`` orders). Counted, not
    # sorted: the sort of 256 scores a (query, head) was 19% of the
    # device's time on the v5e (PR 35, my chip runs)
    ahead = (ranked[..., None, :] > ranked[..., :, None]) \
        | ((ranked[..., None, :] == ranked[..., :, None])
           & (block[None, :] < block[:, None]))
    chosen = ahead.sum(-1) < sparse.topk
    chosen = jnp.where(dense[:, None, None], True, chosen)
    return chosen & allowed[:, None, :]


def select_blocks(q, k, start, length, sparse: SparseConfig):
    """Bool (T, Hk, pool blocks). ``q`` (T, Hk, per, D) scaled; ``k``
    (T, Hk, D)."""
    tokens = q.shape[0]
    step = min(_SELECT_STEP, tokens)
    if tokens % step:
        raise ValueError("%d tokens are no whole steps of %d"
                         % (tokens, step))

    compressed = compress_keys(k, sparse)

    def one(lo):
        scores = block_scores(lax.dynamic_slice_in_dim(q, lo, step),
                              compressed, start, sparse, lo)
        return choose(scores, start, length, sparse, lo)
    chosen = lax.map(one, jnp.arange(0, tokens, step, dtype=jnp.int32))
    return chosen.reshape((tokens,) + chosen.shape[2:])


def _kernel(any_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref,
            acc_ref, *, per: int, block_size: int):
    g, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    steps = pl.num_programs(2)
    tile_q = mask_ref.shape[1]
    tile_k = k_ref.shape[1]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(any_ref[(g * pl.num_programs(1) + i) * steps + j] != 0)
    def _():
        q = q_ref[0, 0]                                    # (per * tq, D)
        s = lax.dot_general(q, k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        # the tile's block mask widened to keys on the matrix unit
        blocks = mask_ref.shape[2]
        widen = (lax.broadcasted_iota(jnp.int32, (blocks, tile_k), 0)
                 == j * (tile_k // block_size)
                 + lax.broadcasted_iota(jnp.int32, (blocks, tile_k), 1)
                 // block_size).astype(mask_ref.dtype)
        chosen = jnp.dot(mask_ref[0], widen,
                         preferred_element_type=jnp.float32)
        q_at = i * tile_q + lax.broadcasted_iota(
            jnp.int32, (tile_q, tile_k), 0)
        k_at = j * tile_k + lax.broadcasted_iota(
            jnp.int32, (tile_q, tile_k), 1)
        chosen = jnp.where(k_at <= q_at, chosen, 0.0)
        # rows are (head, query): the same mask for every head
        s = jnp.where(jnp.concatenate([chosen] * per, axis=0) > 0.5, s,
                      _MASKED)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # a row that has met no chosen key yet holds sums of exp(0);
        # the first chosen key's maximum wipes them (alpha = 0)
        p = jnp.exp(s - m_next)
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_next

    @pl.when(j == steps - 1)
    def _():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def masked_attention(q, k, v, chosen, block_size: int,
                     interpret: bool = False):
    """``q`` (T, Hk, per, D) scaled; ``k``, ``v`` (T, Hk, D); ``chosen``
    (T, Hk, pool blocks) bool, a query's own block among them. -> (T,
    Hk, per, D) in q's dtype: causal softmax attention of each query
    over the keys of its chosen blocks."""
    tokens, groups, per, dim = q.shape
    blocks = chosen.shape[-1]
    tile_q, tile_k = min(_TILE_Q, tokens), min(_TILE_K, tokens)
    if tokens % tile_q or tokens % tile_k or tile_k % block_size:
        raise ValueError("%d tokens in tiles of %d x %d, blocks of %d"
                         % (tokens, tile_q, tile_k, block_size))
    nq, nk = tokens // tile_q, tokens // tile_k
    rows = per * tile_q
    # a query tile as one matrix, rows (head, query)
    q_tiles = q.reshape(nq, tile_q, groups, per, dim) \
        .transpose(2, 0, 3, 1, 4).reshape(groups, nq, rows, dim)
    heads_first = chosen.transpose(1, 0, 2)                 # (Hk, T, B)
    any_chosen = heads_first.reshape(
        groups, nq, tile_q, nk, tile_k // block_size).any(axis=(2, 4))
    out = pl.pallas_call(
        functools.partial(_kernel, per=per, block_size=block_size),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(groups, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, rows, dim),
                             lambda g, i, j, _: (g, i, 0, 0)),
                pl.BlockSpec((1, tile_k, dim), lambda g, i, j, _: (g, j, 0)),
                pl.BlockSpec((1, tile_k, dim), lambda g, i, j, _: (g, j, 0)),
                pl.BlockSpec((1, tile_q, blocks),
                             lambda g, i, j, _: (g, i, 0))],
            out_specs=pl.BlockSpec((1, 1, rows, dim),
                                   lambda g, i, j, _: (g, i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, dim), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, nq, rows, dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret, name=KERNEL_NAME,
    )(any_chosen.reshape(-1).astype(jnp.int32), q_tiles,
      k.transpose(1, 0, 2), v.transpose(1, 0, 2),
      heads_first.astype(q.dtype))
    return out.reshape(groups, nq, per, tile_q, dim) \
        .transpose(1, 3, 0, 2, 4).reshape(tokens, groups, per, dim)


def sparse_attention(q, k, v, row_start, row_tokens, sparse: SparseConfig,
                     interpret: bool = False):
    """``q`` (rows, Q, Hq, D), already scaled; ``k``, ``v`` (rows, Q,
    Hk, D), each serving Hq // Hk query heads; ``row_start``,
    ``row_tokens`` (rows,) int32.

    -> (out (rows, Q, Hq, D) in q's dtype; the chosen blocks (tokens,
    Hk, pool blocks) bool; int32 (4,) counted over (valid query, key-
    value head) pairs: the pairs, those of requests of ``dense_len``
    tokens or more, the causal keys those could read, the keys of the
    blocks they chose)."""
    rows, qlen, hq, dim = q.shape
    hk = k.shape[2]
    tokens = rows * qlen
    if qlen % sparse.block_size:
        raise ValueError("a row of %d tokens is no whole blocks of %d"
                         % (qlen, sparse.block_size))
    start, length, valid = _token_table(row_start, row_tokens, qlen)
    q = q.reshape(tokens, hk, hq // hk, dim)
    k, v = k.reshape(tokens, hk, dim), v.reshape(tokens, hk, dim)
    with jax.named_scope("select"):
        chosen = select_blocks(q, k, start, length, sparse)
    out = masked_attention(q, k, v, chosen, sparse.block_size, interpret)
    at = jnp.arange(tokens, dtype=jnp.int32) - start
    selecting = valid & (length >= sparse.dense_len)
    # every chosen block but the query's own is whole
    keys = (chosen.sum(-1) - 1) * sparse.block_size \
        + (at % sparse.block_size)[:, None] + 1
    counts = jnp.stack([
        valid.sum() * hk, selecting.sum() * hk,
        jnp.where(selecting, at + 1, 0).sum() * hk,
        jnp.where(selecting[:, None], keys, 0).sum()]).astype(jnp.int32)
    return out.reshape(rows, qlen, hq, dim), chosen, counts
