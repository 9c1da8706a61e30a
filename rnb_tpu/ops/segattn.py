"""Causal grouped-query attention over a packed pool of rows, confined
to requests, as one flash kernel over the whole pool. Multi-head
attention is the case of one query head a key-value head, and latent
attention (MLA) in its expanded form the case of values narrower than
queries and keys.

The pool holds ``rows`` of ``Q`` tokens; a request is a run of
consecutive rows and ``row_start[r]`` is the first row of row r's
request (a pad row is a request of its own). A query attends to the
keys of its own request at or before it: the causal triangle over the
pool's tokens, cut by a segment id a token (its request's first
token).

The kernel is JAX's Pallas splash attention in its multi-query form
(``jax.experimental.pallas.ops.tpu.splash_attention``), mapped over
the key-value heads: scores, running maximum and sum stay in VMEM in
float32. It walks a grid of (query block, key block) tiles and reads,
ahead of the data, a table that says which tiles run and which key
block each step holds. The table is built here from the segment table,
dispatch by dispatch, inside the program (``block_table``): a tile
runs iff it lies on or under the diagonal and its keys end after the
first token of the earliest request in its query block. It is data,
not a constant of the program: one program a row bucket, whatever the
dispatch packs. Inside a tile that runs, the causal mask and the
segment ids decide as before: the output is that of the pool's whole
triangle to the last bit. Off the TPU the same kernel runs in Pallas's
interpret mode.

The time of a dispatch follows the tiles it runs. On the v5e, 64 rows
of 128 tokens, 24 dispatches packed as the ``Batcher`` packs the token
cells' own prompts (6.5 requests a dispatch, 0.64 of the 36 tiles on
or under the diagonal run), the whole function, relayouts included (my
chip runs, PR 36): grouped queries, 32 / 2 heads of 128: 1.13 ms +
0.104 ms a tile: 2.45 ms (13 tiles, ten short requests) to 4.88 (36
tiles, one request fills the pool), mean 3.51, where every causal tile
took 4.76; latent attention expanded, 128 heads of 192 / 128: 11.1 ms
+ 0.61 a tile: 19.1 to 33.1, mean 25.2, for 33.5. Smaller tiles skip
more and lose more than that: mean ms at (queries a tile, keys a tile,
keys a step), grouped / latent: (1024, 1024, 512) 3.51 / 25.2; (512,
1024, 512) 3.63 / 26.2; (1024, 512, 512) 4.01 / 27.7; (512, 512, 512)
4.11 / 28.6 at 0.535 of the tiles; (256, 1024, 512) 4.36 / 32.2;
(1024, 1024, 1024) 3.86 / 26.7; (1024, 2048, 512) 3.85 / 27.7 at
0.715; (256, 256, 256) 9.07 / 57.7 (PR 36's first sweep); 2,048
queries a tile run out of VMEM at latent attention's widths. Both
forms agree, so one set of constants. (PR 28 read 4.6 ms for the whole
triangle, and 5.1 to 63.8 ms for a ``jax.numpy`` loop over the
distance between query row and key row.)

A layer whose queries read a *window* of keys has a kernel of its own,
``ops/banded.py``: a band is no triangle.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash, splash_attention_mask as masks)

_LANES = 128
#: queries a tile, keys a tile, keys a step of a tile's inner loop:
#: the fastest of those read on the chip for both callers' forms (the
#: module's text)
_BLOCK_Q, _BLOCK_KV, _BLOCK_COMPUTE = 1024, 1024, 512


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def block_table(first, block_q: int, block_kv: int):
    """The two tables splash reads ahead of the data, for a pool of
    ``len(first)`` query blocks: ``first[i]`` is the first token of the
    request that owns query block i's first token, the earliest key any
    query of the block may attend to.
    -> (``run``, ``fetch``, causal) with the tables int32 (query blocks,
    key blocks): ``run`` is 1 where the tile executes, ``fetch`` the key
    block a step holds; ``causal`` is the tiles on or under the
    diagonal, which a pool that one request fills runs all.

    Tile (i, j) runs iff key block j starts at or before block i's last
    query and ends after ``first[i]``: the blocks ``lo[i] .. hi[i]`` of
    a row, the diagonal's always among them, so every query meets its
    own key. A tile that runs fetches its own block (the kernel takes
    the keys' positions from ``fetch``). A step that does not moves no
    keys and no values beyond what runs: before ``lo[i]`` it names
    ``lo[i]``, past ``hi[i]`` the first block of the next row (of the
    next head's first row after the last), so that the one copy a row
    begins with is asked for while the diagonal's tile computes. The
    table is safe, not exact: a tile it lets run may hold no permitted
    pair, and the kernel's own causal and segment masks decide inside
    every tile."""
    nq = first.shape[0]
    hi = ((np.arange(nq, dtype=np.int32) + 1) * block_q - 1) // block_kv
    j = np.arange(hi[-1] + 1, dtype=np.int32)[None, :]
    hi = hi[:, None]
    lo = first.astype(jnp.int32)[:, None] // block_kv
    run = (j >= lo) & (j <= hi)
    fetch = jnp.where(j > hi, jnp.roll(lo, -1, axis=0), jnp.maximum(j, lo))
    return run.astype(jnp.int32), fetch, int((j <= hi).sum())


def _blocks(tokens: int):
    """(queries a tile, keys a tile) for a pool of ``tokens``."""
    whole = _round_up(tokens, _LANES)
    return min(_BLOCK_Q, whole), min(_BLOCK_KV, whole)


def pool_tokens(tokens: int) -> int:
    """The tokens of a pool as the kernel walks it: whole blocks of
    queries and of keys (the pad tokens are requests of their own)."""
    return _round_up(tokens, math.lcm(*_blocks(tokens)))


def heads_first(x, columns=None):
    """``x`` (tokens, ..., dim), a token's heads behind it -> (...,
    ``pool_tokens(tokens)``, columns): the form the kernel reads, by a
    pad (tokens that are requests of their own, zero columns up to
    ``columns``: by default dim's next whole lanes) and a transpose."""
    tokens, dim = x.shape[0], x.shape[-1]
    columns = _round_up(dim, _LANES) if columns is None else columns
    x = jnp.pad(x, ((0, pool_tokens(tokens) - tokens),)
                + ((0, 0),) * (x.ndim - 2) + ((0, columns - dim),))
    return jnp.moveaxis(x, 0, -2)


def heads_first_attention(q, k, v, row_start, qlen: int,
                          interpret: bool = False):
    """The kernel's own form: ``q`` (Hk, Hq // Hk, P, D), already
    scaled; ``k`` (Hk, P, D); ``v`` (Hk, P, Dv); P the
    ``pool_tokens`` of the ``len(row_start) * qlen`` the pool holds, D
    and Dv whole lanes (what lies past a head's own columns is zero in
    ``k`` and ``v``); ``row_start`` (rows,) int32.
    -> ((Hk, Hq // Hk, P, Dv) in q's dtype, int32 (2,): the tiles the
    kernel ran a head, and the tiles on or under the diagonal)."""
    per, padded = q.shape[1:3]
    tokens = row_start.shape[0] * qlen
    block_q, block_kv = _blocks(tokens)
    if padded != pool_tokens(tokens):
        raise ValueError("%d tokens laid out as %d, not %d"
                         % (tokens, padded, pool_tokens(tokens)))
    # a token's segment id is the first token of its request
    segment = jnp.concatenate([
        jnp.repeat(row_start.astype(jnp.int32) * qlen, qlen),
        jnp.arange(tokens, padded, dtype=jnp.int32)])
    # the static causal mask brings the mask function, the queries'
    # positions and the grid; which tiles of it run is this dispatch's
    kernel = splash.make_splash_mqa_single_device(
        masks.MultiHeadMask([masks.CausalMask((padded, padded))] * per),
        block_sizes=splash.BlockSizes(
            block_q=block_q, block_kv=block_kv,
            block_kv_compute=min(_BLOCK_COMPUTE, block_kv)),
        interpret=interpret)
    info = kernel.fwd_mask_info
    run, fetch, causal = block_table(segment[::block_q], block_q, block_kv)
    kernel = splash.SplashAttentionKernel(
        info._replace(
            block_mask=run[None].astype(info.block_mask.dtype),
            data_next=fetch[None].astype(info.data_next.dtype)),
        None, None, **kernel.kwargs)
    out = jax.vmap(kernel, in_axes=(0, 0, 0, None))(
        q, k, v, splash.SegmentIds(segment, segment))
    return out, jnp.stack([run.sum(), jnp.int32(causal)])


def packed_attention(q, k, v, row_start, interpret: bool = False):
    """``q`` (rows, Q, Hq, D), already scaled (``D ** -0.5``, or the
    family's own); ``k`` (rows, Q, Hk, D) and ``v`` (rows, Q, Hk, Dv),
    each kv head serving Hq // Hk query heads (Dv may differ from D:
    latent attention's 192 / 128); ``row_start`` (rows,) int32.
    -> ((rows, Q, Hq, Dv) in q's dtype, int32 (2,): the tiles the
    kernel ran a head, and the tiles on or under the diagonal).

    Lays the operands out as :func:`heads_first_attention` reads them
    (:func:`heads_first`), and its result back: the kernel wants heads
    first, whole blocks of tokens and whole lanes of D and Dv. A caller
    whose products write that form calls the kernel's own entry."""
    rows, qlen, hq, dim = q.shape
    hk, dim_v = k.shape[2], v.shape[3]
    tokens = rows * qlen
    out, tiles = heads_first_attention(
        heads_first(q.reshape(tokens, hk, hq // hk, dim)),
        heads_first(k.reshape(tokens, hk, dim)),
        heads_first(v.reshape(tokens, hk, dim_v)), row_start, qlen,
        interpret)
    out = jnp.moveaxis(out, -2, 0)[:tokens, ..., :dim_v]
    return out.reshape(rows, qlen, hq, dim_v), tiles
