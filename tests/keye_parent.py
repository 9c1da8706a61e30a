"""Keye-VL's attention as the tree ran it from PR 46 to PR 53, kept for
the tests that hold PR 54's kernel to it bit for bit and for
``scripts/indexed_sweep.py``'s timings beside it: the mixer's passes over
q in front of the kernel (head norm, rotary, scale, rounding, the copy
into (key-value head, query tile, (head, query), D)) and the flash
kernel of one key-value head a grid step, which built its mask once a
key-value head and laid it under itself once a query head. And, at the
end, the thresholds as the tree ran them from PR 46 to PR 55
(:func:`thresholds`: 32 queries a step, a ``lax.cond`` and a lane
reduction a chunk of 2,048 keys from key 0 to the diagonal, 34 counts),
for the tests that hold PR 56's walk to them bit for bit and for the
sweep's timings beside it. Nothing in ``rnb_tpu`` imports this."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rnb_tpu.models.keye_vl2.network import rms_norm
from rnb_tpu.ops import rope
from rnb_tpu.ops.indexed import (LOWEST, _MASKED, _VMEM_LIMIT, _tile,
                                 attention_tiles)

KERNEL = "indexed_attention_one_head"
#: the thresholds' queries a step and keys a chunk, PR 46's; the tests
#: set them small beside ``ops/indexed.py``'s
SELECT_TILE_Q, SELECT_CHUNK = 32, 2048


def _attention_kernel(lo_ref, hi_ref, q_ref, k_ref, v_ref, keys_ref, tau_ref,
                      cut_ref, start_ref, o_ref, sets_ref, m_ref, l_ref,
                      acc_ref, *, per: int):
    i, g, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    steps = pl.num_programs(2)
    tile_q, tile_k = keys_ref.shape

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when((j == 0) & (g == 0))
    def _():
        sets_ref[...] = jnp.zeros(sets_ref.shape, jnp.int32)

    # a tile that holds a pair a query may read: from its first
    # request's first key block to the diagonal's
    @pl.when((j >= lo_ref[i]) & (j <= hi_ref[i]))
    def _():
        s = lax.dot_general(q_ref[0, 0], k_ref[0],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        keys, tau = keys_ref[...], tau_ref[...]
        q_at = i * tile_q + lax.broadcasted_iota(
            jnp.int32, (tile_q, tile_k), 0)
        k_at = j * tile_k + lax.broadcasted_iota(
            jnp.int32, (tile_q, tile_k), 1)
        chosen = ((keys > tau) | ((keys == tau) & (k_at <= cut_ref[...]))) \
            & (k_at <= q_at) & (k_at >= start_ref[...])

        # the sets as bits, once (the heads share them): key tile j is
        # bit j of a word
        @pl.when(g == 0)
        def _():
            sets_ref[...] = sets_ref[...] | (chosen.astype(jnp.int32) << j)
        # rows are (head, query): the same set for every head
        s = jnp.where(jnp.concatenate([chosen] * per, axis=0), s, _MASKED)
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


def masked_attention(q, k, v, keys, tau, cut, start,
                     interpret: bool = False):
    """``q`` (T, Hk, per, D) scaled; ``k``, ``v`` (T, Hk, D); ``keys``,
    ``tau``, ``cut`` the sets (:func:`index_keys`, :func:`thresholds`).
    -> ((T, Hk, per, D) in q's dtype: softmax attention of each query
    over the keys of its set; the sets as bits (T, keys a tile) uint32,
    bit b of word w standing for key ``b * (keys a tile) + w``:
    :func:`unpack_sets` reads them, :func:`count_sets` counts them)."""
    tokens, groups, per, dim = q.shape
    tile_q, tile_k = attention_tiles(tokens)
    nq, nk = tokens // tile_q, tokens // tile_k
    rows = per * tile_q
    # a query tile as one matrix, rows (head, query)
    q_tiles = q.reshape(nq, tile_q, groups, per, dim) \
        .transpose(2, 0, 3, 1, 4).reshape(groups, nq, rows, dim)
    # the key tiles a query tile walks: from the block that holds the
    # first key of its first query's request to the diagonal's. A step
    # outside them names the nearest of them and moves nothing
    lo = (start[::tile_q] // tile_k).astype(jnp.int32)
    hi = jnp.asarray((np.arange(nq) * tile_q + tile_q - 1) // tile_k,
                     jnp.int32)

    def walked(j, i, lo, hi):
        return jnp.clip(j, lo[i], hi[i])
    one = pl.BlockSpec((tile_q, 1), lambda i, g, j, *_: (i, 0))
    out, sets = pl.pallas_call(
        functools.partial(_attention_kernel, per=per),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nq, groups, nk),
            in_specs=[
                pl.BlockSpec((1, 1, rows, dim),
                             lambda i, g, j, *_: (g, i, 0, 0)),
                pl.BlockSpec((1, tile_k, dim), lambda i, g, j, lo, hi:
                             (g, walked(j, i, lo, hi), 0)),
                pl.BlockSpec((1, tile_k, dim), lambda i, g, j, lo, hi:
                             (g, walked(j, i, lo, hi), 0)),
                pl.BlockSpec((tile_q, tile_k), lambda i, g, j, lo, hi:
                             (i, walked(j, i, lo, hi))),
                one, one, one],
            out_specs=[pl.BlockSpec((1, 1, rows, dim),
                                    lambda i, g, j, *_: (g, i, 0, 0)),
                       pl.BlockSpec((tile_q, tile_k),
                                    lambda i, g, j, *_: (i, 0))],
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, dim), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((groups, nq, rows, dim), q.dtype),
                   jax.ShapeDtypeStruct((tokens, tile_k), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=KERNEL,
    )(lo, hi, q_tiles, k.transpose(1, 0, 2), v.transpose(1, 0, 2), keys,
      tau[:, None], cut[:, None], start[:, None])
    out = out.reshape(groups, nq, per, tile_q, dim) \
        .transpose(1, 3, 0, 2, 4).reshape(tokens, groups, per, dim)
    return out, lax.bitcast_convert_type(sets, jnp.uint32)


def replaced_passes(q, q_weight, positions, inv_freq, eps, act):
    """What the mixer did to q in front of that kernel: ``q`` (rows, Q,
    Hq D) float32 as its product wrote it -> (T, Hq, D) in ``act``."""
    rows, qlen, _ = q.shape
    dim = q_weight.shape[0]
    qs = rms_norm(q.reshape(rows, qlen, -1, dim), q_weight, eps, jnp.float32)
    qs = (rope.rotate(qs, positions, inv_freq) * dim ** -0.5).astype(act)
    return qs.reshape(rows * qlen, -1, dim)


def indexed_attention(q, k, v, keys, tau, cut, q_weight, tables, eps,
                      interpret=False, *, qlen, inv_freq):
    """``ops/indexed.indexed_attention``'s arguments and results through
    the passes and the kernel above: of ``tables`` it reads ``start``
    alone, the rotary is ``ops/rope.rotate``'s from ``inv_freq`` over
    rows of ``qlen`` tokens."""
    tokens, dim = q.shape[0], q_weight.shape[0]
    groups = k.shape[1] // dim
    start = tables[2][:, 0]
    positions = (jnp.arange(tokens, dtype=jnp.int32) - start) \
        .reshape(-1, qlen)
    qs = replaced_passes(q.reshape(positions.shape + (-1,)), q_weight,
                         positions, inv_freq, eps, v.dtype)
    out, sets = masked_attention(
        qs.reshape(tokens, groups, -1, dim), k.reshape(tokens, groups, dim),
        v.reshape(tokens, groups, dim), keys, tau, cut, start, interpret)
    return out.reshape(tokens, -1), sets


# -- the thresholds, PR 46 to PR 55 ----------------------------------------


def _count(keys_ref, test, last):
    """(tile, 1) int32: over a step's rows of sort keys, the keys with
    ``test(keys, their positions)``, a chunk at a time; a chunk that
    begins behind position ``last`` holds nothing a query may read."""
    tile, tokens = keys_ref.shape
    chunk = min(SELECT_CHUNK, tokens)
    total = jnp.zeros((tile, 1), jnp.int32)
    for lo in range(0, tokens, chunk):
        def some(lo=lo):
            at = lo + lax.broadcasted_iota(jnp.int32, (tile, chunk), 1)
            hit = test(keys_ref[:, lo:lo + chunk], at)
            return jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)
        total = total + lax.cond(lo <= last, some,
                                 lambda: jnp.zeros((tile, 1), jnp.int32))
    return total


def _threshold_kernel(keys_ref, tau_ref, over_ref, reach_ref, *, topk: int):
    tile = keys_ref.shape[0]
    last = pl.program_id(0) * tile + tile - 1

    def at_least(cand):
        return _count(keys_ref, lambda keys, _: keys >= cand, last)
    # the sign first, then the 31 bits under it from the top: the
    # largest value that topk keys reach
    tau = jnp.where(at_least(jnp.zeros((tile, 1), jnp.int32)) >= topk,
                    0, LOWEST).astype(jnp.int32)

    def step(n, tau):
        cand = tau | (jnp.int32(1) << (30 - n))
        return jnp.where(at_least(cand) >= topk, cand, tau)
    tau = lax.fori_loop(0, 31, step, tau)
    tau_ref[...] = tau
    over_ref[...] = _count(keys_ref, lambda keys, _: keys > tau, last)
    reach_ref[...] = at_least(tau)


def _tie_kernel(fetch_ref, tied_ref, keys_ref, tau_ref, want_ref, cut_ref,
                *, bits: int):
    i = pl.program_id(0)
    tile, tokens = keys_ref.shape
    last = i * tile + tile - 1
    cut_ref[...] = jnp.full((tile, 1), tokens, jnp.int32)

    @pl.when(tied_ref[i] != 0)
    def _():
        tau, want = tau_ref[...], want_ref[...]

        def step(n, cut):
            cand = cut | (jnp.int32(1) << (bits - 1 - n))
            before = _count(keys_ref, lambda keys, at:
                            (keys == tau) & (at < cand), last)
            return jnp.where(before < want, cand, cut)
        # the largest position with fewer than ``want`` equal keys in
        # front of it: where the want-th of them lies
        cut_ref[...] = lax.fori_loop(
            0, bits, step, jnp.zeros((tile, 1), jnp.int32))


def thresholds(keys, position, topk: int, interpret: bool = False):
    """``ops/indexed.thresholds``' arguments and results as the tree
    computed them until PR 55 (a query is ``tied`` where its ``cut`` lies
    under the pool's size)."""
    tokens = keys.shape[0]
    tile = _tile(SELECT_TILE_Q, tokens)
    steps = tokens // tile
    one = pl.BlockSpec((tile, 1), lambda i, *_: (i, 0))
    column = jax.ShapeDtypeStruct((tokens, 1), jnp.int32)
    tau, over, reach = pl.pallas_call(
        functools.partial(_threshold_kernel, topk=topk),
        grid=(steps,),
        in_specs=[pl.BlockSpec((tile, tokens), lambda i: (i, 0))],
        out_specs=[one, one, one], out_shape=[column, column, column],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="index_threshold_parent")(keys)
    tied = (position[:, None] + 1 > topk) & (reach > topk)
    tile_tied = tied.reshape(steps, tile).any(axis=1)
    fetch = lax.cummax(jnp.where(tile_tied, jnp.arange(steps), 0), axis=0)
    cut = pl.pallas_call(
        functools.partial(_tie_kernel,
                          bits=max(1, int(tokens - 1).bit_length())),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(steps,),
            in_specs=[pl.BlockSpec((tile, tokens),
                                   lambda i, fetch, _: (fetch[i], 0)),
                      one, one],
            out_specs=one),
        out_shape=column,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="index_tie_cutoff_parent",
    )(fetch.astype(jnp.int32), tile_tied.astype(jnp.int32), keys, tau,
      topk - over)
    return tau[:, 0], jnp.where(tied, cut, tokens)[:, 0]
