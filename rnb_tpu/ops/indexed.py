"""Attention under a *learned* choice of keys, over a packed pool of rows
(DeepSeek-Sparse-Attention's lightning indexer, arXiv:2512.02556 /
DeepSeek-V3.2-Exp): a small scoring network beside the attention gives
every (query, key) pair of a request a score, a query keeps the ``topk``
keys of its own request at or before it with the largest scores (all of
them while it has ``topk`` or fewer; a tie goes to the lower key), and
every head attends, causally, to those keys only. Queries that choose
and queries that read everything share one pool and the same three
kernels. Nothing is approximated: every query gets exactly its own set. Two
families call the module: ``models/keye_vl2`` (grouped queries, 16 index
heads of 64: the text below) and, since PR 55, ``models/dots3_note``,
whose full layers run *latent* attention under the sets: the same
scores kernel at 64 index heads of 128 with queries from the query
latent, the same thresholds, and a kernel of its own under the sets
(``latent_indexed_attention``, at the end of the module with its text
and sweep); Keye-VL's attention kernel is as PR 54 left it.

The pool holds ``rows`` of ``Q`` tokens; a request is a run of
consecutive rows (``row_start[r]``: the first row of row r's request; a
pad row is a request of its own). T = rows x Q.

**Scores** (:func:`index_keys`, the Pallas kernel ``index_scores``):
``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` over the indexer's
heads j, float32 from bfloat16 operands, a (query tile, key tile) at a
time with the heads unrolled inside it; tiles over the diagonal or
wholly of earlier requests are not computed. What it writes is not the
float but its *sort key*: the int32 whose signed order is the float's
(``-0.0`` folded into ``0.0`` first), and the smallest int32 on every
pair a query may not read (the future, another request). (T, T) int32:
1 GiB at 16,384 tokens, written once and read three times a layer.

**The choice** (:func:`thresholds`, the kernels ``index_threshold`` and
``index_tie_cutoff``) is a threshold a query, not a sort: the largest
``tau[t]`` with at least ``topk`` keys at or over it, built bit by bit
from the top (the sign and 31 bits: 32 counts over the query's row of
sort keys, which lies in VMEM: a sort of 16,384 floats a query is 105
compare-exchange passes, and a rank count 2.7 x 10^8 comparisons), and,
where more keys than ``topk`` lie at or over it — equal scores at the
cut —, the position ``cut[t]`` up to which the keys that *equal*
``tau[t]`` are kept, found the same way over the positions' bits in the
steps that hold such a query and nowhere else. Query t's set is then
``{s : key[t, s] > tau[t] or (key[t, s] == tau[t] and s <= cut[t])}``
among the keys it may read: exactly ``min(t + 1, topk)`` of them. A
query with fewer keys than ``topk`` has ``tau`` the smallest int32:
everything (with exactly ``topk``, its lowest key's: the same set).

A grid step holds 128 queries and their whole rows of sort keys (8 MiB
at 16,384 keys). *A count* (``_count``) is one ``lax.fori_loop`` over
the chunks of 1,024 keys from the one that holds the first key of the
step's first request to the one that holds the step's last query
(:func:`select_walk`, two numbers a step in scalar memory: the keys in
front are other requests', all the smallest int32, and those behind the
future's); inside it every 128 lanes are loaded, compared and added
*under the lanes* into a (128, 128) int32 carry, a load and three
vector operations a register with nothing between them, and the lanes
are summed once, behind the loop. A step none of whose queries has
``topk`` keys to read counts nothing and moves no rows. The two counts
a tie needs fall out of the search: the keys at or over ``tau`` are the
count of the last candidate taken, those over it the count of the last
one refused (``tau + 1`` is that candidate).

**What the walk replaced, and its sweep** (PR 56; from PR 46 to PR 55 a
step held 32 queries, a count was a Python loop over eight chunks of
2,048 keys from key 0 of the pool, each a ``lax.cond`` around a load, a
compare, a cast and a lane reduction, and two further counts behind the
search gave the tie's numbers: 34 counts; ``tests/keye_parent.py`` keeps
that form for the bit-for-bit tests). My chip runs, PR 56, one TPU v5
lite, 128 rows of 128 tokens as one / two / three requests, ``topk``
2,048, ``scripts/indexed_sweep.py --only=thresholds``, ms a layer by
the host's clock around a jitted call, every form's ``tau`` and ``cut``
the parent's to the bit. *The parent* **11.7 / 11.7 / 11.7**: it walks
from key 0 whatever the pool holds. *The walk* at (queries a step, keys
a chunk): (128, 1,024), which stands, **4.99 / 3.26 / 2.76**; (128,
2,048) 4.99 / 3.33 / 2.73; (128, 512) 5.09 / 3.45 / 2.63; (128, 256)
5.48 / 3.47 / 2.81; (128, 4,096) 5.41 / 3.89 / 3.72 (before the steps
that count nothing stopped moving their rows, which took (128, 2,048)
from 5.10 / 3.70 / 3.31; 80 rows are no whole chunks of 4,096); (64,
1,024) 5.25 / 3.41 / 2.84; (64, 2,048) 5.27 / 3.85 / 3.47 and (32,
2,048) 6.07 / 4.50 / 3.89 (likewise before); (256, 512) 7.24 / 4.36 /
3.53 and (256, 2,048) 6.99 / 4.57 / 4.15: a carry of 32 registers
beside 32 of candidates no longer fits the register file. Probes (times
only, not in the tree; (128, 2,048), one / three requests): the kernel
with its rows never moved 4.97 / 2.81; moving the rows and counting
nothing **2.5**: 1 GiB a layer arrives at 430 GB/s, not at the 819 of
the data sheet, and lies under every pool's time since; every count
with an empty loop 2.5 as well (hidden behind the rows). Over the
pools the walk's own time is 0.72 cycles a register visited (a load and
three operations on four vector slots need 0.75) and 1.4 ms a layer of
fixed cost, 330 cycles a count: the lane sum, the candidate's way back
under the lanes and the loop's start, once a count where the parent
paid them once a chunk.

**Attention** (:func:`indexed_attention`, the kernel of that name) is a
flash kernel from q's product to ``o``'s operand. A grid step is a
(query tile, key tile) pair for *all* the heads: it reads q as the
``(tile_q, Hq D)`` block of the float32 array the layer's first product
wrote, k and v as ``(tile_k, Hk D)`` blocks in the activations' dtype
(k normed and turned by XLA, on an eighth of q's columns: a key tile is
read by up to 64 query tiles, and its first lines would be redone at
each), the pair's tile of sort keys, and writes the ``(tile_q, Hq D)``
block the last product reads. Between the two products no array with a
head axis exists in HBM. *At a query tile's first key step* each head's
``(tile_q, D)`` slice goes through the head's RMS norm, the rotary (``x
cos + roll(x, D / 2) sin`` under ``ops/banded.band_tables``' tables, a
dispatch's), the scores' scale and one rounding — ``ops/banded.py``'s
``_first_lines``, the same float32 operations in the same order as
``rms_norm`` + ``ops/rope.rotate`` + the scale + the cast — into VMEM
scratch, a head in front, for the tile's key steps. *At every step* the
mask is built once — three integer comparisons of the sort keys with
``tau`` and ``cut``, the same for every head —, the sets' bits are
written from it, and it becomes an additive float32 tile (0 or -1e30:
the sum is the ``where``'s value to the bit); then the eight query
heads of a key-value head, their rows under each other, take one
product for the scores against the head's ``(tile_k, D)`` lanes of the
block, the tile added under each head by a leading axis (nothing is
concatenated), the running maximum and sum in float32, the
probabilities rounded to the activations' dtype into the values'
product. The running maximum and sum lie in scratch *under each of the
D lanes* (``(Hq, tile_q, D)``: a row's number 128 times), so that ``s -
m`` is ``pltpu.repeat`` and a plain subtraction and ``alpha acc`` a
plain product: as a ``(tile_q, 1)`` column, the form the kernel had
until PR 54, the maximum's way from the lane reduction into the scores'
subtraction was half the kernel's time (the sweep below). A query tile
walks the key tiles from its first request's first to the diagonal's
(two numbers a query tile in scalar memory); inside them it skips
nothing: under seeded random weights a query's 2,048 keys lie all over
its request and no causal tile of a request is ever free of them
(:func:`count_sets` counts the tiles that hold a chosen key, for the
day that changes).

**What a sample keeps** is the kernel's second result: the sets as bits,
(T, keys a tile) uint32, key tile b being bit b of a word — bit b of
word w of query t stands for key ``b * (keys a tile) + w`` — written
once a step from the step's one mask (two operations an element and no
pass of its own: as two passes of XLA's over the matrix, bits and tiles
took 6.3 ms a layer, my chip runs, PR 46); :func:`unpack_sets` is its
inverse on the host.

**What the attention kernel replaced** (PR 54). From PR 46 to PR 53 a
step held *one* key-value head (grid (query tile, 4, key tile)): it
read the same tile of sort keys and built the same mask for each of the
four, laid the mask under itself eight times (``concatenate``) for the
``where`` over the (8 x 256, 512) scores, and kept maximum and sum as
(2,048, 1) columns. Around it the mixer made float32 passes over q
(``rms_norm``, ``rope.rotate`` with a half materialised and
concatenated, the scale, the cast), a copy into (key-value head, query
tile, (head, query), D) and a copy back (``tests/keye_parent.py`` keeps
that form for the bit-for-bit tests).

**The sweep** (my chip runs, PR 54; one TPU v5 lite, 128 rows of 128
tokens as one / two / three requests, 32 / 4 heads of 128;
``scripts/indexed_sweep.py``; ms a layer, by the host's clock around a
jitted call). *The parent*: its kernel alone **25.7 / 15.1 / 12.1**
(PR 46 read 25.4 / 15.1 / 12.1), the passes around it alone, as XLA
runs them, 7.6, both in one program 31.2 / 21.0 / 17.8. *This kernel's
first form* — all heads a step, one mask, q's first lines inside, but
maximum and sum still columns — read 29.5 / 16.6 / 12.8: a step of 32
heads took 25.7 us where the parent's four took 21 (fitted over the
three pools: the fixed part fell from 4.5 ms to 2.7). A probe with a
line left out (wrong results, times only; one request; not in the tree)
said why: without the first lines 29.0, without the mask's sum 29.3,
without the sets' bits 29.4 — the mask and its copies had been no
cost worth the name — but without the row maximum **15.3**, with the
maximum computed and *not subtracted* 17.7, without the row sum 23.7;
with no softmax line at all 15.0, and with no product either 6.7. The
same kernel with the two statistics under every lane: **16.6**, the
same bits; that is the form that stands. On it (the forms beside
the standing one are in this record alone, not in the tree): the heads
of a key-value head as one product, which stands, 16.3 / 9.7 / 7.9, two heads a
product 16.4 / 9.9 / 7.9, a head a product 16.7 / 9.9 / 8.1 (18.5 /
10.0 / 8.0 at a second reading in the same call; it lowers in 0.55 s a
row bucket for 0.30 s, five buckets a run); the mask as a
``where`` under a broadcast in the place of the sum 16.4 / 9.8 / 7.9
(at a head a product 16.6 / 10.0 / 8.0); k handed over transposed once
in front 16.4 / 9.8 / 7.9 (16.8 / 9.9 / 8.1); the heads' products in a
loop the compiler keeps rolled 20.5 / 12.1 / 9.5 (two heads a turn 18.5
/ 11.0 / 8.8; compiled here for a described v5e it takes 2.6 s for
10.7); 128 queries a tile 17.0 /
10.5 / 8.6 with eight heads a product and 22.9 / 13.5 / 10.7 with one
(17.3 with the heads' lines staged, all scores, then all softmaxes, then
all values' products: at 256 queries that order reads 16.4 for 16.6);
512 queries a tile does not fit VMEM. Every form gives the parent's
values and sets to the last bit, compiled as interpreted. The step's
two products are 11.5 ms a layer of one request at the matrix unit's
peak: the kernel stands at 71% of it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rnb_tpu.ops import latent
from rnb_tpu.ops.banded import _first_lines

#: the indexer's scores: queries and keys a tile
_SCORE_TILE_Q, _SCORE_TILE_K = 512, 1024
#: the thresholds: queries a step (their whole rows of sort keys lie in
#: VMEM: 8 MiB at 16,384 keys), and the keys a turn of a count's loop
#: looks at: a count walks the chunks from the step's first request's
#: first key to its diagonal (the sweep in the module's text)
_SELECT_TILE_Q, _SELECT_CHUNK = 128, 1024
#: the attention kernel: queries and keys a tile
_TILE_Q, _TILE_K = 256, 512
_MASKED = -1e30
LOWEST = np.iinfo(np.int32).min
_VMEM_LIMIT = 64 * 2 ** 20
SCORES_KERNEL = "index_scores"
THRESHOLD_KERNEL = "index_threshold"
TIE_KERNEL = "index_tie_cutoff"
ATTENTION_KERNEL = "indexed_attention"
LATENT_KERNEL = "latent_indexed_attention"
#: the latent kernel: queries a tile, keys a tile, heads a step (the
#: sweep at :func:`latent_indexed_attention`)
_LATENT_TILES = (512, 512, 16)


def token_table(row_start, row_tokens, qlen: int):
    """Per token of the pool: (the first token of its request (T,)
    int32, whether it is a valid token (T,) bool)."""
    start = jnp.repeat(row_start.astype(jnp.int32) * qlen, qlen)
    valid = (jnp.arange(qlen)[None, :] < row_tokens[:, None]).reshape(-1)
    return start, valid


def sort_key(x):
    """float32 -> the int32 whose signed order is the float's; ``-0.0``
    and ``0.0`` give one key."""
    bits = lax.bitcast_convert_type(x + 0.0, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _tile(want: int, tokens: int) -> int:
    tile = min(want, tokens)
    if tokens % tile:
        raise ValueError("%d tokens are no whole tiles of %d"
                         % (tokens, tile))
    return tile


# -- the scores -----------------------------------------------------------


def _scores_kernel(first_ref, q_ref, k_ref, w_ref, start_ref, o_ref):
    i, j = pl.program_id(0), pl.program_id(1)
    tile_q, tile_k = o_ref.shape
    heads = q_ref.shape[0]
    # the tile holds a pair a query may read: not over the diagonal,
    # and its keys end after the first of the tile's requests begins
    runs = (j * tile_k <= i * tile_q + tile_q - 1) \
        & ((j + 1) * tile_k > first_ref[i])

    @pl.when(runs)
    def _():
        keys = k_ref[...]                                  # (D, tk)
        acc = jnp.zeros((tile_q, tile_k), jnp.float32)
        for h in range(heads):
            s = jnp.dot(q_ref[h], keys,
                        preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(s, 0.0) * w_ref[:, h:h + 1]
        q_at = i * tile_q + lax.broadcasted_iota(
            jnp.int32, (tile_q, tile_k), 0)
        k_at = j * tile_k + lax.broadcasted_iota(
            jnp.int32, (tile_q, tile_k), 1)
        mine = (k_at <= q_at) & (k_at >= start_ref[...])
        o_ref[...] = jnp.where(mine, sort_key(acc), LOWEST)

    @pl.when(jnp.logical_not(runs))
    def _():
        o_ref[...] = jnp.full(o_ref.shape, LOWEST, jnp.int32)


def index_keys(q, k, w, start, interpret: bool = False):
    """``q`` (T, heads, D) and ``k`` (T, D) in the operands' dtype, the
    indexer's queries and its one key head; ``w`` (T, heads) float32,
    the heads' weights with every scale folded in; ``start`` (T,) int32.
    -> (T, T) int32: :func:`sort_key` of ``I[t, s]`` where query t may
    read key s, the smallest int32 elsewhere."""
    tokens, heads, dim = q.shape
    tile_q, tile_k = _tile(_SCORE_TILE_Q, tokens), \
        _tile(_SCORE_TILE_K, tokens)
    return pl.pallas_call(
        _scores_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tokens // tile_q, tokens // tile_k),
            in_specs=[
                pl.BlockSpec((heads, tile_q, dim),
                             lambda i, j, _: (0, i, 0)),
                pl.BlockSpec((dim, tile_k), lambda i, j, _: (0, j)),
                pl.BlockSpec((tile_q, heads), lambda i, j, _: (i, 0)),
                pl.BlockSpec((tile_q, 1), lambda i, j, _: (i, 0))],
            out_specs=pl.BlockSpec((tile_q, tile_k),
                                   lambda i, j, _: (i, j))),
        out_shape=jax.ShapeDtypeStruct((tokens, tokens), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=SCORES_KERNEL,
    )(start[::tile_q], q.transpose(1, 0, 2), k.T, w.astype(jnp.float32),
      start[:, None])


# -- the choice -----------------------------------------------------------


def select_form(tokens: int):
    """(queries a step, keys a chunk) of the thresholds for a pool of
    ``tokens``: the module's, cut to the pool."""
    return _tile(_SELECT_TILE_Q, tokens), _tile(_SELECT_CHUNK, tokens)


def select_walk(position, topk: int, form=None):
    """A count's walk over a pool, from the pool's layout alone:
    ``position`` (T,) int32, a token's index inside its request. -> (lo,
    hi), (query steps,) int32 each: the first and the last key chunk a
    count of the step visits; ``lo = hi + 1`` is a step that counts
    nothing. The thresholds' kernels take both as they come from here,
    and :func:`chunk_visits` counts them.

    A step walks from the chunk that holds the first key of its *first*
    query's request (requests lie in the pool in order, so no query of
    the step may read a key in front of it: all ``LOWEST``) to the chunk
    that holds its last query (the diagonal). A step none of whose
    queries has ``topk`` keys to read (``position + 1 < topk``: no
    candidate over ``LOWEST`` can reach ``topk`` keys) walks nothing."""
    tokens = position.shape[0]
    tile, chunk = form or select_form(tokens)
    # the chunk that holds each step's last query
    hi = jnp.asarray((np.arange(tokens // tile) * tile + tile - 1) // chunk,
                     jnp.int32)
    first = (jnp.arange(tokens, dtype=jnp.int32) - position)[::tile]
    counts = (position + 1 >= topk).reshape(-1, tile).any(axis=1)
    return jnp.where(counts, first // chunk, hi + 1), hi


def chunk_visits(position, topk: int):
    """int32 (2,): the (query step, key chunk) visits a count makes on
    :func:`select_walk`'s walk over the pool, and those of a walk from
    key 0 to every step's diagonal (the thresholds' until PR 55), both
    at the module's queries a step and keys a chunk."""
    lo, hi = select_walk(position, topk)
    return jnp.stack([(hi + 1 - lo).sum(), (hi + 1).sum()])


def _lanes(chunk: int) -> int:
    """The lanes a count's sums lie under: a register's 128, fewer in a
    toy pool."""
    return math.gcd(chunk, 128)


def _count(keys_ref, lo, hi, chunk: int, test):
    """(tile, 1) int32: over a step's rows of sort keys, the keys with
    ``test(keys, the pool position of the first of them)`` in the chunks
    ``lo`` to ``hi`` of ``chunk`` keys. One loop over the chunks; inside
    it the hits of each 128 lanes are added under the lanes, and the
    lanes are summed once, behind the loop."""
    tile, lanes = keys_ref.shape[0], _lanes(chunk)

    def some(c, total):
        for at in range(0, chunk, lanes):
            first = pl.multiple_of(c * chunk + at, lanes)
            total = total + test(keys_ref[:, pl.ds(first, lanes)],
                                 first).astype(jnp.int32)
        return total
    total = lax.fori_loop(lo, hi + 1, some,
                          jnp.zeros((tile, lanes), jnp.int32))
    return jnp.sum(total, axis=1, keepdims=True)


def _under_lanes(column, chunk: int):
    """A (tile, 1) column as :func:`_count`'s test reads it: a row's
    number under each of a register's lanes."""
    return jnp.broadcast_to(column, (column.shape[0], _lanes(chunk)))


def _threshold_kernel(lo_ref, hi_ref, fetch_ref, keys_ref, tau_ref, over_ref,
                      reach_ref, *, topk: int, chunk: int):
    i = pl.program_id(0)
    tile = keys_ref.shape[0]
    lo, hi = lo_ref[i], hi_ref[i]

    @pl.when(lo > hi)
    def _():
        # no query here has topk keys to read: everything, no tie
        tau_ref[...] = jnp.full((tile, 1), LOWEST, jnp.int32)
        over_ref[...] = jnp.zeros((tile, 1), jnp.int32)
        reach_ref[...] = jnp.zeros((tile, 1), jnp.int32)

    @pl.when(lo <= hi)
    def _():
        def accept(cand, tau, over, reach):
            wide = _under_lanes(cand, chunk)
            count = _count(keys_ref, lo, hi, chunk,
                           lambda keys, _: keys >= wide)
            ok = count >= topk
            # ``reach``, the keys at or over tau, is the count of the
            # last candidate taken; ``over``, the keys over tau, that of
            # the last one refused: tau + 1 is tau with its lowest 0 bit
            # set and the 1s under it cleared, the candidate of that
            # bit's turn, and every later one was taken (tau with no 0
            # under the sign: the sign's own turn, or none, and no key
            # lies over the largest int32)
            return jnp.where(ok, cand, tau), jnp.where(ok, over, count), \
                jnp.where(ok, count, reach)
        # the sign first, then the 31 bits under it from the top: the
        # largest value that topk keys reach. At or over LOWEST lies
        # every key walked
        zero = jnp.zeros((tile, 1), jnp.int32)
        carry = accept(zero, jnp.full((tile, 1), LOWEST, jnp.int32), zero,
                       jnp.full((tile, 1), (hi + 1 - lo) * chunk, jnp.int32))

        def step(n, carry):
            return accept(carry[0] | (jnp.int32(1) << (30 - n)), *carry)
        tau_ref[...], over_ref[...], reach_ref[...] = lax.fori_loop(
            0, 31, step, carry)


def _tie_kernel(lo_ref, hi_ref, fetch_ref, tied_ref, keys_ref, tau_ref,
                want_ref, cut_ref, *, bits: int, chunk: int):
    i = pl.program_id(0)
    tile, tokens = keys_ref.shape
    cut_ref[...] = jnp.full((tile, 1), tokens, jnp.int32)

    @pl.when(tied_ref[i] != 0)
    def _():
        tau, want = _under_lanes(tau_ref[...], chunk), want_ref[...]
        lane = lax.broadcasted_iota(jnp.int32, tau.shape, 1)

        def step(n, cut):
            cand = cut | (jnp.int32(1) << (bits - 1 - n))
            before = _count(keys_ref, lo_ref[i], hi_ref[i], chunk,
                            lambda keys, first:
                            (keys == tau) & (lane < cand - first))
            return jnp.where(before < want, cand, cut)
        # the largest position with fewer than ``want`` equal keys in
        # front of it: where the want-th of them lies
        cut_ref[...] = lax.fori_loop(
            0, bits, step, jnp.zeros((tile, 1), jnp.int32))


# under a ``jit`` of its own, like the attention kernel's call: a
# stack's layers call it with the same shapes, and the two kernels are
# traced and lowered once for all (``form`` is a key of that cache: the
# sweep sets the module's constants)
@functools.partial(jax.jit, static_argnames=("topk", "form", "interpret"))
def _thresholds_call(keys, position, *, topk, form, interpret):
    tokens = keys.shape[0]
    tile, chunk = form
    steps = tokens // tile
    lo, hi = select_walk(position, topk, form)

    def held(runs):
        # a step that does not run moves no rows: it names the block
        # the last step that ran held
        return lax.cummax(jnp.where(runs, jnp.arange(steps), 0), axis=0) \
            .astype(jnp.int32)
    rows = pl.BlockSpec((tile, tokens),
                        lambda i, lo, hi, fetch, *_: (fetch[i], 0))
    one = pl.BlockSpec((tile, 1), lambda i, *_: (i, 0))
    column = jax.ShapeDtypeStruct((tokens, 1), jnp.int32)
    tau, over, reach = pl.pallas_call(
        functools.partial(_threshold_kernel, topk=topk, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(steps,), in_specs=[rows],
            out_specs=[one, one, one]),
        out_shape=[column, column, column],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=THRESHOLD_KERNEL)(lo, hi, held(lo <= hi),
                                                    keys)
    # a query that chooses, with more keys at or over tau than topk:
    # equal scores at the cut. Its set takes the first ``want`` of the
    # keys that equal tau: topk less those over it. Why the keys the
    # walk leaves out (in front of the step's first request: LOWEST,
    # every one) cannot change ``tied`` or ``cut``: a score's sort key
    # is never LOWEST (that is one NaN's bits and no float's), so a
    # query with over topk keys to read has a tau over LOWEST, and no
    # left-out key is at or over it, over it, or equal to it: ``reach``,
    # ``over`` and the tie kernel's counts are what a walk from key 0
    # gives. A query whose tau is LOWEST has topk keys or fewer and is
    # not tied whatever its ``reach`` (every key walked) reads
    tied = (position[:, None] + 1 > topk) & (reach > topk)
    tile_tied = tied.reshape(steps, tile).any(axis=1)
    cut = pl.pallas_call(
        functools.partial(_tie_kernel, chunk=chunk,
                          bits=max(1, int(tokens - 1).bit_length())),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(steps,),
            in_specs=[rows, one, one], out_specs=one),
        out_shape=column,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=TIE_KERNEL,
    )(lo, hi, held(tile_tied), tile_tied.astype(jnp.int32), keys, tau,
      topk - over)
    return tau[:, 0], jnp.where(tied, cut, tokens)[:, 0]


def thresholds(keys, position, topk: int, interpret: bool = False):
    """``keys`` (T, T) int32 from :func:`index_keys`; ``position`` (T,)
    int32, a token's index inside its request. -> (tau, cut), (T,) int32
    each: query t's set is the keys it may read with ``key > tau[t]``,
    or ``key == tau[t]`` at a pool position ``<= cut[t]``; exactly
    ``min(position + 1, topk)`` keys."""
    return _thresholds_call(keys, position, topk=int(topk),
                            form=select_form(keys.shape[0]),
                            interpret=bool(interpret))


# -- the sets, as a mask and as bits --------------------------------------


def chosen_mask(keys, tau, cut, start):
    """Bool (T, T): whether query t's set holds key s (plain
    ``jax.numpy`` over the whole matrix: the tests' and the tools')."""
    tokens = keys.shape[0]
    at = jnp.arange(tokens, dtype=jnp.int32)
    return ((keys > tau[:, None])
            | ((keys == tau[:, None]) & (at[None, :] <= cut[:, None]))) \
        & (at[None, :] <= at[:, None]) & (at[None, :] >= start[:, None])


def unpack_sets(packed):
    """The sets as bits, on the host: ``packed`` (..., count, words)
    uint32, bit b of word w standing for the pool's key ``b * words + w``
    -> bool (..., count, 32 * words): the keys by pool position (those
    past the pool's end never set)."""
    packed = np.asarray(packed)
    bits = (packed[..., None, :] >> np.arange(32, dtype=np.uint32)
            .reshape(32, 1)) & np.uint32(1)
    return bits.reshape(packed.shape[:-1] + (-1,)).astype(bool)


def count_sets(packed, tile_q: int):
    """From the sets as bits (T, words): (int32 (T,): the keys each
    query chose; int32: the (query tile, key tile) pairs, ``tile_q``
    queries by ``words`` keys, in which any query chose any key)."""
    tokens, words = packed.shape
    chose = lax.population_count(packed).sum(axis=1).astype(jnp.int32)
    # a key tile is a bit: the tiles a query tile reaches are the bits
    # set in any word of any of its rows
    reached = lax.reduce(packed.reshape(tokens // tile_q, tile_q * words),
                         np.uint32(0), lax.bitwise_or, (1,))
    return chose, lax.population_count(reached).sum().astype(jnp.int32)


# -- attention under the sets ---------------------------------------------


def attention_tiles(tokens: int):
    """(queries a tile, keys a tile) of the attention kernel for a pool
    of ``tokens``; a key tile is a bit of the sets' words, so a pool is
    32 of them at most."""
    tile_q = _tile(_TILE_Q, tokens)
    tile_k = _tile(_TILE_K, tokens)
    if tokens > 32 * tile_k:
        raise ValueError("%d tokens are more than 32 key tiles of %d"
                         % (tokens, tile_k))
    return tile_q, tile_k


def causal_tiles(tokens: int) -> int:
    """The attention kernel's (query tile, key tile) pairs on or under
    the diagonal of a pool of ``tokens``."""
    return _tiles_under_diagonal(tokens, *attention_tiles(tokens))


def _tiles_under_diagonal(tokens: int, tile_q: int, tile_k: int) -> int:
    return int(((np.arange(tokens // tile_q) * tile_q + tile_q - 1)
                // tile_k + 1).sum())


def _attention_kernel(lo_ref, hi_ref, q_ref, k_ref, v_ref, keys_ref, tau_ref,
                      cut_ref, start_ref, w_ref, cos_ref, sin_ref, o_ref,
                      sets_ref, qs_ref, m_ref, l_ref, acc_ref, *, groups: int,
                      eps: float):
    """One (query tile, key tile) pair for every head. ``q_ref``
    (tile_q, Hq D) float32 as its product wrote it; ``k_ref``, ``v_ref``
    (tile_k, Hk D) in the activations' dtype; ``keys_ref`` the pair's
    sort keys; ``tau_ref``, ``cut_ref``, ``start_ref`` (tile_q, 1);
    ``w_ref`` (1, D) the
    query norm's weight, ``cos_ref``, ``sin_ref`` (tile_q, D) the rotary
    tables; ``o_ref`` as ``q_ref`` in the activations' dtype;
    ``sets_ref`` the query tile's words. Scratch, a head in front:
    ``qs_ref`` the queries as the products read them, ``m_ref``,
    ``l_ref``, ``acc_ref`` the running maximum, sum and result, the
    first two a row's number under each of the D lanes."""
    i, j = pl.program_id(0), pl.program_id(1)
    heads, tile_q, dim = qs_ref.shape
    tile_k = keys_ref.shape[1]
    per = heads // groups

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        sets_ref[...] = jnp.zeros(sets_ref.shape, jnp.int32)
        # a head's norm, rotary, scale and one rounding: what the mixer
        # made five passes of, once a query tile for all its key steps
        weight, cos, sin = w_ref[...], cos_ref[...], sin_ref[...]
        for h in range(heads):
            qs_ref[h] = _first_lines(q_ref[:, h * dim:(h + 1) * dim], weight,
                                     cos, sin, eps, qs_ref.dtype, dim ** -0.5)

    # a tile that holds a pair a query may read: from its first
    # request's first key block to the diagonal's
    @pl.when((j >= lo_ref[i]) & (j <= hi_ref[i]))
    def _():
        keys, tau = keys_ref[...], tau_ref[...]
        q_at = i * tile_q + lax.broadcasted_iota(
            jnp.int32, (tile_q, tile_k), 0)
        k_at = j * tile_k + lax.broadcasted_iota(
            jnp.int32, (tile_q, tile_k), 1)
        # the pair's mask, once: every head reads the same set
        chosen = ((keys > tau) | ((keys == tau) & (k_at <= cut_ref[...]))) \
            & (k_at <= q_at) & (k_at >= start_ref[...])
        # the sets as bits: key tile j is bit j of a word
        sets_ref[...] = sets_ref[...] | (chosen.astype(jnp.int32) << j)
        bias = jnp.where(chosen, 0.0, _MASKED)[None]
        for g in range(groups):
            # a key-value head's query heads, their rows under each
            # other, in one product
            of = pl.ds(g * per, per)
            k = k_ref[:, g * dim:(g + 1) * dim]
            v = v_ref[:, g * dim:(g + 1) * dim]
            s = lax.dot_general(
                qs_ref[of].reshape(per * tile_q, dim), k,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) \
                .reshape(per, tile_q, tile_k) + bias
            m_prev = m_ref[of]
            m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            # a row that has met no chosen key yet holds sums of exp(0);
            # the first chosen key's maximum wipes them (alpha = 0)
            p = jnp.exp(s - pltpu.repeat(m_next, tile_k // dim, 2))
            alpha = jnp.exp(m_prev - m_next)
            l_ref[of] = alpha * l_ref[of] + p.sum(axis=-1, keepdims=True)
            acc_ref[of] = alpha * acc_ref[of] + jnp.dot(
                p.astype(v.dtype).reshape(per * tile_q, tile_k), v,
                preferred_element_type=jnp.float32) \
                .reshape(per, tile_q, dim)
            m_ref[of] = m_next

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        for h in range(heads):
            o_ref[:, h * dim:(h + 1) * dim] = \
                (acc_ref[h] / l_ref[h]).astype(o_ref.dtype)


def _cost(pairs: int, dim: int, arrays) -> pl.CostEstimate:
    """What a call costs, for the compiler that schedules around it
    (``ops/banded.py``'s ``_cost``): the two products of each of the
    ``pairs`` (query, key, head) a step holds over the causal tiles, an
    exponential a score, every operand's and result's bytes once (a key
    tile's more often, which this leaves out)."""
    return pl.CostEstimate(
        flops=2 * 2 * pairs * dim, transcendentals=pairs,
        bytes_accessed=sum(x.size * x.dtype.itemsize for x in arrays))


# a function under ``jit`` of its own: a stack's layers call it with the
# same shapes, and the kernel is traced and lowered once for all
@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _attention_call(q, k, v, keys, tau, cut, weight, cos, sin, start, *,
                    eps, interpret):
    tokens, dim = cos.shape
    heads, groups = q.shape[1] // dim, k.shape[1] // dim
    tile_q, tile_k = attention_tiles(tokens)
    nq, nk = tokens // tile_q, tokens // tile_k
    # the key tiles a query tile walks: from the block that holds the
    # first key of its first query's request to the diagonal's. A step
    # outside them names the nearest of them and moves nothing
    lo = (start[::tile_q, 0] // tile_k).astype(jnp.int32)
    hi = jnp.asarray((np.arange(nq) * tile_q + tile_q - 1) // tile_k,
                     jnp.int32)

    def walked(j, i, lo, hi):
        return jnp.clip(j, lo[i], hi[i])

    def mine(width):
        return pl.BlockSpec((tile_q, width), lambda i, j, *_: (i, 0))

    def theirs(width):
        return pl.BlockSpec((tile_k, width), lambda i, j, lo, hi:
                            (walked(j, i, lo, hi), 0))
    operands = (q, k, v, keys, tau[:, None],
                cut[:, None], start, weight.astype(jnp.float32)[None, :],
                cos, sin)
    outs = (jax.ShapeDtypeStruct(q.shape, v.dtype),
            jax.ShapeDtypeStruct((tokens, tile_k), jnp.int32))
    out, sets = pl.pallas_call(
        functools.partial(_attention_kernel, groups=groups, eps=eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nq, nk),
            in_specs=[
                mine(heads * dim), theirs(groups * dim), theirs(groups * dim),
                pl.BlockSpec((tile_q, tile_k), lambda i, j, lo, hi:
                             (i, walked(j, i, lo, hi))),
                mine(1), mine(1), mine(1),
                pl.BlockSpec((1, dim), lambda i, j, *_: (0, 0)),
                mine(dim), mine(dim)],
            out_specs=[mine(heads * dim), mine(tile_k)],
            scratch_shapes=[pltpu.VMEM((heads, tile_q, dim), v.dtype)]
            + [pltpu.VMEM((heads, tile_q, dim), jnp.float32)] * 3),
        out_shape=outs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=_cost(
            causal_tiles(tokens) * tile_q * tile_k * heads, dim,
            operands + outs),
        interpret=interpret, name=ATTENTION_KERNEL,
    )(lo, hi, *operands)
    return out, lax.bitcast_convert_type(sets, jnp.uint32)


def indexed_attention(q, k, v, keys, tau, cut, q_weight, tables, eps: float,
                      interpret: bool = False):
    """One layer's attention under the sets, from the products' results
    to the last product's operand (the module's text).

    ``q`` (T, Hq D) float32 as its product wrote it: the head norm
    (``q_weight`` (D,), ``eps``), the rotary, the scale and the rounding
    are the kernel's first lines; ``k`` normed and turned and ``v``, (T,
    Hk D) in the activations' dtype; ``keys``, ``tau``, ``cut`` the sets
    (:func:`index_keys`, :func:`thresholds`); ``tables``
    ``ops/banded.band_tables``' three, a dispatch's. -> ((T, Hq D) in v's
    dtype:
    softmax attention of each query over the keys of its set; the sets
    as bits (T, keys a tile) uint32, bit b of word w standing for key
    ``b * (keys a tile) + w``: :func:`unpack_sets` reads them,
    :func:`count_sets` counts them)."""
    cos, sin, start = tables
    return _attention_call(q, k, v, keys, tau, cut, q_weight, cos, sin,
                           start, eps=float(eps), interpret=bool(interpret))


# -- latent attention (MLA, expanded) under the sets ----------------------


def latent_tiles(tokens: int):
    """(queries a tile, keys a tile, heads a step) of the latent kernel
    for a pool of ``tokens``: the module's, the first two cut to the
    pool; a key tile is a bit of the sets' words."""
    tile_q, tile_k, per = _LATENT_TILES
    tile_q, tile_k = _tile(tile_q, tokens), _tile(tile_k, tokens)
    if tokens > 32 * tile_k:
        raise ValueError("%d tokens are more than 32 key tiles of %d"
                         % (tokens, tile_k))
    return tile_q, tile_k, per


def latent_causal_tiles(tokens: int) -> int:
    """The latent kernel's (query tile, key tile) pairs on or under the
    diagonal of a pool of ``tokens``."""
    return _tiles_under_diagonal(tokens, *latent_tiles(tokens)[:2])


def _latent_kernel(lo_ref, hi_ref, q_ref, kv_ref, ks_ref, keys_ref, tau_ref,
                   cut_ref, start_ref, gate_ref, o_ref, sets_ref, m_ref,
                   l_ref, acc_ref, *, own: int, value: int):
    """One (query tile, group of heads, key tile). ``q_ref`` (heads,
    tile_q, lanes) as ``ops/mla.queries`` wrote it; ``kv_ref`` (tile_k,
    heads (own + value)) a head's ``[own key | value]`` as their
    product wrote them; ``ks_ref`` the key tile's shared rotary key
    (``ops/latent.shared_key``); ``keys_ref`` the pair's sort
    keys; ``tau_ref``, ``cut_ref``, ``start_ref`` (tile_q, 1);
    ``gate_ref`` (1, tile_q, heads) float32; ``o_ref`` (tile_q, heads
    value); ``sets_ref`` the query tile's words, written by the first
    group. Scratch, a head in front: the running maximum, sum and
    result, the first two a row's number under each of the value's
    lanes."""
    i, g, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    heads, tile_q, _ = q_ref.shape
    tile_k = keys_ref.shape[1]
    wide = own + value

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when((j == 0) & (g == 0))
    def _():
        sets_ref[...] = jnp.zeros(sets_ref.shape, jnp.int32)

    @pl.when((j >= lo_ref[i]) & (j <= hi_ref[i]))
    def _():
        keys, tau = keys_ref[...], tau_ref[...]
        q_at = i * tile_q + lax.broadcasted_iota(
            jnp.int32, (tile_q, tile_k), 0)
        k_at = j * tile_k + lax.broadcasted_iota(
            jnp.int32, (tile_q, tile_k), 1)
        # the pair's mask: every head reads the same set; a group
        # builds it again (three integer comparisons: the attention
        # kernel's sweep found them no cost beside a head's products)
        chosen = ((keys > tau) | ((keys == tau) & (k_at <= cut_ref[...]))) \
            & (k_at <= q_at) & (k_at >= start_ref[...])

        @pl.when(g == 0)
        def _():
            sets_ref[...] = sets_ref[...] | (chosen.astype(jnp.int32) << j)
        bias = jnp.where(chosen, 0.0, _MASKED)
        shared = ks_ref[...]
        for h in range(heads):
            k = kv_ref[:, h * wide:h * wide + own]
            v = kv_ref[:, h * wide + own:(h + 1) * wide]
            s = latent.scores(q_ref[h], k, shared, own) + bias
            m_prev = m_ref[h]
            m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - pltpu.repeat(m_next, tile_k // value, 1))
            alpha = jnp.exp(m_prev - m_next)
            l_ref[h] = alpha * l_ref[h] + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_next

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        gate = gate_ref[0]
        for h in range(heads):
            o_ref[:, h * value:(h + 1) * value] = \
                (acc_ref[h] / l_ref[h] * gate[:, h:h + 1]) \
                .astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("nope", "value", "tiles",
                                             "interpret"))
def _latent_call(q, kv, shared, gate, keys, tau, cut, start, *, nope, value,
                 tiles, interpret):
    heads, tokens, lanes = q.shape
    own = latent.key_lanes(nope, lanes)
    wide = own + value
    tile_q, tile_k, per = tiles
    per = min(per, heads)
    nq, nk = tokens // tile_q, tokens // tile_k
    lo = (start[::tile_q, 0] // tile_k).astype(jnp.int32)
    hi = jnp.asarray((np.arange(nq) * tile_q + tile_q - 1) // tile_k,
                     jnp.int32)

    def walked(j, i, lo, hi):
        return jnp.clip(j, lo[i], hi[i])

    def mine(width):
        return pl.BlockSpec((tile_q, width), lambda i, g, j, *_: (i, 0))
    operands = (q, kv, shared, keys, tau[:, None], cut[:, None], start,
                latent.gate_groups(gate, per))
    outs = (jax.ShapeDtypeStruct((tokens, heads * value), kv.dtype),
            jax.ShapeDtypeStruct((tokens, tile_k), jnp.int32))
    pairs = _tiles_under_diagonal(tokens, tile_q, tile_k) \
        * tile_q * tile_k * heads
    out, sets = pl.pallas_call(
        functools.partial(_latent_kernel, own=own, value=value),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nq, heads // per, nk),
            in_specs=[
                pl.BlockSpec((per, tile_q, lanes),
                             lambda i, g, j, *_: (g, i, 0)),
                pl.BlockSpec((tile_k, per * wide), lambda i, g, j, lo, hi:
                             (walked(j, i, lo, hi), g)),
                pl.BlockSpec((tile_k, shared.shape[1]),
                             lambda i, g, j, lo, hi:
                             (walked(j, i, lo, hi), 0)),
                pl.BlockSpec((tile_q, tile_k), lambda i, g, j, lo, hi:
                             (i, walked(j, i, lo, hi))),
                mine(1), mine(1), mine(1),
                pl.BlockSpec((1, tile_q, per),
                             lambda i, g, j, *_: (g, i, 0))],
            out_specs=[
                pl.BlockSpec((tile_q, per * value),
                             lambda i, g, j, *_: (i, g)),
                mine(tile_k)],
            scratch_shapes=[pltpu.VMEM((per, tile_q, value),
                                       jnp.float32)] * 3),
        out_shape=outs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 2 ** 20),
        cost_estimate=pl.CostEstimate(
            flops=2 * pairs * (lanes + value), transcendentals=pairs,
            bytes_accessed=sum(x.size * x.dtype.itemsize
                               for x in operands + outs)),
        interpret=interpret, name=LATENT_KERNEL,
    )(lo, hi, *operands)
    return out, lax.bitcast_convert_type(sets, jnp.uint32)


def latent_indexed_attention(q, kv, k_pe, gate, keys, tau, cut, start,
                             nope: int, value: int,
                             interpret: bool = False):
    """Latent attention (MLA) in its expanded form under the sets, from
    ``ops/mla.queries``' result to the output product's operand;
    dots3-note's full layers (``models/dots3_note/network.py``) are the
    caller.

    ``q`` (heads, T, lanes) as ``ops/mla.queries`` wrote it: a head's
    ``[q_nope | q_pe rotated | 0]``, scaled and rounded; ``kv`` (T,
    heads (own + value)) as the key-value latent's product wrote it, a
    head's ``[own key | value]`` (``ops/latent.key_lanes``);
    ``k_pe`` (T, rotary) the one rotary key all heads share, rotated;
    ``gate`` (T, heads) float32, a head's result is multiplied by it;
    ``keys``, ``tau``, ``cut`` the sets; ``start`` (T, 1) int32.
    -> ((T, heads value) in ``kv``'s dtype; the sets as bits (T, keys a
    tile) uint32 as :func:`indexed_attention` writes them).

    **Why a kernel beside** :func:`indexed_attention`. That one is a
    grouped-query kernel: eight query heads share a key head's 128
    lanes, all 32 heads' queries of a 256-query tile are 4 MiB, and the
    mask is built once a (query tile, key tile) pair for all of them.
    Here every one of 128 heads has a key of its own (192 columns, 256
    lanes with the shared rotary key's) and a value of 128: a head's
    keys and values are read once a *query tile*, so the operations a
    byte of them are the queries a tile (256 queries a tile stand at
    the v5e's ridge of 240 operations a byte; 1,024 are four times
    over it), and all heads of such a tile are 64 MiB of q: a step
    holds ``per`` heads, a query tile walks its key tiles once a group
    of heads, and the group builds the mask again from the same sort
    keys (the sets' bits are the first group's to write). Keye-VL's
    kernel is left as PR 54 left it, to the bit. Nothing with a head
    axis is copied in HBM: q is the queries' product's own result,
    ``kv`` the key-value latent's, the output the last product's
    operand; the shared rotary key is one (T, 128) array whose product
    with q's lanes behind the own key stands beside the own key's.

    **The sweep** (my chip runs, PR 55; one TPU v5 lite, 128 rows of 128
    tokens as one / two / three requests, 128 heads of 128 + 64 / 128;
    ``scripts/indexed_sweep.py --shape=latent``, which sets the module's
    ``_LATENT_TILES`` for each form: the kernel takes no tile argument;
    ms a layer by the host's clock around a jitted call; every form
    gives the dense form's values, largest difference 0.0079 at a spread
    of 0.174, and its sets to the bit). At
    (queries a tile, keys a tile, heads a step): (512, 512, 16) **84.7 /
    47.4 / 38.0**, which stands; (256, 512, 16) 89.2 / 51.3 / 40.1;
    (512, 512, 8) 91.9 / 52.2 / 42.2; (1024, 512, 8) 91.7 / 52.1 / 43.6;
    (256, 512, 8) 98.7 / 58.1 / 46.2; (1024, 512, 4) 106.0 / 60.7 / 51.1;
    (512, 512, 4) 108.2 / 63.0 / 51.6; (1024, 512, 2) 131.9 / 77.3 /
    65.9; (2048, 512, 2) 133.2 / 79.2 / 72.4; (1024, 512, 16) runs out
    of VMEM: the
    heads a step decide, the queries a tile hardly — a step's fixed part
    (the pair's sort keys, 1 MiB at 512 x 512, its three comparisons, the
    mask as a float tile) is paid once a group, so it is the *groups* a
    (query tile, key tile) pair has that cost, not its bytes of keys and
    values. The two products of every head over the causal pairs are
    55.0 / 27.5 / 18.3 ms at the matrix unit's peak (over the tiles the
    kernel visits in a pool one request fills, 57.6): the kernel stands
    at 65% of the causal pairs' floor for one request, 58% for two and
    48% for three (more diagonal tiles and more first tiles a request).
    Beside it ``index_keys`` at 64 heads of 128 reads 17.5 / 10.9 / 9.4
    ms (its operations' floor 11.0 / 5.5 / 3.7) and ``thresholds`` 10.8
    whatever the pool holds."""
    heads, tokens, lanes = q.shape
    return _latent_call(
        q, kv, latent.shared_key(k_pe, nope, lanes), gate, keys, tau, cut,
        start, nope=int(nope), value=int(value),
        tiles=latent_tiles(tokens), interpret=bool(interpret))
