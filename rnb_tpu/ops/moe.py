"""Sparse experts for a layer that holds a *share* of them: routing
over every expert the model has, and the held experts' part of the
result as one grouped matrix product.

The router scores all ``num_experts`` in float32 and picks ``top_k``
by the family's rule, which is :func:`route`'s arguments (Nemotron-H:
``sigmoid``, the largest of score + correction bias, weights = the
chosen scores over their sum; DeepSeek-V2: ``softmax``, the best
groups of experts first, weights = the chosen scores as they are),
times a scaling factor — over all the chosen, held here or not. An
expert is two matrices with ``relu^2`` between them or three, gated
(``gate``). The layer is told which experts it holds (``held``: their
ids, in the order of the stacked weights). Every (token, chosen
expert) pair whose expert is held is computed, whatever the
imbalance: pairs are sorted by held expert, the tokens gathered in
that order, and the projections of ``relu(x U_e)^2 D_e`` (or of
``(silu(x G_e) * (x U_e)) D_e``) run as grouped matrix products over
the groups; pairs whose expert is held elsewhere (or whose token is padding) sort
behind the last group, belong to no group and cost no product. On one
chip there is no exchange: what the absent experts would add is left
out.

The pairs go into expert order once and come back once. In: one
gather of the tokens' rows by the sorted order. Back: the inverse of
that order (a scatter of 49,152 int32, not of rows), one gather of the
second product's rows, and one fused pass that masks, weights and sums
a token's ``top_k`` rows in float32. The mask is a ``where`` on the
*rows*: the kernel leaves whatever it finds behind the last group, and
a zero weight would turn a NaN there into a NaN in the sum. (Scaling
and masking every row, scattering the rows onto zeros and summing them
cost 57 ms of a 215 ms dispatch on the v5e, where this costs 33: XLA
sorts such a scatter's indices again and makes two passes over the
array; my chip runs, PR 29.)

How many pair rows move is the caller's to size (``capacity``; PR 43).
Without one, every array with a pair axis has all T k rows, held or
not. A layer that holds an eighth of the experts then gathers in,
passes over and gathers back eight rows for each one a product reads:
K-EXAONE's 131,072 rows of 6,144 cost 34 ms a layer around 10 ms of
products (my chip runs, PR 43). With a capacity C
(:func:`pair_capacity`: twice the pairs a uniform router sends the held
experts, from the shapes alone; None at half the experts or more, where
twice the share is everything) the held pairs — the first
``counts.sum()`` of the sorted order — go through in passes of C rows,
a loop that runs while held pairs are left, so nothing is dropped or
deferred whatever the router does (every pair held: four passes, 100
ms against 95). A pass gathers C tokens' rows,
runs the products at M = C over its share of each group (the clipped
differences of ``cumsum(counts)``) and adds the rows to the tokens'
sums. Pointing the unheld pairs of the old way back at a zero row
would not do: its gather would still write and re-read T k rows of
float32, the largest part of the loss. So the way back is a kernel
(:func:`combine_pairs`) that walks the served pairs, sorted by (token,
choice), copies each one's row out of the product's result and adds it
times its weight to its token's sum — product, then sum, j = 0 ... k-1,
as the masked sum does: one pass gives the sums of all T k rows to the
bit (read on the chip). A copy moves whole (8, 128) tiles and a row of
a float32 ``[C, hidden]`` array is one sublane of each of its tiles
(Mosaic refuses the slice), so the result first goes into ``[C, 8,
hidden / 8]``, a row as eight sublanes, in one pass of XLA's, and the
sums come back from that form in another: 2.5 and 1.2 ms a layer around
a kernel of 2.0 (16 thousand copies of 24 KB, 64 in flight, and the
read of the loop's zero carry; a first form that tested all T k pairs
in its scalar loop took 6.5). XLA's
``zeros.at[token].add(rows)`` over the C rows read 8.4 ms and 3.5 for
its index sort, and does not fix the order of a token's additions.

The pairs lie expert-choice-major, ``(k, T)``: pair ``j * T + t`` is
token ``t``'s ``j``-th chosen expert, and every array with a pair axis
keeps the ``top_k`` choices as its *leading* axis. The device tiles an
array's second-minor axis by 8 sublanes. Token-major, ``(T, k)``, the
way back ended in ``out[place].reshape(T, k, hidden)``: float32 with
k = 6 on that axis, so the reshape was a real copy into a padded
``[8192, 8, hidden]`` (704 MB written at Nemotron's 2688, 1.34 GB at
DeepSeek-V2's 5120: 1.92 and 3.65 ms an expert layer) and the masked
sum read the padded form. As ``[k, 8192, hidden]`` the two minor axes
are whole tiles, the reshape is a bitcast and the sum runs over the
leading axis at the memory's speed: the experts' part of a 64-row
dispatch 109.4 -> 88.0 ms and 34.2 -> 38.6 requests/s for Nemotron,
118.8 -> 91.4 ms and 15.1 -> 16.1 for DeepSeek-V2 (my chip runs, PR
34; 8 and 10 ms of that are the compiler's, which now keeps the source
of the gather in its fast memory: PERF.md section 6). Within a group
the rows lie sorted by (j, t) and not by (t, j): a row's product does
not depend on its neighbours. The sum adds the rows
j = 0 ... k-1 in that order (read on the chip, to the bit); over the
padded sublanes it had been ``((r0 + r4) + r2) + ((r1 + r5) + r3)``,
so a result may differ from the older form's in its last bit. Callers
pass and get ``(T, k)`` and ``(T, hidden)`` as before.

The grouped product is JAX's Pallas kernel
(``jax.experimental.pallas.ops.tpu.megablox.gmm``: one grid step a
(row tile, group) pair that holds rows, float32 accumulator in VMEM).
The first traced run on the v5e (PR 28) read ``lax.ragged_dot`` at 47%
of the device's time and 12 to 25 TFLOP/s of useful work; the kernel
with :func:`gmm_tiling`'s wide tiles read 35 to 64 on the same shapes.
A grid step multiplies its whole row tile and stores the group's rows
alone, so the row tile is 128 wherever a whole K fits beside it
(:func:`gmm_tiling`; PR 44), and :func:`held_experts` returns beside
the pairs each held expert served the rows the first product's steps
multiplied for them (:func:`gmm_visits`): the ``gmm_rows`` counter of a
family's ``network.COUNTERS``. Off the TPU the same kernel runs in
Pallas's interpret mode.

The weights lie in memory as the kernel reads them. Its operands must
be row-major, and the device keeps an array whose last axis is no
multiple of 128 lanes with the axis before it innermost: the first
matrix as published, ``[held, 2688, 1856]`` (1856 = 14.5 x 128), was
stored ``{1,2,0}`` and copied to ``{2,1,0}`` in front of the kernel,
638 MB read and written every E block of every dispatch (2.0 ms, of
which 1.6 had hidden the wait for the gather in front of it: without
the copy that gather reads 2.1 ms for 0.4; my chip runs, PR 29). It is
therefore stored ``[held, inner, hidden]`` (2688 = 21 x 128 innermost,
like the second matrix) and the kernel contracts it over its last
axis (``transpose_rhs``); ``checkpoint.py`` draws it as published and
transposes it once, at set-up.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox import gmm

_HIGHEST = lax.Precision.HIGHEST


def _tile(extent: int, whole_under: int, cap: int, lane: int = 128) -> int:
    """A tile of one dimension: the whole of it up to ``whole_under``,
    else the largest multiple of ``lane`` from ``cap`` down to half of
    it that divides it, else ``cap`` (the kernel masks the ragged last
    tile)."""
    if extent <= whole_under:
        return extent
    for tile in range(cap, cap // 2 - 1, -lane):
        if extent % tile == 0:
            return tile
    return cap


def gmm_visits(counts, tm: int):
    """The grid steps the grouped product takes a column tile at a row
    tile of ``tm`` over groups of ``counts`` rows (int32, in order; rows
    behind the last group belong to none): one for every (row tile,
    group) pair that holds rows, so a group that starts inside a tile
    visits that tile again. Each step multiplies the whole ``tm`` rows
    and keeps the group's (megablox's ``make_group_metadata`` counts the
    same, ``tests/test_moe_tiles.py``)."""
    ends = jnp.cumsum(counts)
    starts = ends - counts
    return jnp.where(counts > 0, (ends + tm - 1) // tm - starts // tm,
                     0).sum()


#: what :func:`gmm_tiling` lets a grid step's blocks take of the 16 MiB
#: of VMEM a kernel may use. Conservative on purpose: the compiler's own
#: limit lies between (512, 1856, 896), 15.96 MB by :func:`_tile_bytes`,
#: which compiles, and (256, 2688, 1024), 16.9, which does not; the
#: account is not the compiler's, so it keeps 2 MiB of room under them
_VMEM = 14 * 2 ** 20


def _tile_bytes(tm: int, tk: int, tn: int) -> int:
    """What a grid step of the grouped product holds in VMEM: both
    bfloat16 operand blocks and the float32 result's twice (the
    pipeline's two buffers) and the float32 accumulator."""
    return 4 * (tm * tk + tk * tn) + 12 * tm * tn


def gmm_tiling(m: int, k: int, n: int):
    """The grouped product's tiles (m, k, n) for ``rows`` (M, K) times
    (K, N) a group, from the shapes alone: a 128-row tile beside a whole
    K wherever that fits in VMEM (*narrow*), else PR 28's *wide* tiles.

    *Wide* tiles: the largest row tile that divides M (512), K whole up
    to 2,048 and N up to 1,024, else cut (:func:`_tile`).
    Read on the v5e at M 49,152 (my chip runs): K 2688 -> N 1856 with
    (512, 896, 1024), K 1856 -> N 2688 with (512, 1856, 896): 3.5 and
    3.0 ms a call in the cell's trace, against 20.5 and 18.6 ms of
    ``lax.ragged_dot`` (PR 28); the first product's weights as
    (G, N, K) 4.97 ms a jitted call by the host's clock, 0.6 ms of
    group metadata in it, and 6.99 ms as (G, K, N), 2.0 of them the
    relayout in front (PR 29). A whole N with 384 rows or more, and a
    whole K with 256 x 1,024 or 512 x 640 of the result, run out of
    VMEM. K 2048 -> N 6144 (K-EXAONE's second product) runs out of VMEM
    with these, a whole K of 2,048 against 1,024 columns of a 512-row
    tile: 18 MB of the 16; that caller brings its own (256, 2048, 1024)
    (``models/exaone_moe/network.py``; PR 42's sweep), which the narrow
    (128, 2048, 1024) below was not read against.

    *Narrow* tiles (PR 44): the kernel takes a grid step for every (row
    tile, group) pair that holds rows and multiplies the whole tile in
    each (:func:`gmm_visits`), so a 512-row tile over groups of 320 rows
    multiplies two and a half rows for each one it keeps. A 128-row
    tile beside a *whole* K: consecutive steps inside a group then keep
    the group's weight block where it is, while at a cut K every step
    fetches it again and the smaller tile loses. Columns: all of N where
    that fits in VMEM beside the whole K, else the widest even split of
    N into whole lanes up to 1,024, else 1,024 with a ragged last tile
    (:data:`_VMEM`); where not even 512 columns fit beside the whole K
    (K-EXAONE's 6,144), or 128 does not divide M, the wide tiles stay.
    The sweep (``scripts/gmm_sweep.py``), the kernel alone
    under a real dispatch's group sizes (the pairs each held expert
    served in one full dispatch of the cell's prompts through the stack
    at its real widths, first and last expert layer: max over mean 1.8
    and 2.0, 1.4 and 1.5, 1.2 and 1.1; a jitted call by the host's clock,
    the group metadata in it; ms a call, the mean of both layers; my
    chip runs, PR 44; *: the wide tiles, >: what this rule returns):

    ==================  =========================  ====================
    product, M, groups  tiles: ms                  rows kept, %
    ==================  =========================  ====================
    Nemotron-H first,   * (512, 896, 1024) 4.82;   41.6 at 512, 59.2 at
    K 2688 -> N 1856,   (256, 896, 1024) 4.42;     256, 74.6 at 128
    49,152, 64 of 384   (128, 896, 1024) 5.36;
    rows                (384, 896, 1024) 4.43;
                        (256, 896, 1856) 4.05;
                        > (128, 2688, 1024) 3.78;
                        (128, 2688, 896) 4.30;
                        (128, 2688, 640) 3.72;
                        (256, 2688, 640) 3.70;
                        (256, 2688, 512) 3.84
    Nemotron-H second,  * (512, 1856, 896) 3.70;   as above
    K 1856 -> N 2688    (384, 1856, 896) 3.33;
                        (256, 1856, 896) 3.23;
                        > (128, 1856, 896) 3.14;
                        (256, 1856, 1024) 3.43;
                        (128, 1856, 1024) 3.35
    Qwen3-Next first,   * (512, 2048, 512) 2.47;   38.2 at 512, 55.3 at
    K 2048 -> N 512,    (320, 2048, 512) 2.15;     256, 71.5 at 128,
    163,840, 256 of     (256, 2048, 512) 2.11;     83.5 at 64
    320 rows            > (128, 2048, 512) 2.09;
                        (64, 2048, 512) 2.28
    Qwen3-Next second,  * (512, 512, 1024) 3.27;   as above
    K 512 -> N 2048     (256, 512, 1024) 3.10;
                        (128, 512, 1024) 2.97;
                        (256, 512, 2048) 2.89;
                        > (128, 512, 2048) 2.60;
                        (64, 512, 2048) 2.60
    DeepSeek-V2 first,  * (512, 1024, 768) 1.51;   36.2 at 512, 53.4 at
    K 5120 -> N 1536,   (256, 1024, 768) 1.34;     256, 69.5 at 128
    49,152, 20 of 307   (128, 1024, 768) 1.73;
    rows                (256, 2560, 768) 1.33;
                        (128, 2560, 768) 1.73;
                        (256, 1024, 1536) 1.19;
                        > (128, 5120, 512) 1.06;
                        (128, 5120, 384) 1.08;
                        (256, 5120, 384) 1.09;
                        (256, 5120, 256) 1.13
    DeepSeek-V2         * (512, 1536, 1024) 1.42;  as above
    second, K 1536 ->   (384, 1536, 1024) 1.28;
    N 5120              (256, 1536, 1024) 1.24;
                        > (128, 1536, 1024) 1.18;
                        (256, 1536, 1280) 1.22;
                        (128, 1536, 1280) 1.25
    ==================  =========================  ====================

    A whole K changes how the float32 partial sums associate: 1.4e-6 and
    2.4e-6 of results of order one against the cut K's, a smaller row
    tile nothing (equal to the bit). K-EXAONE's products were not swept,
    so no 128-row tile was read against groups of 1,024 rows: its first
    products' K of 6,144 fits whole beside 256 columns at most and its
    second brings its own tiles, so all five row buckets keep the wide
    tiles (``tests/test_moe_tiles.py``)."""
    tm = next(t for t in (512, 256, 128, 64, 32, 16, 8, 1) if m % t == 0)
    columns = [n] + [n // parts for parts in range(2, n // 512 + 1)
                     if n % (128 * parts) == 0 and n // parts <= 1024]
    if n > 1024:
        columns.append(1024)
    if tm >= 128:
        for tn in columns:
            if _tile_bytes(128, k, tn) <= _VMEM:
                return (128, k, tn)
    return (tm, _tile(k, 2048, 1024), _tile(n, 1024, 1024))


def grouped_matmul(rows, weights, counts, interpret: bool,
                   transposed: bool = False, tiling=None):
    """``rows`` (M, K), sorted by group; ``weights`` (G, K, N), or
    (G, N, K) where ``transposed``; ``counts`` (G,) int32 rows of each
    group, in order; ``tiling``: the caller's own (m, k, n), where
    :func:`gmm_tiling`'s do not fit its widths. -> float32 (M, N); what
    lies behind the last group's rows is unspecified."""
    m, k = rows.shape
    n = weights.shape[1] if transposed else weights.shape[2]
    return gmm(rows, weights, counts, preferred_element_type=jnp.float32,
               tiling=tiling or gmm_tiling(m, k, n),
               transpose_rhs=transposed, interpret=interpret)


def route(x, w_router, b_corr, top_k: int, scaling: float, *,
          score: str = "sigmoid", n_group: int = 1, topk_group: int = 1,
          renormalise: bool = True):
    """-> (ids (T, top_k) int32, weights (T, top_k) float32). ``x``
    (T, hidden); ``w_router`` (hidden, E).

    The rule is the family's: ``score`` (``sigmoid`` or ``softmax``
    over the E logits, float32); ``b_corr`` (E,) a correction bias
    added for the choice alone, or None; ``n_group`` > 1: the experts
    lie in that many groups of E / n_group, a group's score is its
    best expert's, the best ``topk_group`` groups stay and the rest
    are masked to 0 before the ``top_k`` (group-limited greedy
    routing: a token's experts lie on ``topk_group`` devices at most);
    ``renormalise``: the chosen scores over their sum. Times
    ``scaling``."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=_HIGHEST)
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError("router score %r" % (score,))
    choice = scores if b_corr is None \
        else scores + b_corr.astype(jnp.float32)
    if n_group > 1:
        grouped = choice.reshape(choice.shape[0], n_group, -1)
        _, best = lax.top_k(grouped.max(-1), topk_group)
        kept = (best[:, :, None] == jnp.arange(n_group)).any(1)
        choice = jnp.where(kept[:, :, None], grouped, 0.0) \
            .reshape(choice.shape)
    _, ids = lax.top_k(choice, top_k)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    if renormalise:
        picked = picked / picked.sum(-1, keepdims=True)
    return ids, picked * scaling


def held_slots(num_experts: int, held):
    """(num_experts,) int32: an expert's position among the held
    stacks (``held``: their ids, in the stacks' order), -1 where
    another chip holds it."""
    slots = [-1] * int(num_experts)
    for pos, expert in enumerate(held):
        slots[int(expert)] = pos
    return jnp.asarray(slots, jnp.int32)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def pair_capacity(tokens: int, k: int, held: int, num_experts: int):
    """The pair rows :func:`held_experts`' buffers hold at a time, from
    the shapes alone: the smallest multiple of 512 (the grouped
    product's widest row tile) that is at least twice the pairs a uniform
    router sends the held experts, ``2 tokens k held / num_experts``;
    None, the buffers of all ``tokens k`` pairs, where that is not under
    half of them (at a half share twice the share is everything)."""
    pairs = tokens * k
    capacity = -(-2 * pairs * held // (num_experts * 512)) * 512
    return capacity if 2 * capacity < pairs else None


def _expert_products(rows, counts, up, down, gate, interpret, down_tiling):
    """``rows`` (M, hidden) sorted by held expert, ``counts`` the rows
    of each -> (float32 (M, hidden): the expert's two or three products
    as grouped ones; int32: the rows the first product's grid steps
    multiplied, :func:`gmm_visits` at its row tile)."""
    tiling = gmm_tiling(*rows.shape, up.shape[1])

    def first(weights):
        return grouped_matmul(rows, weights, counts, interpret,
                              transposed=True, tiling=tiling)
    hidden = first(up)
    if gate is None:
        hidden = relu2(hidden)
    else:
        hidden = jax.nn.silu(first(gate)) * hidden
    return grouped_matmul(hidden.astype(rows.dtype), down, counts,
                          interpret, tiling=down_tiling), \
        gmm_visits(counts, tiling[0]) * tiling[0]


def held_experts(x, ids, weights, token_ok, held_slot, up, down,
                 interpret: bool = False, gate=None, down_tiling=None,
                 capacity=None):
    """The held experts' part of the layer's result: ``relu(x U_e)^2
    D_e`` or, where ``gate`` is given (stacked and stored like ``up``),
    the gated form ``(silu(x G_e) * (x U_e)) D_e``.

    ``x`` (T, hidden); ``ids``/``weights`` (T, k) from :func:`route`;
    ``token_ok`` (T,) bool, False on padding; ``held_slot`` (E,) int32:
    an expert's position in the stacks, or -1 where it is held
    elsewhere; ``up`` (held, inner, hidden), ``down`` (held, inner,
    hidden); ``interpret``: run the kernels in Pallas's interpret mode
    (off the TPU); ``down_tiling``: the second product's (m, k, n) where
    :func:`gmm_tiling`'s own do not fit the family's widths;
    ``capacity``: the pair rows the buffers hold at a time
    (:func:`pair_capacity`), None for all T k of them. -> (out (T,
    hidden) float32, counts (held,) int32: the pairs each held expert
    served, int32: the rows the first product's grid steps multiplied
    for them, :func:`gmm_visits`) and, with a capacity, the pair rows
    the buffers held (int32: the capacity times the passes the held
    pairs took)."""
    tokens, k = ids.shape
    held = up.shape[0]
    products = functools.partial(
        _expert_products, up=up, down=down, gate=gate, interpret=interpret,
        down_tiling=down_tiling)
    # every pair axis below is (k, T): pair j*T + t is token t's j-th
    # chosen expert (the module's docstring says why)
    slot = held_slot[ids.T]                             # (k, T)
    here = token_ok[None, :] & (slot >= 0)              # served on this chip
    flat_slot = jnp.where(here, slot, held).reshape(-1)
    order = jnp.argsort(flat_slot, stable=True)
    counts = jnp.bincount(flat_slot, length=held + 1)[:held] \
        .astype(jnp.int32)
    if capacity is not None:
        return _held_by_capacity(x, weights, order, counts, capacity,
                                 products, interpret)
    rows = x[order % tokens]                            # (k*T, hidden)
    out, multiplied = products(rows, counts)
    # the way back: where each pair lies in expert order (the inverse
    # of ``order``), and one gather of the product's rows
    place = jnp.zeros_like(order).at[order].set(
        jnp.arange(tokens * k, dtype=order.dtype), unique_indices=True)
    back = out[place].reshape(k, tokens, -1)
    # a pair that is not served here lies behind the last group: its
    # row is whatever the kernel left there (0 x NaN is NaN), so the
    # mask is on the rows and not a zero weight
    back = jnp.where(here[:, :, None], back * weights.T[:, :, None], 0.0)
    return back.sum(axis=0), counts, multiplied


def _held_by_capacity(x, weights, order, counts, capacity, products,
                      interpret):
    """:func:`held_experts` with buffers of ``capacity`` pair rows: the
    held pairs are the first ``counts.sum()`` of ``order``; a pass takes
    the next ``capacity`` of them through ``products`` and adds each
    token's weighted rows to its sum, in a loop that runs while held
    pairs are left (once, where the router keeps to twice its mean). One
    body for the first pass and the others: the stage program is traced,
    lowered and compiled a row bucket at every start, and a pass is
    three grouped products and the way back."""
    tokens, k = weights.shape
    pairs = k * tokens
    order = jnp.pad(order, (0, -(-pairs // capacity) * capacity - pairs))
    ends = jnp.cumsum(counts)
    weights = weights.T.reshape(-1)                     # by pair, (k, T)

    def one_pass(carry):
        first, acc, multiplied = carry
        chosen = lax.dynamic_slice(order, (first,), (capacity,))
        sizes = jnp.clip(ends - first, 0, capacity) \
            - jnp.clip(ends - counts - first, 0, capacity)
        token = chosen % tokens
        out, more = products(x[token], sizes)
        # the rows that hold a pair, in the order (token, choice): a
        # token's rows lie together, j = 0 ... k-1
        rows = jnp.arange(capacity, dtype=jnp.int32)
        key = jnp.where(rows < ends[-1] - first,
                        token * k + chosen // tokens, pairs)
        key, rows, weight = lax.sort((key, rows, weights[chosen]),
                                     num_keys=1)
        return first + capacity, combine_pairs(
            out, rows, key // k, weight, acc, interpret), multiplied + more

    first, acc, multiplied = lax.while_loop(
        lambda carry: carry[0] < ends[-1], one_pass,
        (jnp.int32(0), jnp.zeros((tokens, 8, x.shape[1] // 8), jnp.float32),
         jnp.int32(0)))
    return acc.reshape(tokens, -1), counts, multiplied, first


#: row copies in flight in :func:`combine_pairs`' kernel
_COPIES = 64


def _combine_kernel(starts_ref, rows_ref, tokens_ref, weights_ref, out_hbm,
                    *rest, tile: int):
    """One grid step: ``tile`` tokens' sums. In SMEM: ``starts_ref``
    where each tile's entries begin in the three lists, ``rows_ref`` a
    pair's row of ``out_hbm``, ``tokens_ref`` its token, ``weights_ref``
    its weight; then the sums so far, the sums, ``_COPIES`` rows of
    buffer and their DMA semaphores."""
    acc_ref, sum_ref, buf, sems = rest
    step = pl.program_id(0)
    first = starts_ref[step]
    entries = starts_ref[step + 1] - first
    sum_ref[...] = acc_ref[...]

    def copy(entry):
        slot = entry % _COPIES
        return pltpu.make_async_copy(out_hbm.at[rows_ref[first + entry]],
                                     buf.at[slot], sems.at[slot])

    def start(entry, carry):
        copy(entry).start()
        return carry
    lax.fori_loop(0, jnp.minimum(entries, _COPIES), start, 0)

    def add(entry, carry):
        copy(entry).wait()
        token = tokens_ref[first + entry] - step * tile
        # a token's entries follow each other, j = 0 ... k-1
        sum_ref[token] += buf[entry % _COPIES] * weights_ref[first + entry]

        @pl.when(entry + _COPIES < entries)
        def _():
            copy(entry + _COPIES).start()
        return carry
    lax.fori_loop(0, entries, add, 0)


def combine_pairs(out, rows, token, weight, acc, interpret: bool = False):
    """``acc`` and each token's sum of its served pairs' rows of ``out``
    times their weights, in float32, as one Pallas kernel that copies a
    served pair's row and touches no other.

    ``out`` (C, hidden) float32; the served pairs as lists of C, sorted
    by token and a token's in the order they are to be added: ``rows``
    int32 a pair's row of ``out``, ``token`` its token, ``weight``
    float32; behind the last served pair ``token`` says T and the other
    two are not read; ``acc`` float32 (T, 8, hidden / 8), the sums so
    far, and so the result: a row as eight sublanes, which is how one
    row can be copied (a copy moves whole (8, 128) tiles; ``out`` goes
    into that form in one pass of XLA's in front of the kernel)."""
    tokens, *row = acc.shape
    row = tuple(row)
    tile = next(t for t in (128, 64, 32, 16, 8, 4, 2, 1) if tokens % t == 0)
    starts = jnp.searchsorted(
        token, jnp.arange(tokens // tile + 1, dtype=jnp.int32) * tile) \
        .astype(jnp.int32)
    sums = pl.BlockSpec((tile,) + row, lambda i, *_: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_combine_kernel, tile=tile),
        out_shape=jax.ShapeDtypeStruct((tokens,) + row, jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(tokens // tile,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), sums],
            out_specs=sums,
            scratch_shapes=[pltpu.VMEM((_COPIES,) + row, jnp.float32),
                            pltpu.SemaphoreType.DMA((_COPIES,))]),
        input_output_aliases={5: 0},
        # two blocks of sums in and two out, 3 MiB each at 128 tokens of
        # 6,144, and the rows in flight: the default 16 MiB is too tight
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret, name="combine_pairs",
    )(starts, rows, token, weight, out.reshape((out.shape[0],) + row), acc)


def dense_expert(x, up, down, gate=None):
    """``relu(x U)^2 D``, or ``(silu(x G) * (x U)) D`` where ``gate`` is
    given, for one expert every token visits (the shared ones, a dense
    layer's feed-forward): inputs in their dtype, float32
    accumulation. -> float32."""
    hidden = jnp.dot(x, up, preferred_element_type=jnp.float32)
    if gate is None:
        hidden = relu2(hidden)
    else:
        hidden = jax.nn.silu(jnp.dot(
            x, gate, preferred_element_type=jnp.float32)) * hidden
    return jnp.dot(hidden.astype(x.dtype), down,
                   preferred_element_type=jnp.float32)
