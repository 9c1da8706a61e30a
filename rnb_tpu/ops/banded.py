"""Grouped-query attention under a *window* over a packed pool of rows:
a query reads the ``window`` keys of its request that end with its own.
One Pallas TPU kernel, K-EXAONE's sliding layers' own
(``models/exaone_moe``), from the three products' results to the operand
of the fourth; and, since PR 55, a second one at the end of the module
for *latent* attention under a window (``latent_banded_attention``:
dots3-note's sliding layers, ``models/dots3_note`` — every head its own
key of 256 lanes and value of 128, a window of 513, no norm, nothing to
turn; its text and sweep stand at the function). K-EXAONE's kernel is
as PR 52 left it.

The pool holds ``rows`` of ``Q`` tokens; a request is a run of
consecutive rows (``ops/segattn.py``'s text). Under a window of 128 keys
and a context of 16k tokens the causal triangle is the wrong picture:
what a block of queries may read is a *band*, as wide as the block and
the window together, wherever the block lies. So the kernel has no
table, no inner loop over key blocks and no running softmax.

*A step* is (query block i, key-value head g), both grid axes
``parallel``. The block is ``B`` tokens, ``B >= window - 1`` and ``B``
dividing the pool (:func:`band_block`: 128 at the published window of
128), so the queries of block i read two key blocks, their own and the
one before it: ``k`` and ``v`` are passed twice, block ``max(i - 1, 0)``
and block ``i``. A pair (query t, key s) is kept iff ``s <= t``, ``s > t
- window`` and ``s >= start[t]``, the first token of t's request
(``ops/indexed.token_table``'s form); ``s`` is counted from the real
block index, so that in front of the pool's first block stands a block
of negative positions that masks itself. Every query keeps its own key.
The band is whole in one step: one plain softmax.

*Eight query heads a step.* The ``Hq / Hk`` query heads that read key
head g share the step: the block's keys are normed, turned and laid out
once for the eight, 1,024 steps a layer. Each head's (B, D) slice has
its own product for the scores, against 256 keys, and its own for the
values, and the eight go through each line of the kernel together (the
sweep has what the other orders cost).

*Operands as the products wrote them.* ``q`` is the float32 ``(T, Hq
D)`` result of the layer's first product, read by the block ``(B, (Hq /
Hk) D)`` at column block g; ``k`` float32 ``(T, Hk D)``, ``v`` ``(T, Hk
D)`` in the activations' dtype; the result is written ``(T, Hq D)`` in
that dtype at q's column block: the last product's operand. A head is a
lane slice (D = 128 is a lane tile). Between the products no array with
a head axis exists in HBM: no pad, no transpose.

*QK-norm and rotary are the kernel's first lines*, a head's ``(B, D)``
slice at a time, in float32: ``x rsqrt(mean(x^2) + eps) weight``, then
``x cos + roll(x, D / 2) sin`` with the tables ``[cos | cos]`` and
``[-sin | sin]`` (:func:`band_tables`: ``ops/rope.turn_tables``'s
numbers, built once a dispatch for all the layers and read a ``(B, D)``
block a step: the same float32 numbers as ``ops/rope.rotate``'s
half-split form), then ``D^-1/2`` on q and one rounding to the
activations' dtype. Keys are normed and turned in both steps that read
them (an eighth of q's work, twice). Scores and softmax are float32; the
probabilities go into the values' product as float32, as splash handed
them to its own (interpreted, the product then keeps them; compiled,
Mosaic rounds them: the sweep below), and are summed in float32 beside
it; the division stands behind the product.

Off the TPU the same kernel runs in Pallas's interpret mode.

**What it replaced** (PR 52). Until then a layer with a window ran
``ops/segattn.py``'s kernel, splash, under its local mask with a table
cut to the band (``window_table``: (query blocks, steps)), one query
head a step. On the v5e, 128 rows of 128 tokens, 64 / 8 heads of 128, a
window of 128, that kernel's call alone (my chip runs, PR 42; one, two
and three requests in the pool read alike, within 0.2 ms): ms at
(queries a tile, keys a tile, keys a step) and the steps a query block:
(512, 512, 512) **6.2-6.3**, 2; (512, 256, 256) 7.2, 3; (256, 256, 256)
7.5, 2; (256, 256, 128) 7.6; (512, 512, 256) 7.6; (1024, 512, 512) 7.7,
3; (1024, 256, 256) 8.0, 5; (1024, 1024, 512) 8.6, 2; (512, 128, 128)
9.8, 5; (256, 128, 128) 10.0, 3; (2048, 256, 256) 10.9; (1024, 128, 128)
11.0, 9; (128, 128, 128) 11.6, 2: the smallest tiles computed least and
lost to the grid's 16,384 steps of 0.7 us, the largest computed eight
times the band. Under the causal table the same layer took 17.5 (three
requests, 57 tiles), 21.4 (two, 76) and 33.9 ms (one, 136). Around that
kernel the mixer made float32 passes for the norms, the rotary (a half
materialised and concatenated), the scale and the cast, a pad and a
transpose for each of q, k and v, and a transpose back: 6 ms a layer
beside the kernel's 5.4 at the mix's mean dispatch (PERF.md section 5,
PR 43). The window's own work (each query against 128 keys; q, k, v read
and the result written once in bfloat16) is 0.74 ms of the chip's
memory bandwidth.

**The sweep** (my chip runs, PR 52; one TPU v5 lite, the same 128 rows
and widths, this kernel's call alone with its first lines inside; ms).
``scripts/banded_sweep.py`` reads the kernel as it stands at 128 and at
256 queries a step and the passes it replaced; every other variant below
was an edit of the kernel's body in a probe that is not in the tree (the
chip tool's log of PR 52 holds its lines). *The first form* laid the
eight heads' rows under each other, (head, query), for one product of
1,024 x 128 against 256 keys and one for the values, and rounded the
probabilities to bfloat16 in front of theirs: **2.95** at 128 queries a
step, 3.63 at 256 (half the steps, twice the masked pairs, 64 MiB of
VMEM allowed). On that form: ``q`` read as bfloat16 (a first product
that rounds) 2.96 and 3.61: no faster, for a second rounding of q (the
check's largest difference 0.0148 for 0.0096), so q stays float32 - the
kernel is not bound by its bytes (1.04 GB a layer, 1.27 ms). The
probabilities handed to the values' product in float32 3.02-3.04, the
same result to the last bit: Mosaic's product at default precision
rounds float32 operands to bfloat16 itself (a probe of 1,024 x 256 x 128
read the same largest error 0.0658 for float32 operands as for bfloat16
ones, and 6e-6 at ``highest``), so splash's float32 probabilities went
into its product as bfloat16 too. They are float32 here for the
interpreted kernel's sake, which the tests hold to the reference: there
the product keeps what it is handed, as splash's did, and a cast would
be a rounding the parent's tests never saw. The softmax over all heads'
rows at once 2.99; the halves' swap as a product with a permutation at
``highest`` in the place of the lane rotation 3.07, the same bits. Where
the 2.95 went, by leaving a line out (wrong results, times only):
without the rotary's lane rotation 2.00, without the norm's mean 2.19,
without the row maximum 2.55, without the row sum 2.87, without the
exponential 2.93, without the division 2.91; without rotation, mean,
maximum, sum and exponential together 1.90, which is the copies, the two
products and the plain multiplies. *The form that stands* takes the
copies out: with float32 probabilities, the values' product a head and
the scores' still one 3.01-3.03; both products a head, each head through
all its lines before the next 3.12; **both products a head, the eight
heads through each line together 2.59-2.60** (2.64-2.65 with the keys
transposed once in front), the same bits as the first form in every
case. What is left is bound by the unit that moves values across lanes
(a step rotates 160 registers and reduces 416 along their lanes), not by
bytes and not by the matrix unit. The passes this kernel replaced, as
XLA runs them without a kernel between them (norms, rotary, scale and
cast, three pads and transposes, one transpose back and one sum to hold
them apart): 10.8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rnb_tpu.ops import latent, rope

#: the kernel's name in the device's trace and in the scope table
KERNEL_NAME = "banded_attention"

#: what a score outside the band is set to (splash's own)
_MASKED = -0.7 * float(np.finfo(np.float32).max)

#: a block's rows are whole sublane tiles of the activations' dtype
_SUBLANES = 16


def band_block(tokens: int, window: int) -> int:
    """Queries a step for a pool of ``tokens``: the least count of whole
    sublane tiles that divides the pool and is at least ``window - 1``
    (two blocks then hold any query's band), else the whole pool."""
    return next((b for b in range(_SUBLANES, tokens, _SUBLANES)
                 if tokens % b == 0 and b >= window - 1), tokens)


def band_tables(row_start, qlen: int, inv_freq):
    """What the kernel reads beside a layer's q, k and v, the same for
    every layer of a dispatch: ``row_start`` (rows,) int32, rows of
    ``qlen`` tokens, ``inv_freq`` (D // 2,) the rotary frequencies.
    -> (cos, sin, start): float32 (T, D) ``[cos | cos]`` and ``[-sin |
    sin]`` of each token's position inside its request, so that
    ``ops/rope.rotate(x)`` is ``x cos + roll(x, D / 2) sin``; int32 (T,
    1) the first token of each token's request."""
    half = len(inv_freq)
    cos, sin = rope.turn_tables(
        rope.pool_positions(row_start, qlen).reshape(-1), inv_freq, 0,
        2 * half)
    sign = np.repeat(np.float32([-1.0, 1.0]), half)
    start = jnp.repeat(row_start.astype(jnp.int32) * qlen, qlen)
    return cos, sin * sign, start[:, None]


def band_start(row_start, qlen: int):
    """int32 (T, 1): the first token of each token's request
    (:func:`band_tables`' third, alone: a layer that turns nothing reads
    no other table)."""
    start = jnp.repeat(row_start.astype(jnp.int32) * qlen, qlen)
    return start[:, None]


def band_tiles(tokens: int, block: int):
    """(the steps a key-value head takes, the (``block``, 2 ``block``)
    tiles on or under the diagonal: what a kernel that walked the causal
    triangle at this kernel's tile sizes would run)."""
    steps = tokens // block
    return steps, int((np.arange(steps) // 2 + 1).sum())


def _first_lines(x, weight, cos, sin, eps: float, act, scale=None):
    """A head's (B, D) float32 slice as its product wrote it -> in ``act``, as
    the kernel's products read it: through the head's RMS norm
    (``weight`` (1, D) float32, with ``eps``), turned by its tokens'
    positions (``cos``, ``sin`` (B, D): :func:`band_tables`'), times
    ``scale`` where one is given, all in float32, and rounded once."""
    x = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    x = x * weight
    x = x * cos + pltpu.roll(x, x.shape[1] // 2, 1) * sin
    return (x if scale is None else x * scale).astype(act)


def _kernel(*refs, window: int, dim: int, eps, pair_eps=None):
    """One query block of one key-value head. ``q_ref`` (B, per * D)
    and ``k1_ref`` (B, D) as their products wrote them, ``v1_ref`` (B,
    D) in the activations' dtype; ``k0_ref``, ``v0_ref`` the same of
    the block before it (of this block again at the pool's first);
    ``start_ref`` (B, 1) the first token of each query's request;
    ``o_ref`` as ``q_ref``, in the activations' dtype.

    With a head norm (``eps`` not None: K-EXAONE's) ``q_ref`` and the
    keys are float32, ``cos1_ref``, ``sin1_ref`` (B, D) are the block's
    rotary tables and ``cos0_ref``, ``sin0_ref`` the block before's,
    ``qw_ref``, ``kw_ref`` (1, D) the norms' weights in float32:
    :func:`_first_lines`. Without one q comes scaled and rounded, and
    nothing is turned.

    With ``pair_eps`` (differential attention, Phi-4-mini-flash's:
    :func:`differential_banded_attention`) a "head" of ``D`` lanes is a
    *pair* of heads of ``D / 2`` — ``[q1 | q2]``, ``[k1 | k2]``, ``[v1 |
    v2]`` — with two softmaxes, ``q1 . k1`` and ``q2 . k2``, over the
    one value of ``D`` columns; the last lines are ``P1 V - lambda P2
    V``, the RMS norm over the ``D`` columns and its weight:
    ``lam_ref`` (1, D) float32 ``lambda`` over the lanes, ``sub_ref``
    (1, D) the norm's weight times the layer's scale."""
    if eps is not None:
        (q_ref, k0_ref, k1_ref, v0_ref, v1_ref, cos0_ref, cos1_ref,
         sin0_ref, sin1_ref, start_ref, qw_ref, kw_ref, o_ref) = refs
    else:
        (q_ref, k0_ref, k1_ref, v0_ref, v1_ref, start_ref, lam_ref,
         sub_ref, o_ref) = refs
    i = pl.program_id(0)
    block = q_ref.shape[0]
    per = q_ref.shape[1] // dim
    act = v1_ref.dtype

    if eps is not None:
        cos, sin = cos1_ref[...], sin1_ref[...]
        q_weight, k_weight = qw_ref[...], kw_ref[...]
        # the scores' scale goes onto the float32 queries, before their
        # one rounding
        q = [_first_lines(q_ref[:, h * dim:(h + 1) * dim], q_weight, cos,
                          sin, eps, act, dim ** -0.5) for h in range(per)]
        k = jnp.concatenate([
            _first_lines(k0_ref[...], k_weight, cos0_ref[...],
                         sin0_ref[...], eps, act),
            _first_lines(k1_ref[...], k_weight, cos, sin, eps, act)],
            axis=0)
    else:
        q = [q_ref[:, h * dim:(h + 1) * dim] for h in range(per)]
        k = jnp.concatenate([k0_ref[...], k1_ref[...]], axis=0)
    if pair_eps is not None:
        # a pair's two heads against the pair's keys whole, the other
        # head's lanes zeroed: the matrix unit's columns are 128 either
        # way (``ops/ssd.py``'s lanes), and nothing is shifted along them
        first = lax.broadcasted_iota(jnp.int32, (block, dim), 1) < dim // 2
        q = [jnp.where(first == mine, pair, jnp.zeros_like(pair))
             for pair in q for mine in (True, False)]
    v = jnp.concatenate([v0_ref[...], v1_ref[...]], axis=0)
    t = i * block + lax.broadcasted_iota(jnp.int32, (block, 2 * block), 0)
    at = (i - 1) * block \
        + lax.broadcasted_iota(jnp.int32, (block, 2 * block), 1)
    keep = (at <= t) & (at > t - window) & (at >= start_ref[...])
    # a head's two products are its own, and the heads go through each
    # line together: the module's sweep has both choices' times
    s = [lax.dot_general(of_head, k, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
         for of_head in q]
    p, sums = [], []
    for of_head in s:
        of_head = jnp.where(keep, of_head, _MASKED)
        p.append(jnp.exp(of_head - of_head.max(axis=-1, keepdims=True)))
        sums.append(p[-1].sum(axis=-1, keepdims=True))
    o = [jnp.dot(of_head, v, preferred_element_type=jnp.float32)
         for of_head in p]
    if pair_eps is not None:
        lam, sub = lam_ref[...], sub_ref[...]
        for h in range(per):
            pair = o[2 * h] / sums[2 * h] \
                - lam * (o[2 * h + 1] / sums[2 * h + 1])
            pair = pair * lax.rsqrt(
                jnp.mean(pair * pair, -1, keepdims=True) + pair_eps)
            o_ref[:, h * dim:(h + 1) * dim] = (pair * sub) \
                .astype(o_ref.dtype)
        return
    for h in range(per):
        o_ref[:, h * dim:(h + 1) * dim] = (o[h] / sums[h]).astype(o_ref.dtype)


def _cost(steps: int, heads: int, per: int, block: int, dim: int,
          operands, out) -> pl.CostEstimate:
    """What a call costs, for the compiler that schedules around it
    (``ops/deltanet.py``'s ``_cost``): the two products of a step, an
    exponential a score, and every operand's bytes as the steps read
    them (what stands for the block before is read twice)."""
    pairs = steps * heads * per * block * 2 * block
    return pl.CostEstimate(
        flops=2 * 2 * pairs * dim, transcendentals=pairs,
        bytes_accessed=sum(x.size * x.dtype.itemsize
                           for x in operands + (out,)))


# a function under ``jit`` of its own: a stack's sliding layers call it
# with the same shapes, and the kernel is traced and lowered once for all
@functools.partial(jax.jit, static_argnames=("window", "eps", "interpret"))
def _band_call(q, k, v, q_weight, k_weight, cos, sin, start, *, window,
               eps, interpret):
    tokens, dim = cos.shape
    heads = k.shape[1] // dim
    per = q.shape[1] // k.shape[1]
    block = band_block(tokens, window)
    steps = tokens // block

    def spec(width, before=False, shared=False):
        """``width`` columns of a block of tokens: this step's or the
        block before it, key-value head g's or, of an array every head
        reads, the only ones."""
        def at(i, g):
            return (jnp.maximum(i - 1, 0) if before else i,
                    0 if shared else g)
        return pl.BlockSpec((block, width), at)
    weight = pl.BlockSpec((1, dim), lambda i, g: (0, 0))
    f32 = jnp.float32
    operands = (q, k, k, v, v, cos, cos, sin, sin, start,
                q_weight.astype(f32)[None, :], k_weight.astype(f32)[None, :])
    out = jax.ShapeDtypeStruct(q.shape, v.dtype)
    return pl.pallas_call(
        functools.partial(_kernel, window=window, dim=dim, eps=eps),
        grid=(steps, heads),
        in_specs=[spec(per * dim), spec(dim, True), spec(dim),
                  spec(dim, True), spec(dim), spec(dim, True, True),
                  spec(dim, shared=True), spec(dim, True, True),
                  spec(dim, shared=True), spec(1, shared=True), weight,
                  weight],
        out_specs=spec(per * dim), out_shape=out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=_cost(steps, heads, per, block, dim, operands, out),
        interpret=interpret, name=KERNEL_NAME)(*operands)


def banded_attention(q, k, v, q_weight, k_weight, tables, window: int,
                     eps: float, interpret: bool = False):
    """One layer's attention under a window, from the three products'
    results to the fourth's operand (the module's text).

    ``q`` (T, Hq D) and ``k`` (T, Hk D) float32, un-normalised, each key
    head serving Hq // Hk query heads; ``v`` (T, Hk D) in the
    activations' dtype; ``q_weight``, ``k_weight`` (D,) the head norms'
    weights, with ``eps``; ``tables`` :func:`band_tables`' three;
    ``window``: a query reads the so many keys of its request that end
    with its own. -> ((T, Hq D) in ``v``'s dtype; int32 (2,):
    :func:`band_tiles`, the steps the kernel ran a key-value head and
    the tiles of its size on or under the diagonal)."""
    out = _band_call(q, k, v, q_weight, k_weight, *tables,
                     window=int(window), eps=float(eps),
                     interpret=bool(interpret))
    tiles = band_tiles(q.shape[0], band_block(q.shape[0], int(window)))
    return out, jnp.asarray(tiles, jnp.int32)


# -- differential attention under a window --------------------------------

DIFFERENTIAL_KERNEL_NAME = "differential_banded_attention"


@functools.partial(jax.jit, static_argnames=(
    "window", "pairs", "dim", "eps", "interpret"))
def _differential_call(qkv, lam, sub, start, *, window, pairs, dim, eps,
                       interpret):
    tokens = qkv.shape[0]
    query_pairs, key_pairs = pairs
    per = query_pairs // key_pairs
    block = band_block(tokens, window)
    steps = tokens // block
    # q's, k's and v's columns in the one array their product wrote:
    # pair g's start at these blocks of ``per * dim`` and of ``dim``
    k_at, v_at = query_pairs, query_pairs + key_pairs

    def spec(width, first, before=False):
        return pl.BlockSpec((block, width), lambda i, g: (
            jnp.maximum(i - 1, 0) if before else i, first + g))
    row = pl.BlockSpec((1, dim), lambda i, g: (0, 0))
    f32 = jnp.float32
    operands = (qkv, qkv, qkv, qkv, qkv, start,
                jnp.broadcast_to(lam.astype(f32), (1, dim)),
                sub.astype(f32)[None, :])
    out = jax.ShapeDtypeStruct((tokens, query_pairs * dim), qkv.dtype)
    # two score tiles a query pair, each with its product with the value
    scored = steps * key_pairs * 2 * per * block * 2 * block
    return pl.pallas_call(
        functools.partial(_kernel, window=window, dim=dim, eps=None,
                          pair_eps=eps),
        grid=(steps, key_pairs),
        in_specs=[spec(per * dim, 0), spec(dim, k_at, True),
                  spec(dim, k_at), spec(dim, v_at, True), spec(dim, v_at),
                  pl.BlockSpec((block, 1), lambda i, g: (i, 0)), row, row],
        out_specs=spec(per * dim, 0), out_shape=out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=96 * 2 ** 20),
        cost_estimate=pl.CostEstimate(
            flops=2 * 2 * scored * dim, transcendentals=scored,
            bytes_accessed=2 * qkv.size * qkv.dtype.itemsize),
        interpret=interpret, name=DIFFERENTIAL_KERNEL_NAME)(*operands)


def differential_banded_attention(qkv, lam, sub_weight, start, window: int,
                                  heads, eps: float,
                                  interpret: bool = False):
    """One layer's *differential* attention under a window, from the
    layer's first product to its last one's operand; Phi-4-mini-flash's
    sliding layers (``models/phi4_flash/network.py``) are the caller.
    The kernel is K-EXAONE's above without its first lines and with two
    more last ones (:func:`_kernel`'s text): what follows is what
    differs.

    ``qkv`` (T, (Hq + 2 Hk) d) in the activations' dtype, the one array
    the layer's first product wrote, ``[Q | K | V]`` with the bias added
    and Q *scaled* before its one rounding (``d ** -0.5`` = 1 / 8 at the
    published 64: exact in any dtype), heads of ``d`` side by side;
    ``heads`` (Hq, Hk). Heads pair by stripes: query pair j is heads 2j
    (``q1``) and 2j + 1 (``q2``), key-value pair g likewise, and query
    pair j reads pair ``g = j // (Hq / Hk)`` — so a pair is ``D = 2 d``
    = 128 adjacent lanes of each of Q, K and V, and the kernel reads
    them where they lie: the array is passed five times, no slice, pad
    or transpose in HBM. ``lam`` () float32 the layer's ``lambda``;
    ``sub_weight`` (D,) the sub-layer norm's weight times the layer's
    scale ``1 - lambda_init``, ``eps`` the norm's; ``start`` (T, 1)
    int32 (:func:`band_start`); a query reads the ``window`` keys of its
    request that end with its own.
    -> ((T, Hq d) in ``qkv``'s dtype: per pair ``RMSNorm(P1 [v1 | v2] -
    lambda P2 [v1 | v2]) sub_weight``; int32 (2,): :func:`band_tiles`).

    A step is (query block, key-value pair): its ``Hq / Hk`` query pairs
    are ``2 Hq / Hk`` score tiles, each a pair's lanes with the other
    head's zeroed against the pair's keys whole — a product over 128
    lanes costs the matrix unit what one over 64 does, and no lane is
    shifted; each softmax has its product with the 128-wide value (a
    pair's scores are computed once for both halves of the value), and
    the subtraction, the norm and the weight run on the (B, 128) results.
    The window of 512 makes the block 512 and a step two key blocks:
    1,024 keys computed where at most 512 are kept, four float32 (512,
    1,024) score tiles a step (the limit on VMEM is raised for them)."""
    hq, hk = heads
    dim = 2 * (qkv.shape[1] // (hq + 2 * hk))
    out = _differential_call(
        qkv, lam, sub_weight, start, window=int(window),
        pairs=(hq // 2, hk // 2), dim=int(dim), eps=float(eps),
        interpret=bool(interpret))
    tiles = band_tiles(qkv.shape[0], band_block(qkv.shape[0], int(window)))
    return out, jnp.asarray(tiles, jnp.int32)


# -- latent attention (MLA, expanded) under a window ----------------------
#
# dots3-note's sliding layers (``models/dots3_note``): every head has a
# key of its own, wider than its value, no head norm, and q comes rotated,
# scaled and rounded from ``ops/mla.queries``. The module's text above is
# K-EXAONE's kernel's; what differs here is said at
# :func:`latent_banded_attention`.

LATENT_KERNEL_NAME = "latent_banded_attention"
#: heads a step of the latent kernel (the sweep at
#: :func:`latent_banded_attention`)
_LATENT_HEADS = 16


def _latent_kernel(q_ref, kv0_ref, kv1_ref, ks0_ref, ks1_ref, start_ref,
                   gate_ref, o_ref, *, window: int, own: int, value: int):
    """One query block of ``heads`` heads. ``q_ref`` (heads, B, lanes);
    ``kv1_ref`` (B, heads (own + value)) a head's ``[own key | value]``
    as their product wrote them, ``ks1_ref`` the block's shared key;
    ``kv0_ref``, ``ks0_ref`` the same of the block before it;
    ``start_ref`` (B, 1); ``gate_ref`` (1, B, heads) float32 the heads'
    output gates; ``o_ref`` (B, heads value)."""
    i = pl.program_id(0)
    heads, block, _ = q_ref.shape
    wide = own + value
    t = i * block + lax.broadcasted_iota(jnp.int32, (block, 2 * block), 0)
    at = (i - 1) * block \
        + lax.broadcasted_iota(jnp.int32, (block, 2 * block), 1)
    keep = (at <= t) & (at > t - window) & (at >= start_ref[...])
    shared = jnp.concatenate([ks0_ref[...], ks1_ref[...]], axis=0)
    gate = gate_ref[0]
    for h in range(heads):
        k = jnp.concatenate([kv0_ref[:, h * wide:h * wide + own],
                             kv1_ref[:, h * wide:h * wide + own]], axis=0)
        v = jnp.concatenate([kv0_ref[:, h * wide + own:(h + 1) * wide],
                             kv1_ref[:, h * wide + own:(h + 1) * wide]],
                            axis=0)
        s = jnp.where(keep, latent.scores(q_ref[h], k, shared, own),
                      _MASKED)
        p = jnp.exp(s - s.max(axis=-1, keepdims=True))
        o = jnp.dot(p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
        o_ref[:, h * value:(h + 1) * value] = (
            o / p.sum(axis=-1, keepdims=True) * gate[:, h:h + 1]) \
            .astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "nope", "value", "per", "interpret"))
def _latent_band_call(q, kv, shared, gate, start, *, window, nope, value,
                      per, interpret):
    heads, tokens, lanes = q.shape
    own = latent.key_lanes(nope, lanes)
    wide = own + value
    block_rows = band_block(tokens, window)
    steps = tokens // block_rows

    def block(width, before=False, shared=False):
        """``width`` columns of a block of tokens: this step's or the
        block before it, the step's heads' or every head's."""
        return pl.BlockSpec((block_rows, width), lambda i, g: (
            jnp.maximum(i - 1, 0) if before else i, 0 if shared else g))
    operands = (q, kv, kv, shared, shared, start,
                latent.gate_groups(gate, per))
    out = jax.ShapeDtypeStruct((tokens, heads * value), kv.dtype)
    pairs = steps * heads * block_rows * 2 * block_rows
    return pl.pallas_call(
        functools.partial(_latent_kernel, window=window, own=own,
                          value=value),
        grid=(steps, heads // per),
        in_specs=[
            pl.BlockSpec((per, block_rows, lanes), lambda i, g: (g, i, 0)),
            block(per * wide, before=True), block(per * wide),
            block(shared.shape[1], True, True),
            block(shared.shape[1], shared=True), block(1, shared=True),
            pl.BlockSpec((1, block_rows, per), lambda i, g: (g, i, 0))],
        out_specs=block(per * value),
        out_shape=out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=96 * 2 ** 20),
        cost_estimate=pl.CostEstimate(
            flops=2 * pairs * (lanes + value), transcendentals=pairs,
            bytes_accessed=sum(x.size * x.dtype.itemsize
                               for x in operands + (out,))),
        interpret=interpret, name=LATENT_KERNEL_NAME)(*operands)


def latent_banded_attention(q, kv, k_pe, gate, start, window: int,
                            nope: int, value: int,
                            interpret: bool = False):
    """Latent attention in its expanded form under a window, from
    ``ops/mla.queries``' result to the output product's operand;
    dots3-note's sliding layers (``models/dots3_note/network.py``) are
    the caller.

    ``q`` (heads, T, lanes) as ``ops/mla.queries`` wrote it: a head's
    ``[q_nope | q_pe rotated | 0]``, scaled and rounded; ``kv`` (T,
    heads (own + value)) as the key-value latent's product wrote it, a
    head's ``[own key | value]`` with ``own``
    ``ops/latent.key_lanes``; ``k_pe`` (T, rotary) the one rotary key
    all heads share, rotated; ``gate`` (T, heads) float32, a head's
    result is multiplied by it; ``start`` (T, 1) int32
    (:func:`band_tables`' third); a query reads the ``window`` keys of
    its request that end with its own. -> ((T, heads value) in ``kv``'s
    dtype, int32 (2,): :func:`band_tiles`).

    What differs from K-EXAONE's kernel above: a step is (query block,
    ``per`` heads) and every head has *its own* key and value, so a
    step's operands are ``per`` times a head's and nothing is shared
    but the rotary key, which is not copied under the heads in HBM: it
    is added to the own key's empty columns in VMEM (or, where the own
    key is whole lane tiles, has a product of its own); no norm, no
    rotation: q's are ``ops/mla.queries``', the keys' are XLA's on 64
    columns a token; the head's output gate is the kernel's last line.
    The window of 513 makes the block 512 and a step two key blocks of
    512: 1,024 keys computed where at most 513 are kept.

    **The sweep** (my chip runs, PR 55; one TPU v5 lite, 128 rows of 128
    tokens as one / two / three requests, 64 heads of 192 + 64 / 128, a
    window of 513; ``scripts/indexed_sweep.py --shape=latent``, which
    sets ``_LATENT_HEADS`` for each count; ms a layer; every form gives
    the explicit mask's values, largest difference 0.0073 at a spread of
    0.169). Heads a step: 16 **5.00 / 5.25 / 5.00**; 8 5.13 /
    5.46 / 5.51; 4 5.57 / 5.86 / 5.87; 2 6.02 / 6.00 / 6.05. The two
    products over the band's two blocks are 4.19 ms at the matrix unit's
    peak (over the pairs the window keeps 2.05; q, ``kv`` and the result
    once are 1.80 ms of the memory's bandwidth): at 16 heads a step the
    kernel stands at 84% of what it computes and 41% of what the window
    asks for. Walking the causal triangle instead (the full layers'
    kernel's 42-92 ms a layer at twice the heads) would be ten times
    that."""
    heads, tokens, lanes = q.shape
    per = min(_LATENT_HEADS, heads)
    out = _latent_band_call(
        q, kv, latent.shared_key(k_pe, nope, lanes), gate, start,
        window=int(window), nope=int(nope), value=int(value), per=int(per),
        interpret=bool(interpret))
    tiles = band_tiles(tokens, band_block(tokens, int(window)))
    return out, jnp.asarray(tiles, jnp.int32)
