"""The queries' up-projection of latent attention (MLA) in its expanded
form, as a product that writes the operand the pool's flash kernel
reads (``ops/segattn.py``: ``heads_first_attention``), once:
heads-first, ``(heads, tokens, columns)``, the columns whole lanes.

One Pallas kernel, a step a (block of tokens, head): the latents'
block stays in VMEM while the heads' weights stream past it, the
product of one step is a float32 ``(tokens, columns)`` that never
leaves VMEM, and what the float32 product needed before its one
rounding happens there: the rotary columns are turned, the softmax
scale applied. The weight's columns of a head are ``[q_nope | q_pe |
q_pe turned | 0]`` (``models/deepseek_v2/checkpoint.py``): with the
halves ``[x1 | x2]`` the product brings ``[-x2 | x1]`` beside them, a
lane roll lays it under the halves, and ``x cos + turned sin`` is the
half-split rotation of ``ops/rope.py`` in the same float32 numbers.

*Which callers rotate.* ``models/deepseek_v2/network.py`` (its queries
come from a latent, ``q_lora_rank``, and 64 of a head's 192 columns are
rotary, so the kernel requires room for them twice) and, since PR 55,
``models/dots3_note/network.py`` at two geometries in one stack, both
from a latent of 1,024: 128 heads of 128 + 64 (the same columns as
DeepSeek-V2's) and 64 heads of 192 + 64, whose rotary columns begin in
the *middle* of the second lane tile (``whole`` 128, ``first`` 64: the
last two of three lane tiles are rolled as one, by 192) and whose third
lane tile holds nothing but the turned copies: ``out_columns`` 256
leaves it unwritten (a quarter of a GiB a layer at 16,384 tokens). That
caller builds the rotary tables once a dispatch a layer type
(:func:`turn_tables`: two bases in one stack) and hands them to every
layer's call; its output's reader is no splash kernel but
``ops/indexed.latent_indexed_attention`` and
``ops/banded.latent_banded_attention``, which read ``(heads, tokens,
lanes)`` as it lies. ``models/kimi_linear/network.py`` runs the same expanded form
**without** a query latent and **without** positions
(``mla_use_nope``): nothing is turned, so it does not call this kernel;
it stores its query weight heads-first and whole lanes wide too and one
batched product writes the flash kernel's operand
(``heads_first_attention``, which both share, knows no rotation).

On the v5e, 8,192 tokens, 128 heads of 128 + 64 + 64 columns from a
latent of 1536 (my chip runs, PR 38): 4.43 ms at 2,048 tokens a step,
4.54 at 1,024, 4.75 at 512; the matrix unit's peak allows 4.19. (XLA's
own product of the published 192 columns a head, tokens-first and
rounded to bfloat16, nothing else: 3.28.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rnb_tpu.ops import rope

_LANES = 128
#: tokens a step
_TOKENS = 2048
KERNEL_NAME = "mla_queries"


def query_lanes(nope: int, rotary: int) -> int:
    """The columns of a head's queries and keys as the kernel reads
    them: whole lanes with room for the rotary columns twice."""
    return -(-(nope + 2 * rotary) // _LANES) * _LANES


def _kernel(x_ref, w_ref, cos_ref, sin_ref, o_ref, *, whole, turn, scale):
    acc = jnp.dot(x_ref[...], w_ref[0], preferred_element_type=jnp.float32)
    if whole:
        o_ref[0, :, :whole] = (acc[:, :whole] * scale).astype(o_ref.dtype)
    last = acc[:, whole:]
    last = last * cos_ref[...] + pltpu.roll(last, turn, 1) * sin_ref[...]
    kept = o_ref.shape[2] - whole
    if kept < last.shape[1]:
        # the lane tiles behind the rotated columns held what the
        # rotation read: nothing a caller reads
        last = last[:, :kept]
    o_ref[0, :, whole:] = (last * scale).astype(o_ref.dtype)


def turn_tables(positions, inv_freq, nope: int, mscale: float = 1.0):
    """The (cos, sin) :func:`queries` turns a head's rotary columns by
    (``ops/rope.turn_tables`` over the lane tiles from the one they
    begin in): a caller whose layers share positions and frequencies
    builds them once a dispatch and hands them to every layer's call."""
    whole = nope // _LANES * _LANES
    return rope.turn_tables(
        positions, inv_freq, nope - whole,
        query_lanes(nope, 2 * len(inv_freq)) - whole, mscale)


def queries(latent, weight, positions, inv_freq, nope: int, scale: float,
            mscale: float = 1.0, interpret: bool = False, tables=None,
            out_columns=None):
    """``latent`` (tokens, rank), normed; ``weight`` (heads, rank,
    ``query_lanes``) in the stored order; ``positions`` (tokens,).
    -> (heads, tokens, ``query_lanes``) in the latent's dtype: a head's
    ``[q_nope | q_pe rotated | 0]`` times ``scale``, the rotation (its
    cos and sin times ``mscale``) and the scale on the float32
    product. ``tables``: :func:`turn_tables`' pair where the caller has
    built it (``positions``, ``inv_freq`` and ``mscale`` are then not
    read but for the rotary's width); ``out_columns``: the whole lanes
    of a head that are written, where the last lane tiles hold nothing
    but what the rotation read (192 + 64 + 64 columns are three lane
    tiles of which the first two hold the head)."""
    rotary = 2 * len(inv_freq)
    columns = weight.shape[2]
    # the lane tiles in front of the one the rotary columns begin in
    # are scaled and no more; the rest is rolled as one
    whole = nope // _LANES * _LANES
    first, width = nope - whole, columns - whole
    if first + 2 * rotary > width:
        raise ValueError("no room for %d rotary columns twice behind %d "
                         "in %d" % (rotary, nope, columns))
    written = columns if out_columns is None else int(out_columns)
    if written % _LANES or not nope + rotary <= written <= columns:
        raise ValueError("%d columns written of %d, %d of them a head's"
                         % (written, columns, nope + rotary))
    cos, sin = tables if tables is not None else rope.turn_tables(
        positions, inv_freq, first, width, mscale)
    tokens, rank = latent.shape
    heads = weight.shape[0]
    step = min(_TOKENS, tokens)
    if tokens % step:
        raise ValueError("%d tokens in steps of %d" % (tokens, step))
    return pl.pallas_call(
        functools.partial(_kernel, whole=whole, turn=width - rotary,
                          scale=scale),
        grid=(tokens // step, heads),
        in_specs=[pl.BlockSpec((step, rank), lambda i, h: (i, 0)),
                  pl.BlockSpec((1, rank, columns), lambda i, h: (h, 0, 0)),
                  pl.BlockSpec((step, width), lambda i, h: (i, 0)),
                  pl.BlockSpec((step, width), lambda i, h: (i, 0))],
        out_specs=pl.BlockSpec((1, step, written), lambda i, h: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((heads, tokens, written),
                                       latent.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret, name=KERNEL_NAME,
    )(latent, weight, cos, sin)
