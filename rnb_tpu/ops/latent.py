"""What the two kernels of *latent* attention (MLA in its expanded form)
share — ``ops/indexed.latent_indexed_attention`` under a learned choice
of keys and ``ops/banded.latent_banded_attention`` under a window,
dots3-note's full and sliding layers (``models/dots3_note``): where a
head's own key and the one rotary key all heads share lie in the
operands, one head's scores from them, and the heads' output gates as
the blocks a grid step reads."""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

_LANES = 128


def key_lanes(nope: int, lanes: int) -> int:
    """The columns of a head's *own* key in ``kv`` for queries of
    ``lanes`` columns ``[q_nope | q_pe | 0]``: ``nope`` where that is
    whole lane tiles (the shared rotary key then has a product of its
    own against q's lanes behind them), else all ``lanes`` (the own key
    is ``[k_nope | 0]`` and the shared key is added under it)."""
    return nope if nope % _LANES == 0 else lanes


def shared_key(k_pe, nope: int, lanes: int):
    """``k_pe`` (T, rotary), rotated -> the shared key as the latent
    kernels read it beside :func:`key_lanes`' own keys: (T,
    ``lanes - nope``) ``[k_pe | 0]`` or (T, ``lanes``) ``[0 | k_pe |
    0]``."""
    front = 0 if nope % _LANES == 0 else nope
    width = lanes - nope + front
    return jnp.pad(k_pe, ((0, 0), (front, width - front - k_pe.shape[1])))


def scores(q, k_own, shared, own: int):
    """One head's scores (queries, keys) float32. ``q`` (queries,
    lanes); ``k_own`` (keys, ``own``) the head's own key columns;
    ``shared`` the keys' :func:`shared_key`."""
    contract = (((1,), (1,)), ((), ()))
    if own == q.shape[1]:
        # disjoint columns: the sum is each operand's own value
        return lax.dot_general(q, k_own + shared, contract,
                               preferred_element_type=jnp.float32)
    return lax.dot_general(q[:, :own], k_own, contract,
                           preferred_element_type=jnp.float32) \
        + lax.dot_general(q[:, own:], shared, contract,
                          preferred_element_type=jnp.float32)


def gate_groups(gate, per: int):
    """``gate`` (T, heads) float32 -> (heads // per, T, per): a step's
    heads' gates as one block whose last axis is whole."""
    tokens, heads = gate.shape
    return gate.astype(jnp.float32).reshape(tokens, heads // per, per) \
        .transpose(1, 0, 2)
