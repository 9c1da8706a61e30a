"""Sparse experts for a layer that holds a *share* of them: routing
over every expert the model has, and the held experts' part of the
result as one grouped matrix product.

The router scores all ``num_experts`` in float32 (``sigmoid``), picks
the ``top_k`` largest of score + correction bias, and weights the
chosen by their scores over the sum of the chosen, times a scaling
factor — over all the chosen, held here or not. The layer is told
which experts it holds (``held``: their ids, in the order of the
stacked weights). Every (token, chosen expert) pair whose expert is
held is computed, whatever the imbalance: pairs are sorted by held
expert, the tokens gathered in that order, and the two projections of
``relu(x U_e)^2 D_e`` run as grouped matrix products over the groups;
pairs whose expert is held elsewhere (or whose token is padding) sort
behind the last group, belong to no group and cost no product. On one
chip there is no exchange: what the absent experts would add is left
out.

The grouped product is JAX's Pallas kernel
(``jax.experimental.pallas.ops.tpu.megablox.gmm``: one grid step a
(row tile, group) pair that holds rows, float32 accumulator in VMEM).
The first traced run on the v5e (PR 28) read ``lax.ragged_dot`` at 47%
of the device's time and 12 to 25 TFLOP/s of useful work; the kernel
with the tiles below read 35 to 64 on the same shapes. Off the TPU the
same kernel runs in Pallas's interpret mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
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


def grouped_matmul(rows, weights, counts, interpret: bool):
    """``rows`` (M, K), sorted by group; ``weights`` (G, K, N);
    ``counts`` (G,) int32 rows of each group, in order. -> float32
    (M, N); what lies behind the last group's rows is unspecified.

    Tiles (m, k, n), read on the v5e at M 49,152 (PR 28, my chip
    runs): K 2688 -> N 1856 with (512, 896, 1024) 7.0 ms, K 1856 ->
    N 2688 with (512, 1856, 896) 3.8 ms, against 20.5 and 18.6 ms of
    ``lax.ragged_dot``; 1,024 rows a tile run out of VMEM."""
    m, k = rows.shape
    n = weights.shape[2]
    tm = next(t for t in (512, 256, 128, 64, 32, 16, 8, 1) if m % t == 0)
    tiling = (tm, _tile(k, 2048, 1024), _tile(n, 1024, 1024))
    return gmm(rows, weights, counts, preferred_element_type=jnp.float32,
               tiling=tiling, interpret=interpret)


def route(x, w_router, b_corr, top_k: int, scaling: float):
    """-> (ids (T, top_k) int32, weights (T, top_k) float32). ``x``
    (T, hidden); ``w_router`` (hidden, E)."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=_HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, ids = lax.top_k(scores + b_corr.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, picked / picked.sum(-1, keepdims=True) * scaling


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def held_experts(x, ids, weights, token_ok, held_slot, up, down,
                 interpret: bool = False):
    """The held experts' part of the layer's result.

    ``x`` (T, hidden); ``ids``/``weights`` (T, k) from :func:`route`;
    ``token_ok`` (T,) bool, False on padding; ``held_slot`` (E,) int32:
    an expert's position in the stacks, or -1 where it is held
    elsewhere; ``up`` (held, hidden, inner), ``down`` (held, inner,
    hidden); ``interpret``: run the grouped product's kernel in
    Pallas's interpret mode (off the TPU). -> (out (T, hidden)
    float32, counts (held,) int32: the pairs each held expert served)."""
    tokens, k = ids.shape
    held = up.shape[0]
    slot = held_slot[ids]                               # (T, k)
    slot = jnp.where(token_ok[:, None] & (slot >= 0), slot, held)
    flat_slot = slot.reshape(-1)
    order = jnp.argsort(flat_slot, stable=True)
    counts = jnp.bincount(flat_slot, length=held + 1)[:held] \
        .astype(jnp.int32)
    rows = x[order // k]                                # (T*k, hidden)
    hidden = grouped_matmul(rows, up, counts, interpret)
    hidden = relu2(hidden).astype(x.dtype)
    out = grouped_matmul(hidden, down, counts, interpret)
    # rows behind the last group are no expert's: their product is
    # whatever the grouped kernel leaves there, so they are zeroed
    served = jnp.arange(tokens * k) < counts.sum()
    w = weights.reshape(-1)[order]
    out = jnp.where(served[:, None], out * w[:, None], 0.0)
    back = jnp.zeros((tokens * k, out.shape[1]), jnp.float32) \
        .at[order].set(out)
    return back.reshape(tokens, k, -1).sum(axis=1), counts


def dense_expert(x, up, down):
    """``relu(x U)^2 D`` for one expert every token visits (the shared
    one): inputs in their dtype, float32 accumulation. -> float32."""
    hidden = jnp.dot(x, up, preferred_element_type=jnp.float32)
    return jnp.dot(relu2(hidden).astype(x.dtype), down,
                   preferred_element_type=jnp.float32)
