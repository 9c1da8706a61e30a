"""Weights from a seed, made on the device: the drawing machinery the
token families share. A family brings its tensor list
(``<family>/checkpoint.py``: ``tensor_specs``) and its config class.

A checkpoint of such a family is a *recipe*: a small JSON file holding
the family's name, the seed, the configuration's sizes and the ids of
the experts held. Every tensor is a function of (seed, name): its key
is folded from the seed and the name's CRC, a routed expert's from its
*global* id as well, so that two chips holding different experts of
one layer hold the same model. :func:`make_tensor` draws it with
``jax.random`` on the device, in float32, and rounds to its stored
dtype once; :func:`reference_reader` hands a plain reference those
same stored values, upcast to float32, one tensor at a time.

A tensor is drawn in its published form and may be *stored* in
another, made once, inside the jit that draws it; the draw and the
reader's values do not know of it:

- ``TensorSpec.transposed``: the last two axes swapped (a routed
  expert's first matrices lie ``[held, inner, hidden]``, the
  orientation the grouped product reads without a relayout,
  ``ops/moe.py``);
- ``TensorSpec.halves``: ``(head, first, width)``: the last axis is
  heads of ``head`` columns, and in each the ``width`` columns from
  ``first`` are stored evens first, then odds (a rotary projection's
  interleaved pairs as the two halves ``ops/rope.py`` rotates);
- ``TensorSpec.heads_first``: ``(head, order)``: a matrix whose
  columns are heads of ``head`` lies ``[heads, rows, len(order)]``, a
  head's stored column j holding its column ``order[j]`` (after
  ``halves``), the negative of column ``-1 - order[j]``, or zero
  (``None``): the operand a product writes heads-first and whole
  lanes wide, its pad columns part of the weight (``ops/mla.py``).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import zlib
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class TensorSpec:
    shape: Tuple[int, ...]
    dtype: str          # "bfloat16" | "float32"
    kind: str           # normal | ones (x scale) | a_log | a_log_states |
                        # dt_bias | uniform
    scale: float = 1.0
    per_expert: bool = False   # leading axis = routed experts
    #: ``shape`` is the stored one: the published orientation, in which
    #: the tensor is drawn and read, has the last two axes swapped
    transposed: bool = False
    #: (head, first, width): columns stored evens first, then odds
    halves: Optional[Tuple[int, int, int]] = None
    #: (head, order): ``shape`` is the stored [heads, rows, len(order)]
    heads_first: Optional[Tuple[int, Tuple[Optional[int], ...]]] = None
    #: a ``dt_bias`` draw's (min, max, floor) of the time step
    steps: Tuple[float, ...] = ()
    #: ((columns, factor), ...): the last axis in runs of columns, each
    #: drawn at ``scale`` times its own factor (a product whose result
    #: the model multiplies by a scalar a segment)
    segments: Tuple[Tuple[int, float], ...] = ()
    #: added to the draw along the last axis, an entry a column (a bias
    #: drawn around a mean of its own: ``B_res = 3 I + N(0, 1)``)
    offsets: Tuple[float, ...] = ()


def published_shape(spec: TensorSpec) -> Tuple[int, ...]:
    """The shape ``spec`` is drawn and read in."""
    shape = spec.shape
    if spec.heads_first is not None:
        heads, rows, _ = shape
        return (rows, heads * spec.heads_first[0])
    if spec.transposed:
        return shape[:-2] + (shape[-1], shape[-2])
    return shape


def halves_order(spec: TensorSpec, inverse: bool = False) -> np.ndarray:
    """The stored position -> published column of ``spec.halves`` (or
    its inverse: published column -> stored position)."""
    head, first, width = spec.halves
    last = published_shape(spec)[-1]
    order = np.arange(last).reshape(last // head, head)
    pairs = order[:, first:first + width].copy()
    order[:, first:first + width] = np.concatenate(
        [pairs[:, 0::2], pairs[:, 1::2]], axis=1)
    order = order.reshape(-1)
    return np.argsort(order) if inverse else order


def _heads_first_columns(order) -> Tuple[np.ndarray, np.ndarray]:
    """-> (the head's column each stored column reads, its sign: 0 for
    a column of zeros) of a ``heads_first`` order."""
    source = np.array([0 if j is None else j if j >= 0 else -1 - j
                       for j in order])
    sign = np.array([0 if j is None else 1 if j >= 0 else -1
                     for j in order], np.int8)
    return source, sign


def _key(seed: int, name: str):
    import jax
    key = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (int(seed) >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _drawer(spec: TensorSpec):
    """The jitted draw of one spec: (key, expert ids) -> the tensor as
    stored."""
    import jax
    import jax.numpy as jnp
    dtype = getattr(jnp, spec.dtype)
    shape = published_shape(spec)
    if spec.per_expert:
        shape = shape[1:]

    def one(key):
        if spec.kind == "normal":
            x = jax.random.normal(key, shape, jnp.float32) * spec.scale
        elif spec.kind == "uniform":
            x = jax.random.uniform(key, shape, jnp.float32,
                                   -spec.scale, spec.scale)
        elif spec.kind == "ones":
            x = jnp.full(shape, spec.scale, jnp.float32)
        elif spec.kind == "a_log":
            x = jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                           1.0, 16.0))
        elif spec.kind == "a_log_states":
            # Mamba-1's own: log(1 .. N) along the state axis, a channel
            x = jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[-1] + 1, dtype=jnp.float32)), shape)
        elif spec.kind == "dt_bias":
            t_min, t_max, t_floor = spec.steps
            u = jax.random.uniform(key, shape, jnp.float32)
            dt = jnp.exp(u * (math.log(t_max) - math.log(t_min))
                         + math.log(t_min))
            dt = jnp.maximum(dt, t_floor)
            x = dt + jnp.log(-jnp.expm1(-dt))      # inverse softplus
        else:
            raise ValueError("tensor kind %r" % (spec.kind,))
        if spec.segments:
            x = x * np.repeat(
                np.asarray([f for _, f in spec.segments], np.float32),
                [n for n, _ in spec.segments])
        if spec.offsets:
            x = x + np.asarray(spec.offsets, np.float32)
        x = x.astype(dtype)
        if spec.halves is not None:
            x = x[..., halves_order(spec)]
        if spec.heads_first is not None:
            head, order = spec.heads_first
            source, sign = _heads_first_columns(order)
            x = x.reshape(shape[0], -1, head)[..., source]
            return jnp.swapaxes(x * sign.astype(dtype), 0, 1)
        return jnp.swapaxes(x, -1, -2) if spec.transposed else x

    if spec.per_expert:
        return jax.jit(lambda key, ids: jax.vmap(
            lambda e: one(jax.random.fold_in(key, e)))(ids))
    return jax.jit(lambda key, ids: one(key))


def make_tensor(seed: int, name: str, spec: TensorSpec,
                expert_ids: Sequence[int], device):
    """The tensor ``name`` of the model ``seed`` names, on ``device``,
    in its stored dtype and form. ``expert_ids`` are the global ids of
    the experts a per-expert stack holds, in its order."""
    import jax
    with jax.default_device(device):
        ids = np.asarray(expert_ids, np.int32)
        if spec.per_expert:
            spec = dataclasses.replace(
                spec, shape=(len(ids),) + spec.shape[1:])
        return _drawer(spec)(_key(seed, name), ids)


Specs = Dict[str, Dict[str, TensorSpec]]


def make_params(specs: Specs, seed: int, held: Sequence[int], device,
                groups: Optional[Sequence[str]] = None):
    """The parameter tree a family's ``network.forward`` reads (or the
    named groups of it), on ``device``: the group ``top`` at the root,
    every other group under its name."""
    import jax
    params = {}
    for group in (groups if groups is not None else specs):
        made = {name: make_tensor(seed, "%s.%s" % (group, name), spec,
                                  held, device)
                for name, spec in specs[group].items()}
        if group == "top":
            params.update(made)
        else:
            params[group] = made
    jax.block_until_ready(params)
    return params


def reference_reader(specs: Specs, seed: int, device) -> Callable:
    """``read(name, expert_ids=None)`` -> the stored values of tensor
    ``name`` (``top.embed``, ``b3.in_proj``, ...) as float32, in the
    published form; for a per-expert stack, of the experts named. What
    a plain reference reads its weights through, one tensor at a
    time."""
    import jax.numpy as jnp

    def read(name: str, expert_ids: Optional[Sequence[int]] = None):
        group, tensor = name.split(".", 1)
        spec = specs[group][tensor]
        stored = make_tensor(seed, name, spec,
                             expert_ids if expert_ids is not None else (),
                             device).astype(jnp.float32)
        if spec.heads_first is not None:
            head, order = spec.heads_first
            at = np.array([order.index(j) for j in range(head)])
            stored = jnp.swapaxes(stored, 0, 1)[..., at]
            stored = stored.reshape(stored.shape[0], -1)
        elif spec.transposed:
            stored = jnp.swapaxes(stored, -1, -2)
        if spec.halves is not None:
            stored = stored[..., halves_order(spec, inverse=True)]
        return stored
    return read


# -- the recipe file ------------------------------------------------------


def save_recipe(path: str, family: str, config: dict, seed: int,
                held: Sequence[int]) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({"family": family, "seed": int(seed),
                   "held_experts": [int(e) for e in held],
                   "config": config}, f, indent=1)


def read_recipe(path: str) -> dict:
    with open(path) as f:
        return json.load(f)
