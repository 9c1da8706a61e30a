"""Weights from a seed, made on the device.

A checkpoint of this family is a *recipe*: a small JSON file holding
the seed, the configuration's sizes and the ids of the experts held.
Every tensor is a function of (seed, name): its key is folded from
the seed and the name's CRC, a routed expert's from its *global* id as
well, so that two chips holding different experts of one layer hold
the same model. :func:`make_tensor` draws it with ``jax.random`` on
the device, in float32, and rounds to its stored dtype once;
:func:`reference_reader` hands the plain reference those same stored
values, upcast to float32, one tensor at a time.

A tensor is drawn in its published orientation and may be *stored* in
another (``TensorSpec.transposed``): the routed experts' first matrix
is published ``[experts, hidden, inner]`` and lies on the device as
``[held, inner, hidden]``, the orientation the grouped product reads
without a relayout (``ops/moe.py``). The draw and the reader's values
do not know of it; the transpose is made once, inside the jit that
draws the tensor.

Initial scales (all of them this repo's assumption: the published
checkpoint is trained, not initialised): embedding N(0, 1) so the
residual stream starts at a spread of one; every projection into a
mixer N(0, 1/fan_in); every projection back onto the residual stream
(``out_proj``, ``o``, the experts' second matrix) N(0, 1/fan_in)
divided by sqrt(published layers), the family's
``rescale_prenorm_residual``; head N(0, 1/hidden) so logits keep a
spread of about one. ``A_log`` = log U(1, 16), ``dt_bias`` the inverse
softplus of a step drawn log-uniformly in [time_step_min,
time_step_max] and floored at time_step_floor, ``D`` = 1, as the
family's Mamba-2 mixer initialises them; norm weights 1; the
convolution U(+-1/sqrt(kernel)); the router's correction bias
N(0, 0.02^2): small, and it changes choices.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import zlib
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from rnb_tpu.models.nemotron_h.network import (ATTENTION, EXPERTS, MAMBA,
                                               NemotronHConfig)

B_CORR_STD = 0.02


@dataclasses.dataclass(frozen=True)
class TensorSpec:
    shape: Tuple[int, ...]
    dtype: str          # "bfloat16" | "float32"
    kind: str           # normal | ones | a_log | dt_bias | uniform
    scale: float = 1.0
    per_expert: bool = False   # leading axis = routed experts
    #: ``shape`` is the stored one: the published orientation, in which
    #: the tensor is drawn and read, has the last two axes swapped
    transposed: bool = False


def tensor_specs(cfg: NemotronHConfig, num_held: int
                 ) -> Dict[str, Dict[str, TensorSpec]]:
    """{group: {tensor: spec}} with groups ``top`` and ``b<i>``."""
    d, bf, f32 = cfg.hidden_size, "bfloat16", "float32"
    back = 1.0 / math.sqrt(cfg.published_layers)

    def lin(fan_in, fan_out, scale=1.0):
        return TensorSpec((fan_in, fan_out), bf, "normal",
                          scale / math.sqrt(fan_in))

    specs = {"top": {
        "embed": TensorSpec((cfg.vocab_size, d), bf, "normal", 1.0),
        "final_norm": TensorSpec((d,), bf, "ones"),
        "head": lin(d, cfg.vocab_size)}}
    for i, kind in enumerate(cfg.pattern):
        block = {"norm": TensorSpec((d,), bf, "ones")}
        if kind == MAMBA:
            heads = cfg.mamba_num_heads
            block.update({
                "in_proj": lin(d, cfg.d_inner + cfg.conv_dim + heads),
                "conv_w": TensorSpec((cfg.conv_dim, cfg.conv_kernel), bf,
                                     "uniform",
                                     1.0 / math.sqrt(cfg.conv_kernel)),
                "conv_b": TensorSpec((cfg.conv_dim,), bf, "uniform",
                                     1.0 / math.sqrt(cfg.conv_kernel)),
                "dt_bias": TensorSpec((heads,), f32, "dt_bias"),
                "a_log": TensorSpec((heads,), f32, "a_log"),
                "d": TensorSpec((heads,), f32, "ones"),
                "gnorm": TensorSpec((cfg.d_inner,), bf, "ones"),
                "out_proj": lin(cfg.d_inner, d, back)})
        elif kind == ATTENTION:
            hq = cfg.num_attention_heads * cfg.head_dim
            hk = cfg.num_key_value_heads * cfg.head_dim
            block.update({"q": lin(d, hq), "k": lin(d, hk),
                          "v": lin(d, hk), "o": lin(hq, d, back)})
        elif kind == EXPERTS:
            inner = cfg.moe_intermediate_size
            shared = cfg.moe_shared_expert_intermediate_size
            block.update({
                "router": lin(d, cfg.router_experts),
                "b_corr": TensorSpec((cfg.router_experts,), f32, "normal",
                                     B_CORR_STD),
                "up": TensorSpec((num_held, inner, d), bf, "normal",
                                 1.0 / math.sqrt(d), per_expert=True,
                                 transposed=True),
                "down": TensorSpec((num_held, inner, d), bf, "normal",
                                   back / math.sqrt(inner),
                                   per_expert=True),
                "shared_up": lin(d, shared),
                "shared_down": lin(shared, d, back)})
        else:
            raise ValueError("block kind %r" % (kind,))
        specs["b%d" % i] = block
    return specs


def _key(seed: int, name: str):
    import jax
    key = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (int(seed) >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _drawer(cfg_steps: Tuple[float, float, float], spec: TensorSpec):
    """The jitted draw of one spec: (key, expert ids) -> the tensor as
    stored."""
    import jax
    import jax.numpy as jnp
    dtype = getattr(jnp, spec.dtype)
    shape = spec.shape[1:] if spec.per_expert else spec.shape
    if spec.transposed:
        shape = shape[:-2] + (shape[-1], shape[-2])
    t_min, t_max, t_floor = cfg_steps

    def one(key):
        if spec.kind == "normal":
            x = jax.random.normal(key, shape, jnp.float32) * spec.scale
        elif spec.kind == "uniform":
            x = jax.random.uniform(key, shape, jnp.float32,
                                   -spec.scale, spec.scale)
        elif spec.kind == "ones":
            x = jnp.ones(shape, jnp.float32)
        elif spec.kind == "a_log":
            x = jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                           1.0, 16.0))
        elif spec.kind == "dt_bias":
            u = jax.random.uniform(key, shape, jnp.float32)
            dt = jnp.exp(u * (math.log(t_max) - math.log(t_min))
                         + math.log(t_min))
            dt = jnp.maximum(dt, t_floor)
            x = dt + jnp.log(-jnp.expm1(-dt))      # inverse softplus
        else:
            raise ValueError("tensor kind %r" % (spec.kind,))
        x = x.astype(dtype)
        return jnp.swapaxes(x, -1, -2) if spec.transposed else x

    if spec.per_expert:
        return jax.jit(lambda key, ids: jax.vmap(
            lambda e: one(jax.random.fold_in(key, e)))(ids))
    return jax.jit(lambda key, ids: one(key))


def make_tensor(cfg: NemotronHConfig, seed: int, name: str,
                spec: TensorSpec, expert_ids: Sequence[int], device):
    """The tensor ``name`` of the model ``seed`` names, on ``device``,
    in its stored dtype and orientation. ``expert_ids`` are the global
    ids of the experts a per-expert stack holds, in its order."""
    import jax
    with jax.default_device(device):
        ids = np.asarray(expert_ids, np.int32)
        steps = (cfg.time_step_min, cfg.time_step_max, cfg.time_step_floor)
        if spec.per_expert:
            spec = dataclasses.replace(
                spec, shape=(len(ids),) + spec.shape[1:])
        return _drawer(steps, spec)(_key(seed, name), ids)


def make_params(cfg: NemotronHConfig, seed: int, held: Sequence[int],
                device, groups: Optional[Sequence[str]] = None):
    """The parameter tree ``network.forward`` reads (or the named
    groups of it), on ``device``."""
    import jax
    specs = tensor_specs(cfg, len(held))
    params = {}
    for group in (groups if groups is not None else specs):
        made = {name: make_tensor(cfg, seed, "%s.%s" % (group, name),
                                  spec, held, device)
                for name, spec in specs[group].items()}
        if group == "top":
            params.update(made)
        else:
            params[group] = made
    jax.block_until_ready(params)
    return params


def reference_reader(cfg: NemotronHConfig, seed: int, device):
    """``read(name, expert_ids=None)`` -> the stored values of tensor
    ``name`` (``top.embed``, ``b3.in_proj``, ...) as float32, in the
    published orientation; for a per-expert stack, of the experts
    named. What the plain reference reads its weights through, one
    tensor at a time."""
    import jax.numpy as jnp
    specs = tensor_specs(cfg, 1)

    def read(name: str, expert_ids: Optional[Sequence[int]] = None):
        group, tensor = name.split(".", 1)
        spec = specs[group][tensor]
        stored = make_tensor(cfg, seed, name, spec,
                             expert_ids if expert_ids is not None else (),
                             device).astype(jnp.float32)
        return jnp.swapaxes(stored, -1, -2) if spec.transposed else stored
    return read


# -- the recipe file ------------------------------------------------------


def save_recipe(path: str, config: dict, seed: int,
                held: Sequence[int]) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({"family": "nemotron_h", "seed": int(seed),
                   "held_experts": [int(e) for e in held],
                   "config": config}, f, indent=1)


def load_recipe(path: str):
    """-> (NemotronHConfig, seed, held expert ids)."""
    with open(path) as f:
        recipe = json.load(f)
    return (NemotronHConfig.from_published(recipe["config"]),
            int(recipe["seed"]), tuple(recipe["held_experts"]))
