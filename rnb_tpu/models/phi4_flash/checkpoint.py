"""The tensors of a Phi-4-mini-flash stack, made on the device from a
seed by the machinery the token families share
(``rnb_tpu/models/seeded.py``: the recipe, the draw, the reader the
plain reference reads through). The family holds no experts, so a
recipe's ``held_experts`` is empty.

**Groups.** ``top`` (the embedding, tied to the head, and the final
norm); ``pairs``: the (Mamba, window) pairs of layers 0 .. n/2 - 1,
every tensor *stacked* along a leading axis of n/4, the Mamba layer's
named ``m.<tensor>`` and the attention layer's ``a.<tensor>`` — the
operand ``network.forward``'s ``lax.scan`` slices, so that no weight is
copied to be stacked; ``l<n/2>`` (the memory's Mamba layer) and
``l<n/2 + 1>`` (the full attention layer), alone; ``cross``: the (GMU,
cross) pairs behind them, stacked, ``g.<tensor>`` and ``c.<tensor>``.
A stacked tensor is drawn a layer at a time from (seed, name, index
along the stack) — ``seeded``'s per-expert draw, the index in the
expert id's place, one layer a call: the drawing program of a tensor is
then one for every layer and stack that holds its shape (23 programs
for the model and not 55: the stack whole, a program a stack's depth,
cost a cold set-up 100 s more; my chip run, PR 59) — and
:func:`reference_reader` reads layer i's part under the name
``l<i>.<tensor>`` whichever group holds it.

**The draw** (all of it this repo's assumption: the published
checkpoint is trained, not initialised; the configuration's
``assumed.weights`` says the same). Embedding N(0, 1): the stream starts
at a spread of one, and the tied head's logits at sqrt(hidden). A
projection into a mixer or an MLP N(0, 1 / fan_in); one back onto the
stream (``out_proj``, ``o``, ``g_out``, ``down``) N(0, 1 / fan_in) /
sqrt(layers). ``W_qkv``'s key columns at ``KEY_GAIN``: queries of spread
one against them give scores of spread 2.5 — a few dominant keys a
query; at a spread of one both softmaxes over a thousand keys are flat,
alike, and their difference — the mechanism — is a scale the sub-layer
norm removes (Falcon-H1's ``KEY_GAIN`` has the softmax's half of that
reason). Biases N(0, 0.1), so that one left out shows. The Mamba mixer's
own initialisation: ``A_log = log(1 .. N)`` a channel, ``dt_bias`` the
inverse softplus of a step drawn log-uniformly in [0.001, 0.1] — decays
from ``exp(-0.001)`` to ``exp(-1.6)`` a token, states neither constant
nor dead — ``D = 1``, the convolution U(+-1/sqrt(taps)); the lambda
vectors N(0, 0.1); norm weights (``w_sub`` among them) 1.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence

from rnb_tpu.models import seeded
from rnb_tpu.models.phi4_flash.network import Phi4FlashConfig
from rnb_tpu.models.seeded import TensorSpec

FAMILY = "phi4_flash"
#: the spread of a key's columns behind ``W_qkv``
KEY_GAIN = 2.5
_BIAS = 0.1

#: a stacked group's prefix -> the layer kind it holds
PREFIXES = {"m": "mamba", "a": "window", "g": "gmu", "c": "cross"}


def layer_specs(cfg: Phi4FlashConfig, kind: str) -> Dict[str, TensorSpec]:
    """{tensor: spec} of one layer of ``kind`` (``network.kind``'s five;
    ``window`` and ``full`` are alike), its norms and MLP with it."""
    d, bf, f32 = cfg.hidden_size, "bfloat16", "float32"
    back = 1.0 / math.sqrt(cfg.num_hidden_layers)

    def lin(fan_in, fan_out, over=1.0, **kwargs):
        return TensorSpec((fan_in, fan_out), bf, "normal",
                          1.0 / (math.sqrt(fan_in) * over), **kwargs)

    def ones(width):
        return TensorSpec((width,), bf, "ones")

    def bias(width):
        return TensorSpec((width,), bf, "normal", _BIAS)
    inner = cfg.intermediate_size
    specs = {"ln1_w": ones(d), "ln1_b": bias(d), "ln2_w": ones(d),
             "ln2_b": bias(d), "gate_up": lin(d, 2 * inner),
             "down": lin(inner, d, 1.0 / back)}
    di, n, taps = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    hq, hk, _ = cfg.qkv_parts
    pair = 2 * cfg.head_dim
    differential = {
        **{name: TensorSpec((cfg.head_dim,), f32, "normal", 0.1)
           for name in ("lq1", "lk1", "lq2", "lk2")},
        "sub_w": TensorSpec((pair,), f32, "ones"),
        "o": lin(hq, d, 1.0 / back), "o_b": bias(d)}
    if kind == "mamba":
        specs.update({
            "in_proj": lin(d, 2 * di),
            "conv_w": TensorSpec((di, taps), bf, "uniform",
                                 1.0 / math.sqrt(taps)),
            "conv_b": TensorSpec((di,), bf, "uniform",
                                 1.0 / math.sqrt(taps)),
            "x_proj": lin(di, cfg.dt_rank + 2 * n),
            "dt_proj": lin(cfg.dt_rank, di),
            "dt_bias": TensorSpec((di,), f32, "dt_bias", steps=(
                cfg.time_step_min, cfg.time_step_max, cfg.time_step_floor)),
            "a_log": TensorSpec((di, n), f32, "a_log_states"),
            "d": TensorSpec((di,), f32, "ones"),
            "out_proj": lin(di, d, 1.0 / back)})
    elif kind in ("window", "full"):
        specs.update({
            "qkv": lin(d, hq + 2 * hk, segments=(
                (hq, 1.0), (hk, KEY_GAIN), (hk, 1.0))),
            "qkv_b": bias(hq + 2 * hk), **differential})
    elif kind == "gmu":
        specs.update({"g_in": lin(d, di), "g_out": lin(di, d, 1.0 / back)})
    elif kind == "cross":
        specs.update({"q": lin(d, hq), "q_b": bias(hq), **differential})
    else:
        raise ValueError("layer kind %r" % (kind,))
    return specs


def stacked_groups(cfg: Phi4FlashConfig):
    """{group: (its first layer, (prefix of the even layer, of the odd
    one), pairs held)} of the two stacked groups."""
    half = cfg.memory_layer
    return {"pairs": (0, ("m", "a"), half // 2),
            "cross": (half + 2, ("g", "c"),
                      (cfg.num_hidden_layers - half - 2) // 2)}


def tensor_specs(cfg: Phi4FlashConfig, num_held: int = 0
                 ) -> Dict[str, Dict[str, TensorSpec]]:
    """{group: {tensor: spec}}: ``top``, the two stacked groups (a
    spec's ``shape`` with the stack's leading axis) and the two layers
    that stand alone."""
    d = cfg.hidden_size
    specs = {"top": {
        "embed": TensorSpec((cfg.vocab_size, d), "bfloat16", "normal", 1.0),
        "final_norm_w": TensorSpec((d,), "bfloat16", "ones"),
        "final_norm_b": TensorSpec((d,), "bfloat16", "normal", _BIAS)}}
    for group, (_, prefixes, count) in stacked_groups(cfg).items():
        specs[group] = {
            "%s.%s" % (prefix, name): dataclasses.replace(
                spec, shape=(count,) + spec.shape, per_expert=True)
            for prefix in prefixes
            for name, spec in layer_specs(cfg, PREFIXES[prefix]).items()}
    for i in (cfg.memory_layer, cfg.key_layer):
        specs["l%d" % i] = layer_specs(cfg, cfg.kind(i))
    return specs


def _one_layer(spec: TensorSpec) -> TensorSpec:
    """The spec one layer of a stack is drawn by: a stack of one."""
    if spec.per_expert:
        return dataclasses.replace(spec, shape=(1,) + spec.shape[1:])
    return dataclasses.replace(spec, shape=(1,) + spec.shape,
                               per_expert=True)


def layer_params(cfg: Phi4FlashConfig, i: int) -> int:
    """The parameters of layer ``i``."""
    kind = cfg.kind(i)
    return sum(math.prod(spec.shape) for spec in layer_specs(
        cfg, "window" if kind == "full" else kind).values())


def total_params(cfg: Phi4FlashConfig) -> int:
    """Every parameter held: the layers, the final norm and the
    embedding once (the head is tied to it)."""
    return sum(layer_params(cfg, i) for i in range(cfg.num_hidden_layers)) \
        + cfg.vocab_size * cfg.hidden_size + 2 * cfg.hidden_size


def _where(cfg: Phi4FlashConfig, i: int):
    """Layer i's (group, prefix or None, index along the stack or
    None)."""
    for group, (first, prefixes, count) in stacked_groups(cfg).items():
        if first <= i < first + 2 * count:
            return group, prefixes[(i - first) % 2], (i - first) // 2
    return "l%d" % i, None, None


def make_params(cfg: Phi4FlashConfig, seed: int, held: Sequence[int],
                device, groups: Optional[Sequence[str]] = None):
    """The parameter tree ``network.forward`` reads (or the named
    groups of it), on ``device``: a stacked group's tensors drawn a
    layer at a time, by the index along the stack."""
    del held
    import jax
    import jax.numpy as jnp
    specs = tensor_specs(cfg)
    stacks = stacked_groups(cfg)
    params = {}
    for group in (groups if groups is not None else specs):
        if group == "top":
            params.update(seeded.make_params(specs, seed, (), device,
                                             [group]))
            continue
        count = stacks[group][2] if group in stacks else None
        made = {}
        for name, spec in specs[group].items():
            layers = [seeded.make_tensor(
                seed, "%s.%s" % (group, name), _one_layer(spec), (k,),
                device) for k in range(count or 1)]
            made[name] = layers[0][0] if count is None \
                else jnp.concatenate(layers)
        params[group] = made
    jax.block_until_ready(params)
    return params


def reference_reader(cfg: Phi4FlashConfig, seed: int, device):
    """``read(name, index=None)``: the stored values of tensor ``name``
    (``top.embed``, ``l5.qkv``, ... — layer i's whichever group holds
    it) as float32 — of ``stored[index]`` where an index is given, taken
    before the values are widened: the embedding is 1 GB as stored, and
    a reference that runs beside the program's weights reads the rows of
    its tokens, and a block of rows at a time for the tied head."""
    import jax.numpy as jnp
    specs = tensor_specs(cfg)

    def read(name: str, index=None):
        group, tensor = name.split(".", 1)
        spec = None
        if group != "top":
            group, prefix, at = _where(cfg, int(group[1:]))
            if prefix is not None:
                tensor = "%s.%s" % (prefix, tensor)
            spec = _one_layer(specs[group][tensor])
        stored = seeded.make_tensor(
            seed, "%s.%s" % (group, tensor), spec or specs[group][tensor],
            () if spec is None else (at or 0,), device)
        if spec is not None:
            stored = stored[0]
        if index is not None:
            stored = stored[index]
        return stored.astype(jnp.float32)
    return read


def save_recipe(path: str, config: dict, seed: int,
                held: Sequence[int] = ()) -> None:
    seeded.save_recipe(path, FAMILY, config, seed, held)


def load_recipe(path: str):
    """-> (Phi4FlashConfig, seed, the experts held: none)."""
    recipe = seeded.read_recipe(path)
    return (Phi4FlashConfig.from_published(recipe["config"]),
            int(recipe["seed"]), tuple(recipe["held_experts"]))
