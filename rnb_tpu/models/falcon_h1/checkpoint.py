"""The tensors of a Falcon-H1 stack, made on the device from a seed by
the machinery the token families share (``rnb_tpu/models/seeded.py``:
the recipe, the draw, the reader the plain reference reads through).
Every tensor is stored as published; the family holds no experts, so a
recipe's ``held_experts`` is empty.

Initial scales (all of them this repo's assumption: the published
checkpoint is trained, not initialised). The model multiplies nearly
every product by a muP scalar (0.0375 behind attention, 0.088 behind
the scan, 0.011 on the keys, 0.0078 on the logits): with the spreads
the other families draw, both mixers would reach the stream at a few
hundredths of its spread, the softmax would be flat, and the logits
would notice neither the mechanism nor a fault in it. So each matrix is
drawn at the other families' spread *over the scalar the model puts on
its product*, and the multipliers stay as published in program and
reference alike: embedding N(0, 1 / embedding_multiplier^2), the stream
starts at a spread of one; a projection into a mixer N(0, 1 / fan_in)
over its multiplier (``in_proj`` a segment of columns, over
``ssm_in_multiplier`` times the segment's ``ssm_multipliers``); one back
onto the stream (``out_proj``, ``o``, ``down``) N(0, 1 / fan_in) /
sqrt(published layers) over its multiplier; head N(0, 1 / hidden) over
``lm_head_multiplier``. The keys come out of ``k`` and its two scalars
at ``KEY_GAIN``: queries of spread one against them give scores (``q .
k / sqrt(d)``) of spread 2.5 — a few dominant keys a query; at a spread
of one the softmax over a thousand keys is so flat that the branch
would add a hundredth of a value's spread (MiniCPM-SALA's ``QK_GAIN``
has the same reason). ``A_log`` = log U(1, 16), ``dt_bias`` the inverse
softplus of a step drawn log-uniformly in [0.001, 0.1] floored at 1e-4,
``D`` = 1, the convolution U(+-1/sqrt(taps)), as
``nemotron_h/checkpoint.py`` draws them (the Mamba-2 mixer's own
initialisation); norm weights 1.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

from rnb_tpu.models import seeded
from rnb_tpu.models.falcon_h1.network import FalconH1Config
from rnb_tpu.models.seeded import TensorSpec

FAMILY = "falcon_h1"
#: the spread of a key's columns behind ``k`` and its scalars
KEY_GAIN = 2.5


def tensor_specs(cfg: FalconH1Config, num_held: int = 0
                 ) -> Dict[str, Dict[str, TensorSpec]]:
    """{group: {tensor: spec}} with groups ``top`` and ``l<i>``."""
    d, bf, f32 = cfg.hidden_size, "bfloat16", "float32"
    back = 1.0 / math.sqrt(cfg.published_layers)

    def lin(fan_in, fan_out, over=1.0, **kwargs):
        return TensorSpec((fan_in, fan_out), bf, "normal",
                          1.0 / (math.sqrt(fan_in) * over), **kwargs)

    def ones(width):
        return TensorSpec((width,), bf, "ones")

    specs = {"top": {
        "embed": TensorSpec((cfg.vocab_size, d), bf, "normal",
                            1.0 / cfg.embedding_multiplier),
        "final_norm": ones(d),
        "head": lin(d, cfg.vocab_size, cfg.lm_head_multiplier)}}
    heads, taps = cfg.mamba_n_heads, cfg.mamba_d_conv
    hq = cfg.num_attention_heads * cfg.head_dim
    hk = cfg.num_key_value_heads * cfg.head_dim
    conv_dim, inner = sum(cfg.conv_parts), cfg.intermediate_size
    into = cfg.attention_in_multiplier
    layer = {
        "input_norm": ones(d), "pre_ff_norm": ones(d),
        "in_proj": lin(d, sum(cfg.in_proj_parts), cfg.ssm_in_multiplier,
                       segments=tuple(
                           (width, 1.0 / m) for width, m in zip(
                               cfg.in_proj_parts, cfg.ssm_multipliers))),
        "conv_w": TensorSpec((conv_dim, taps), bf, "uniform",
                             1.0 / math.sqrt(taps)),
        "conv_b": TensorSpec((conv_dim,), bf, "uniform",
                             1.0 / math.sqrt(taps)),
        "dt_bias": TensorSpec((heads,), f32, "dt_bias", steps=(
            cfg.time_step_min, cfg.time_step_max, cfg.time_step_floor)),
        "a_log": TensorSpec((heads,), f32, "a_log"),
        "d": TensorSpec((heads,), f32, "ones"),
        "gnorm": ones(cfg.d_ssm),
        "out_proj": lin(cfg.d_ssm, d, cfg.ssm_out_multiplier / back),
        "q": lin(d, hq, into),
        "k": lin(d, hk, into * cfg.key_multiplier / KEY_GAIN),
        "v": lin(d, hk, into),
        "o": lin(hq, d, cfg.attention_out_multiplier / back),
        "gate": lin(d, inner, cfg.mlp_multipliers[0]),
        "up": lin(d, inner),
        "down": lin(inner, d, cfg.mlp_multipliers[1] / back)}
    for i in range(cfg.num_hidden_layers):
        specs["l%d" % i] = dict(layer)
    return specs


def params_per_layer(cfg: FalconH1Config) -> int:
    """The parameters of one block (the issue's 430,120,032 at the
    published sizes)."""
    return sum(math.prod(spec.shape)
               for spec in tensor_specs(cfg)["l0"].values())


def make_params(cfg: FalconH1Config, seed: int, held: Sequence[int],
                device, groups: Optional[Sequence[str]] = None):
    """The parameter tree ``network.forward`` reads (or the named
    groups of it), on ``device``."""
    return seeded.make_params(tensor_specs(cfg), seed, held, device, groups)


def reference_reader(cfg: FalconH1Config, seed: int, device):
    """``read(name, index=None)``: the stored values of tensor ``name``
    (``top.embed``, ``l3.in_proj``, ...) as float32 — of ``stored[index]``
    where an index is given, taken before the values are widened: the
    embedding and the head are 2.7 GB each as stored, and a reference
    that runs beside the program's weights reads the rows of its tokens
    and a block of the head's columns at a time."""
    import jax.numpy as jnp
    specs = tensor_specs(cfg)

    def read(name: str, index=None):
        group, tensor = name.split(".", 1)
        stored = seeded.make_tensor(seed, name, specs[group][tensor], (),
                                    device)
        if index is not None:
            stored = stored[index]
        return stored.astype(jnp.float32)
    return read


def save_recipe(path: str, config: dict, seed: int,
                held: Sequence[int] = ()) -> None:
    seeded.save_recipe(path, FAMILY, config, seed, held)


def load_recipe(path: str):
    """-> (FalconH1Config, seed, the experts held: none)."""
    recipe = seeded.read_recipe(path)
    return (FalconH1Config.from_published(recipe["config"]),
            int(recipe["seed"]), tuple(recipe["held_experts"]))
