"""The tensors of a Nemotron-H stack, made on the device from a seed
by the machinery the token families share (``rnb_tpu/models/seeded.py``:
the recipe, the draw, the reader the plain reference reads through).

The routed experts' first matrix is published ``[experts, hidden,
inner]`` and lies on the device as ``[held, inner, hidden]``
(``TensorSpec.transposed``), the orientation the grouped product reads
without a relayout (``ops/moe.py``).

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

import math
from typing import Dict, Optional, Sequence

from rnb_tpu.models import seeded
from rnb_tpu.models.nemotron_h.network import (ATTENTION, EXPERTS, MAMBA,
                                               NemotronHConfig)
from rnb_tpu.models.seeded import TensorSpec

FAMILY = "nemotron_h"
B_CORR_STD = 0.02


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
                "dt_bias": TensorSpec((heads,), f32, "dt_bias", steps=(
                    cfg.time_step_min, cfg.time_step_max,
                    cfg.time_step_floor)),
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


def make_params(cfg: NemotronHConfig, seed: int, held: Sequence[int],
                device, groups: Optional[Sequence[str]] = None):
    """The parameter tree ``network.forward`` reads (or the named
    groups of it), on ``device``."""
    return seeded.make_params(tensor_specs(cfg, len(held)), seed, held,
                              device, groups)


def reference_reader(cfg: NemotronHConfig, seed: int, device):
    """``read(name, expert_ids=None)``: see ``seeded.reference_reader``."""
    return seeded.reference_reader(tensor_specs(cfg, 1), seed, device)


def save_recipe(path: str, config: dict, seed: int,
                held: Sequence[int]) -> None:
    seeded.save_recipe(path, FAMILY, config, seed, held)


def load_recipe(path: str):
    """-> (NemotronHConfig, seed, held expert ids)."""
    recipe = seeded.read_recipe(path)
    return (NemotronHConfig.from_published(recipe["config"]),
            int(recipe["seed"]), tuple(recipe["held_experts"]))
