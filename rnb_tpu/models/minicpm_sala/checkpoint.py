"""The tensors of a MiniCPM-SALA stack, made on the device from a seed
by the machinery the token families share (``rnb_tpu/models/seeded.py``:
the recipe, the draw, the reader the plain reference reads through).
Every tensor is stored as published; the family holds no experts, so a
recipe's ``held_experts`` is empty.

Initial scales (all of them this repo's assumption: the published
checkpoint is trained, not initialised): embedding N(0, 1 /
scale_emb^2), so that the residual stream starts at a spread of one
behind the model's own factor of ``scale_emb``; every projection N(0, 1
/ fan_in), the ones back onto the residual stream too (the model
scales each addition by ``scale_depth / sqrt(layers)`` itself); head
N(0, 1 / hidden); norm weights 1, but a sparse layer's ``q_norm`` and
``k_norm``: ``QK_GAIN``. With gains of one, random keys give scores of
spread one and a softmax so flat over thousands of keys that the
layer's output is a hundredth of a value's spread: the logits would not
see the mechanism this family is here for, nor a fault in it. The gains
give scores a spread of 2.5, a few dominant keys a query.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

from rnb_tpu.models import seeded
from rnb_tpu.models.minicpm_sala.network import (LIGHTNING, SPARSE,
                                                 MinicpmSalaConfig)
from rnb_tpu.models.seeded import TensorSpec

FAMILY = "minicpm_sala"
#: the weights of a sparse layer's (q_norm, k_norm)
QK_GAIN = (2.0, 1.25)


def tensor_specs(cfg: MinicpmSalaConfig, num_held: int = 0
                 ) -> Dict[str, Dict[str, TensorSpec]]:
    """{group: {tensor: spec}} with groups ``top`` and ``l<i>``."""
    d, bf = cfg.hidden_size, "bfloat16"

    def lin(fan_in, fan_out):
        return TensorSpec((fan_in, fan_out), bf, "normal",
                          1.0 / math.sqrt(fan_in))

    def ones(width, value=1.0):
        return TensorSpec((width,), bf, "ones", value)

    specs = {"top": {
        "embed": TensorSpec((cfg.vocab_size, d), bf, "normal",
                            1.0 / cfg.scale_emb),
        "final_norm": ones(d),
        "head": lin(d, cfg.vocab_size)}}
    for i, kind in enumerate(cfg.mixer_types):
        inner = cfg.intermediate_size
        layer = {"attn_norm": ones(d), "ffn_norm": ones(d),
                 "gate_mlp": lin(d, inner), "up": lin(d, inner),
                 "down": lin(inner, d)}
        if kind == SPARSE:
            dim = cfg.head_dim
            hq, hk = (cfg.num_attention_heads * dim,
                      cfg.num_key_value_heads * dim)
            layer.update({"q": lin(d, hq), "k": lin(d, hk),
                          "v": lin(d, hk), "gate": lin(d, hq),
                          "o": lin(hq, d), "q_norm": ones(dim, QK_GAIN[0]),
                          "k_norm": ones(dim, QK_GAIN[1])})
        elif kind == LIGHTNING:
            dim = cfg.lightning_head_dim
            wide = cfg.lightning_nh * dim
            layer.update({"q": lin(d, wide), "k": lin(d, wide),
                          "v": lin(d, wide), "gate": lin(d, wide),
                          "o": lin(wide, d), "q_norm": ones(dim),
                          "k_norm": ones(dim), "o_norm": ones(wide)})
        specs["l%d" % i] = layer
    return specs


def make_params(cfg: MinicpmSalaConfig, seed: int, held: Sequence[int],
                device, groups: Optional[Sequence[str]] = None):
    """The parameter tree ``network.forward`` reads (or the named
    groups of it), on ``device``."""
    return seeded.make_params(tensor_specs(cfg), seed, held, device, groups)


def reference_reader(cfg: MinicpmSalaConfig, seed: int, device):
    """``read(name)``: see ``seeded.reference_reader``."""
    return seeded.reference_reader(tensor_specs(cfg), seed, device)


def save_recipe(path: str, config: dict, seed: int,
                held: Sequence[int] = ()) -> None:
    seeded.save_recipe(path, FAMILY, config, seed, held)


def load_recipe(path: str):
    """-> (MinicpmSalaConfig, seed, the experts held: none)."""
    recipe = seeded.read_recipe(path)
    return (MinicpmSalaConfig.from_published(recipe["config"]),
            int(recipe["seed"]), tuple(recipe["held_experts"]))
