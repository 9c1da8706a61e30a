"""The tensors of a Xing4.0 stack, made on the device from a seed by the
machinery the token families share (``rnb_tpu/models/seeded.py``).

A layer's attention, MLP and experts are DeepSeek-V2's tensors under
DeepSeek-V2's names, stored forms and scales
(``models/deepseek_v2/checkpoint.tensor_specs``, whose back-projections
divide by sqrt(2 x published layers): 2 x 40 here); an expert layer adds
the router's correction bias ``b_corr`` (float32, N(0, 0.02^2), as
K-EXAONE's). Each sublayer (``attn``, ``ffn``) adds its mappings
(``ops/hyper.py``):

- ``<sub>_hc_phi``: published ``(n C, 2n + n^2)``, the columns pre |
  post | res (row-major), N(0, 1 / (n C)): a unit-spread ``x^`` gives
  logits of spread one;
- ``<sub>_hc_alpha``: (3,) float32, **1** (the paper starts at 0.01;
  at one the dynamic term is as large as the static one, so that a
  comparison sees it);
- ``<sub>_hc_bias``: (2n + n^2,) float32, N(0, 1), the ``res`` part plus
  3 on its diagonal (``B_res = 3 I + N(0, 1)``: a stream mostly keeps to
  itself, and 20 Sinkhorn steps are not yet where 5 are).

All of them this repo's assumption: the published checkpoint is
trained, not initialised.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

from rnb_tpu.models import seeded
from rnb_tpu.models.deepseek_v2 import checkpoint as deepseek
from rnb_tpu.models.seeded import TensorSpec
from rnb_tpu.models.xing4.network import Xing4Config
from rnb_tpu.ops import hyper

FAMILY = "xing4"
B_CORR_STD = 0.02
#: ``B_res``'s diagonal
RES_DIAGONAL = 3.0
SUBLAYERS = ("attn", "ffn")


def tensor_specs(cfg: Xing4Config, num_held: int
                 ) -> Dict[str, Dict[str, TensorSpec]]:
    """{group: {tensor: spec}} with groups ``top`` and ``l<i>``."""
    n, wide = cfg.hc_mult, cfg.hc_mult * cfg.hidden_size
    rows = hyper.rows_of(n)
    diagonal = tuple(RES_DIAGONAL * (i == j) for i in range(n)
                     for j in range(n))
    specs = deepseek.tensor_specs(cfg, num_held)
    for i in range(cfg.num_hidden_layers):
        layer = specs["l%d" % i]
        if not cfg.is_dense(i):
            layer["b_corr"] = TensorSpec((cfg.router_experts,), "float32",
                                         "normal", B_CORR_STD)
        for sub in SUBLAYERS:
            layer.update({
                sub + "_hc_phi": TensorSpec(
                    (wide, rows), "bfloat16", "normal",
                    1.0 / math.sqrt(wide)),
                sub + "_hc_alpha": TensorSpec((3,), "float32", "ones"),
                sub + "_hc_bias": TensorSpec(
                    (rows,), "float32", "normal", 1.0,
                    offsets=(0.0,) * (2 * n) + diagonal)})
    return specs


def make_params(cfg: Xing4Config, seed: int, held: Sequence[int],
                device, groups: Optional[Sequence[str]] = None):
    """The parameter tree ``network.forward`` reads (or the named
    groups of it), on ``device``."""
    return seeded.make_params(tensor_specs(cfg, len(held)), seed, held,
                              device, groups)


def reference_reader(cfg: Xing4Config, seed: int, device):
    """``read(name, expert_ids=None)``: see ``seeded.reference_reader``."""
    return seeded.reference_reader(tensor_specs(cfg, 1), seed, device)


def save_recipe(path: str, config: dict, seed: int,
                held: Sequence[int]) -> None:
    seeded.save_recipe(path, FAMILY, config, seed, held)


def load_recipe(path: str):
    """-> (Xing4Config, seed, held expert ids)."""
    recipe = seeded.read_recipe(path)
    return (Xing4Config.from_published(recipe["config"]),
            int(recipe["seed"]), tuple(recipe["held_experts"]))
