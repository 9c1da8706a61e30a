"""The tensors of a Keye-VL-2.0 language stack, made on the device from
a seed by the machinery the token families share
(``rnb_tpu/models/seeded.py``: the recipe, the draw, the reader the
plain reference reads through).

Stored forms that differ from the published one, made once at set-up: a
routed expert's first two matrices (``gate``, ``up``) lie ``[held,
inner, hidden]``, the orientation the grouped product reads without a
relayout (``ops/moe.py``). The rotary columns need no reordering: the
published code rotates halves, as ``ops/rope.py`` does.

Initial values (all of them this repo's assumption: the published
checkpoint is trained, not initialised): embedding N(0, 1) so the
residual stream starts at a spread of one; every projection into a
mixer N(0, 1/fan_in); the two back onto the residual stream (``o``, an
expert's last matrix) N(0, 1/fan_in) times ``BACK``; head and router
N(0, 1/hidden); norm weights 1, but ``q_norm`` and ``k_norm``
``QK_NORM``, a gain of 1.5 each: with gains of one, random keys give a
softmax so flat over thousands of keys that what a query reads is the
mean of its keys' values whichever keys those are, and the logits would
tell neither the indexer's sets from all keys nor from the latest 2,048
(``benchmarks/families/keye_vl2.py`` has the counts); the indexer's
three matrices N(0, 1/hidden) — its scores then spread by about 0.5
over a query's keys, the ``topk``-th and the median of 10,000 by 0.4
apart, where bfloat16 operands move a score by 0.004 —, its key norm's
weight 1 and bias N(0, 0.1^2): small, and a reader that drops it
chooses other keys.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

from rnb_tpu.models import seeded
from rnb_tpu.models.keye_vl2.network import KeyeVL2Config
from rnb_tpu.models.seeded import TensorSpec

FAMILY = "keye_vl2"
QK_NORM = 1.5
#: the scale of the two projections back onto the stream: 1 / sqrt(2),
#: a layer's two additions (every layer is of one kind: the pattern's
#: period is one layer)
BACK = 1.0 / math.sqrt(2.0)
INDEX_BIAS_STD = 0.1


def tensor_specs(cfg: KeyeVL2Config, num_held: int
                 ) -> Dict[str, Dict[str, TensorSpec]]:
    """{group: {tensor: spec}} with groups ``top`` and ``l<i>``."""
    d, bf = cfg.hidden_size, "bfloat16"
    dim, inner = cfg.head_dim, cfg.moe_intermediate_size
    hq, hk = cfg.num_attention_heads * dim, cfg.num_key_value_heads * dim
    index = cfg.indexer_num_heads * cfg.indexer_head_dim

    def lin(fan_in, fan_out, scale=1.0):
        return TensorSpec((fan_in, fan_out), bf, "normal",
                          scale / math.sqrt(fan_in))

    def ones(width, scale=1.0):
        return TensorSpec((width,), bf, "ones", scale)

    def first():
        return TensorSpec((num_held, inner, d), bf, "normal",
                          1.0 / math.sqrt(d), per_expert=True,
                          transposed=True)

    specs = {"top": {
        "embed": TensorSpec((cfg.vocab_size, d), bf, "normal", 1.0),
        "final_norm": ones(d),
        "head": lin(d, cfg.vocab_size)}}
    for i in range(cfg.num_hidden_layers):
        specs["l%d" % i] = {
            "attn_norm": ones(d), "ffn_norm": ones(d),
            "q": lin(d, hq), "k": lin(d, hk), "v": lin(d, hk),
            "q_norm": ones(dim, QK_NORM), "k_norm": ones(dim, QK_NORM),
            "o": lin(hq, d, BACK),
            "index_q": lin(d, index),
            "index_k": lin(d, cfg.indexer_head_dim),
            "index_w": lin(d, cfg.indexer_num_heads),
            "index_k_norm": ones(cfg.indexer_head_dim),
            "index_k_bias": TensorSpec((cfg.indexer_head_dim,), bf,
                                       "normal", INDEX_BIAS_STD),
            "router": lin(d, cfg.router_experts),
            "gate": first(), "up": first(),
            "down": TensorSpec((num_held, inner, d), bf, "normal",
                               BACK / math.sqrt(inner), per_expert=True)}
    return specs


def make_params(cfg: KeyeVL2Config, seed: int, held: Sequence[int],
                device, groups: Optional[Sequence[str]] = None):
    """The parameter tree ``network.forward`` reads (or the named
    groups of it), on ``device``."""
    return seeded.make_params(tensor_specs(cfg, len(held)), seed, held,
                              device, groups)


def reference_reader(cfg: KeyeVL2Config, seed: int, device):
    """``read(name, expert_ids=None)``: see ``seeded.reference_reader``."""
    return seeded.reference_reader(tensor_specs(cfg, 1), seed, device)


def save_recipe(path: str, config: dict, seed: int,
                held: Sequence[int]) -> None:
    seeded.save_recipe(path, FAMILY, config, seed, held)


def load_recipe(path: str):
    """-> (KeyeVL2Config, seed, held expert ids)."""
    recipe = seeded.read_recipe(path)
    return (KeyeVL2Config.from_published(recipe["config"]),
            int(recipe["seed"]), tuple(recipe["held_experts"]))
