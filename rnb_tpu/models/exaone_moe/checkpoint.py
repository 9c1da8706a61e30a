"""The tensors of a K-EXAONE stack, made on the device from a seed by
the machinery the token families share (``rnb_tpu/models/seeded.py``:
the recipe, the draw, the reader the plain reference reads through).

Stored forms that differ from the published one, each made once at
set-up: a routed expert's first two matrices (``gate``, ``up``) lie
``[held, inner, hidden]``, the orientation the grouped product reads
without a relayout (``ops/moe.py``). The rotary columns need no
reordering: the published code rotates halves, as ``ops/rope.py`` does.

Initial scales (all of them this repo's assumption: the published
checkpoint is trained, not initialised): embedding N(0, 1) so the
residual stream starts at a spread of one; every projection N(0,
1/fan_in) (a norm stands behind every mixer and feed-forward, so what
they add to the stream has a spread of one whatever the scale of their
last matrix); head N(0, 1/hidden) so logits keep a spread of about one;
router N(0, 1/hidden); norm weights 1, the query and key norms' too
(the siblings that norm *in front* of the mixer gave theirs a gain of
1.5, lest a softmax flat over thousands of random keys hide the
attention from the logits; here the norm behind the mixer brings its
term to a spread of one however flat the softmax, and at the tests' toy
widths the sharper scores doubled the bfloat16 reading, 2-3% to 4-10%
of the spread); the router's correction bias N(0, 0.02^2): small, and
it changes choices (as ``nemotron_h`` draws its).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

from rnb_tpu.models import seeded
from rnb_tpu.models.exaone_moe.network import ExaoneMoeConfig
from rnb_tpu.models.seeded import TensorSpec

FAMILY = "exaone_moe"
B_CORR_STD = 0.02


def tensor_specs(cfg: ExaoneMoeConfig, num_held: int
                 ) -> Dict[str, Dict[str, TensorSpec]]:
    """{group: {tensor: spec}} with groups ``top`` and ``l<i>``."""
    d, bf = cfg.hidden_size, "bfloat16"
    dim = cfg.head_dim
    hq, hk = cfg.num_attention_heads * dim, cfg.num_key_value_heads * dim

    def lin(fan_in, fan_out):
        return TensorSpec((fan_in, fan_out), bf, "normal",
                          1.0 / math.sqrt(fan_in))

    def ones(width):
        return TensorSpec((width,), bf, "ones")

    specs = {"top": {
        "embed": TensorSpec((cfg.vocab_size, d), bf, "normal", 1.0),
        "final_norm": ones(d),
        "head": lin(d, cfg.vocab_size)}}
    for i in range(cfg.num_hidden_layers):
        layer = {
            "q": lin(d, hq), "k": lin(d, hk), "v": lin(d, hk),
            "o": lin(hq, d), "q_norm": ones(dim), "k_norm": ones(dim),
            "attn_norm": ones(d), "ffn_norm": ones(d)}
        if cfg.is_dense(i):
            inner = cfg.intermediate_size
            layer.update({"gate": lin(d, inner), "up": lin(d, inner),
                          "down": lin(inner, d)})
        else:
            inner, shared = (cfg.moe_intermediate_size,
                             cfg.shared_intermediate_size)

            def first(inner=inner):
                return TensorSpec((num_held, inner, d), bf, "normal",
                                  1.0 / math.sqrt(d), per_expert=True,
                                  transposed=True)
            layer.update({
                "router": lin(d, cfg.router_experts),
                "b_corr": TensorSpec((cfg.router_experts,), "float32",
                                     "normal", B_CORR_STD),
                "gate": first(), "up": first(),
                "down": TensorSpec((num_held, inner, d), bf, "normal",
                                   1.0 / math.sqrt(inner),
                                   per_expert=True),
                "shared_gate": lin(d, shared), "shared_up": lin(d, shared),
                "shared_down": lin(shared, d)})
        specs["l%d" % i] = layer
    return specs


def make_params(cfg: ExaoneMoeConfig, seed: int, held: Sequence[int],
                device, groups: Optional[Sequence[str]] = None):
    """The parameter tree ``network.forward`` reads (or the named
    groups of it), on ``device``."""
    return seeded.make_params(tensor_specs(cfg, len(held)), seed, held,
                              device, groups)


def reference_reader(cfg: ExaoneMoeConfig, seed: int, device):
    """``read(name, expert_ids=None)``: see ``seeded.reference_reader``."""
    return seeded.reference_reader(tensor_specs(cfg, 1), seed, device)


def save_recipe(path: str, config: dict, seed: int,
                held: Sequence[int]) -> None:
    seeded.save_recipe(path, FAMILY, config, seed, held)


def load_recipe(path: str):
    """-> (ExaoneMoeConfig, seed, held expert ids)."""
    recipe = seeded.read_recipe(path)
    return (ExaoneMoeConfig.from_published(recipe["config"]),
            int(recipe["seed"]), tuple(recipe["held_experts"]))
