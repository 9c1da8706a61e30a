"""The tensors of a DeepSeek-V2 stack, made on the device from a seed
by the machinery the token families share (``rnb_tpu/models/seeded.py``:
the recipe, the draw, the reader the plain reference reads through).

Stored forms that differ from the published one, each made once at
set-up: a routed expert's first two matrices (``gate``, ``up``) lie
``[held, inner, hidden]``, the orientation the grouped product reads
without a relayout (``ops/moe.py``); the rotary columns of ``q_b`` (64
of each head's 192) and of ``kv_a`` (its last 64) are stored evens
first, then odds: the de-interleaving the published code makes at run
time before it rotates halves (``ops/rope.py``). ``q_b`` lies
heads-first, ``[heads, latent, columns]``, a head's columns the whole
lanes that ``ops/mla.py`` writes as the flash kernel's queries:
``[q_nope | q_pe | q_pe turned | 0]`` (the two rotary halves ``[x1 |
x2]`` once more as ``[-x2 | x1]``, so that the product itself brings
what the rotation multiplies by the sines), to the next 128 columns (at
the published sizes 128 + 64 + 64: the pad is the turned columns).
``kv_b``'s columns are a head's ``[k_nope | v]``, as published.

Initial scales (all of them this repo's assumption: the published
checkpoint is trained, not initialised): embedding N(0, 1) so the
residual stream starts at a spread of one; every projection into a
mixer, and from a latent, N(0, 1/fan_in); every projection back onto
the residual stream (``o``, an MLP's or expert's last matrix)
N(0, 1/fan_in) divided by sqrt(2 x published layers), two residual
additions a layer; head N(0, 1/hidden) so logits keep a spread of
about one; router N(0, 1/hidden); norm weights 1.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

from rnb_tpu.models import seeded
from rnb_tpu.models.deepseek_v2.network import DeepseekV2Config
from rnb_tpu.models.seeded import TensorSpec
from rnb_tpu.ops import mla

FAMILY = "deepseek_v2"


def query_columns(cfg: DeepseekV2Config):
    """``q_b``'s stored columns of one head (``TensorSpec.heads_first``)."""
    nope, half = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim // 2
    order = list(range(cfg.qk_head_dim)) \
        + [-1 - (nope + half + i) for i in range(half)] \
        + [nope + i for i in range(half)]
    return tuple(order) + (None,) * (mla.query_lanes(
        nope, cfg.qk_rope_head_dim) - len(order))


def tensor_specs(cfg: DeepseekV2Config, num_held: int
                 ) -> Dict[str, Dict[str, TensorSpec]]:
    """{group: {tensor: spec}} with groups ``top`` and ``l<i>``."""
    d, bf = cfg.hidden_size, "bfloat16"
    back = 1.0 / math.sqrt(2 * cfg.published_layers)
    heads = cfg.num_attention_heads
    rank, rotary = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    columns = query_columns(cfg)

    def lin(fan_in, fan_out, scale=1.0, halves=None):
        return TensorSpec((fan_in, fan_out), bf, "normal",
                          scale / math.sqrt(fan_in), halves=halves)

    def ones(width):
        return TensorSpec((width,), bf, "ones")

    specs = {"top": {
        "embed": TensorSpec((cfg.vocab_size, d), bf, "normal", 1.0),
        "final_norm": ones(d),
        "head": lin(d, cfg.vocab_size)}}
    for i in range(cfg.num_hidden_layers):
        layer = {
            "attn_norm": ones(d), "ffn_norm": ones(d),
            "q_a": lin(d, cfg.q_lora_rank),
            "q_a_norm": ones(cfg.q_lora_rank),
            "q_b": TensorSpec(
                (heads, cfg.q_lora_rank, len(columns)), bf, "normal",
                1.0 / math.sqrt(cfg.q_lora_rank),
                halves=(cfg.qk_head_dim, cfg.qk_nope_head_dim, rotary),
                heads_first=(cfg.qk_head_dim, columns)),
            "kv_a": lin(d, rank + rotary,
                        halves=(rank + rotary, rank, rotary)),
            "kv_a_norm": ones(rank),
            "kv_b": lin(rank, heads * (cfg.qk_nope_head_dim
                                       + cfg.v_head_dim)),
            "o": lin(heads * cfg.v_head_dim, d, back)}
        if cfg.is_dense(i):
            inner = cfg.intermediate_size
            layer.update({"gate": lin(d, inner), "up": lin(d, inner),
                          "down": lin(inner, d, back)})
        else:
            inner, shared = (cfg.moe_intermediate_size,
                             cfg.shared_intermediate_size)

            def first(inner=inner):
                return TensorSpec((num_held, inner, d), bf, "normal",
                                  1.0 / math.sqrt(d), per_expert=True,
                                  transposed=True)
            layer.update({
                "router": lin(d, cfg.router_experts),
                "gate": first(), "up": first(),
                "down": TensorSpec((num_held, inner, d), bf, "normal",
                                   back / math.sqrt(inner),
                                   per_expert=True),
                "shared_gate": lin(d, shared), "shared_up": lin(d, shared),
                "shared_down": lin(shared, d, back)})
        specs["l%d" % i] = layer
    return specs


def make_params(cfg: DeepseekV2Config, seed: int, held: Sequence[int],
                device, groups: Optional[Sequence[str]] = None):
    """The parameter tree ``network.forward`` reads (or the named
    groups of it), on ``device``."""
    return seeded.make_params(tensor_specs(cfg, len(held)), seed, held,
                              device, groups)


def reference_reader(cfg: DeepseekV2Config, seed: int, device):
    """``read(name, expert_ids=None)``: see ``seeded.reference_reader``."""
    return seeded.reference_reader(tensor_specs(cfg, 1), seed, device)


def save_recipe(path: str, config: dict, seed: int,
                held: Sequence[int]) -> None:
    seeded.save_recipe(path, FAMILY, config, seed, held)


def load_recipe(path: str):
    """-> (DeepseekV2Config, seed, held expert ids)."""
    recipe = seeded.read_recipe(path)
    return (DeepseekV2Config.from_published(recipe["config"]),
            int(recipe["seed"]), tuple(recipe["held_experts"]))
