"""The tensors of a Qwen3-Next stack, made on the device from a seed by
the machinery the token families share (``rnb_tpu/models/seeded.py``:
the recipe, the draw, the reader the plain reference reads through).

Stored forms that differ from the published one, made once at set-up: a
routed expert's first two matrices (``gate``, ``up``) lie ``[held,
inner, hidden]``, the orientation the grouped product reads without a
relayout (``ops/moe.py``). The columns of ``in_qkvz`` lie ``[q | k | v |
z]`` and of ``in_ba`` ``[b | a]``, each part heads-major (the published
code stores them interleaved a key head and reorders at run time: a
permutation of a drawn matrix's columns); ``q``'s columns are a head's
``[query | gate]``, as published.

Initial values (all of them this repo's assumption: the published
checkpoint is trained, not initialised): embedding N(0, 1) so the
residual stream starts at a spread of one; every projection into a
mixer N(0, 1/fan_in); every projection back onto the residual stream
(``o``, an expert's last matrix) N(0, 1/fan_in) divided by sqrt(2 x
full_attention_interval), the residual additions of one period of the
pattern (divided by the published depth's 96, the four layers held add
a tenth of the stream's spread, the logits are the embedding's, and
every stored matrix rounded through float8 reads 4% of their spread
where the stated precision reads 1.8%: the comparison would not notice
a fault in a mixer; as it is the same two read 12-19% and 2.5%, at the
tests' toy widths); head and router N(0, 1/hidden); the zero-centred norm weights uniform in +-0.1 (a trained
one is near and not at zero), but ``q_norm`` and ``k_norm`` 0.5, a gain
of 1.5 each: with gains of one, random keys give a softmax so flat over
thousands of keys that the attention's result is a hundredth of a
value's spread and the logits would notice neither the mechanism nor a
fault in it; the DeltaNet's output norm 1; ``A_log`` the log of
uniform(1, 16) and ``dt_bias`` the inverse softplus of a step drawn
log-uniformly in 0.001 to 0.1 (Mamba-2's draw, which the Gated DeltaNet
code keeps): a state then fades over tens to thousands of tokens and
crosses rows; the convolution uniform in +-1/sqrt(taps).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

from rnb_tpu.models import seeded
from rnb_tpu.models.qwen3_next.network import Qwen3NextConfig
from rnb_tpu.models.seeded import TensorSpec

FAMILY = "qwen3_next"
#: the step's draw: (min, max, floor)
TIME_STEPS = (0.001, 0.1, 1e-4)
QK_NORM = 0.5


def tensor_specs(cfg: Qwen3NextConfig, num_held: int
                 ) -> Dict[str, Dict[str, TensorSpec]]:
    """{group: {tensor: spec}} with groups ``top`` and ``l<i>``."""
    d, bf, f32 = cfg.hidden_size, "bfloat16", "float32"
    back = 1.0 / math.sqrt(2 * cfg.full_attention_interval)
    inner, shared = (cfg.moe_intermediate_size,
                     cfg.shared_expert_intermediate_size)
    hq, hk, dim = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    hv, taps = cfg.linear_num_value_heads, cfg.linear_conv_kernel_dim

    def lin(fan_in, fan_out, scale=1.0):
        return TensorSpec((fan_in, fan_out), bf, "normal",
                          scale / math.sqrt(fan_in))

    def centred(width):
        return TensorSpec((width,), bf, "uniform", 0.1)

    def first():
        return TensorSpec((num_held, inner, d), bf, "normal",
                          1.0 / math.sqrt(d), per_expert=True,
                          transposed=True)

    specs = {"top": {
        "embed": TensorSpec((cfg.vocab_size, d), bf, "normal", 1.0),
        "final_norm": centred(d),
        "head": lin(d, cfg.vocab_size)}}
    for i in range(cfg.num_hidden_layers):
        layer = {"mixer_norm": centred(d), "ffn_norm": centred(d)}
        if cfg.is_attention(i):
            layer.update({
                "q": lin(d, hq * 2 * dim), "k": lin(d, hk * dim),
                "v": lin(d, hk * dim),
                "q_norm": TensorSpec((dim,), bf, "ones", QK_NORM),
                "k_norm": TensorSpec((dim,), bf, "ones", QK_NORM),
                "o": lin(hq * dim, d, back)})
        else:
            layer.update({
                "in_qkvz": lin(d, cfg.conv_dim + cfg.value_dim),
                "in_ba": lin(d, 2 * hv),
                "conv_w": TensorSpec((cfg.conv_dim, taps), bf, "uniform",
                                     1.0 / math.sqrt(taps)),
                "dt_bias": TensorSpec((hv,), f32, "dt_bias",
                                      steps=TIME_STEPS),
                "a_log": TensorSpec((hv,), f32, "a_log"),
                "o_norm": TensorSpec((cfg.linear_value_head_dim,), bf,
                                     "ones"),
                "o": lin(cfg.value_dim, d, back)})
        layer.update({
            "router": lin(d, cfg.router_experts),
            "gate": first(), "up": first(),
            "down": TensorSpec((num_held, inner, d), bf, "normal",
                               back / math.sqrt(inner), per_expert=True),
            "shared_gate": lin(d, shared), "shared_up": lin(d, shared),
            "shared_down": lin(shared, d, back),
            "shared_w": lin(d, 1)})
        specs["l%d" % i] = layer
    return specs


def make_params(cfg: Qwen3NextConfig, seed: int, held: Sequence[int],
                device, groups: Optional[Sequence[str]] = None):
    """The parameter tree ``network.forward`` reads (or the named
    groups of it), on ``device``."""
    return seeded.make_params(tensor_specs(cfg, len(held)), seed, held,
                              device, groups)


def reference_reader(cfg: Qwen3NextConfig, seed: int, device):
    """``read(name, expert_ids=None)``: see ``seeded.reference_reader``."""
    return seeded.reference_reader(tensor_specs(cfg, 1), seed, device)


def save_recipe(path: str, config: dict, seed: int,
                held: Sequence[int]) -> None:
    seeded.save_recipe(path, FAMILY, config, seed, held)


def load_recipe(path: str):
    """-> (Qwen3NextConfig, seed, held expert ids)."""
    recipe = seeded.read_recipe(path)
    return (Qwen3NextConfig.from_published(recipe["config"]),
            int(recipe["seed"]), tuple(recipe["held_experts"]))
