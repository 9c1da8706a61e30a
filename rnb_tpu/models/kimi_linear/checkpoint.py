"""The tensors of a Kimi-Linear stack, made on the device from a seed by
the machinery the token families share (``rnb_tpu/models/seeded.py``:
the recipe, the draw, the reader the plain reference reads through).

Stored forms that differ from the published one, made once at set-up: a
routed expert's first two matrices (``gate``, ``up``) lie ``[held,
inner, hidden]``, the orientation the grouped product reads without a
relayout (``ops/moe.py``); latent attention's query weight lies
``[heads, hidden, 256]``, a head's 192 columns and 64 of zeros behind
them, so that one batched product writes the flash kernel's operand
heads-first and whole lanes wide (``seeded.TensorSpec.heads_first``).
The three KDA projections lie side by side as ``in_qkv``'s columns ``[q
| k | v]``, heads-major, and their three convolutions as the rows of
one ``conv_w``: a depthwise convolution knows no neighbour channel.

Initial values (all of them this repo's assumption: the published
checkpoint is trained, not initialised): embedding N(0, 1) so the
residual stream starts at a spread of one; every projection into a
mixer N(0, 1/fan_in), the low-rank pairs' second matrices too; every
projection back onto the residual stream (``o``, an MLP's or an
expert's last matrix) N(0, 1/fan_in) divided by sqrt(2 x 4), the
residual additions of one period of the pattern (as
``qwen3_next/checkpoint.py`` argues: by the published depth the layers
held would add a tenth of the stream's spread and the logits would
notice neither a mixer nor a fault in it); head and router N(0,
1/hidden); the router's correction bias N(0, 0.02^2): small, and not
zero, so that the choice reads it; norm weights 1, stored plain; the
latent attention's query weight times ``QUERY_GAIN``: with a gain of
one, random keys give a softmax so flat over thousands of keys that the
logits would notice neither the attention nor a fault in it (what
``minicpm-sala-l4`` found and ``qwen3-next-l4-ep2`` cured with 1.5 on
queries and on keys: the product of the two stands here on the
queries); ``A_log`` the log of uniform(1,
16) **a head** and ``dt_bias`` the inverse softplus of a step drawn
log-uniformly in 0.001 to 0.1 **a channel** (Mamba-2's draw, which the
delta-rule codes keep, drawn once a key channel here): with the gate's
low-rank term a head's 128 channels then fade at rates one to two
orders apart, over tens to thousands of tokens, and the scalar-gate
control arm (one mean rate a head) reads another model; the gate's
``f_b`` N(0, 1/128); the convolution uniform in
+-1/sqrt(taps).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

from rnb_tpu.models import seeded
from rnb_tpu.models.kimi_linear.network import KimiLinearConfig
from rnb_tpu.models.seeded import TensorSpec

FAMILY = "kimi_linear"
#: the step's draw: (min, max, floor)
TIME_STEPS = (0.001, 0.1, 1e-4)
#: the inner width of the two low-rank pairs: ``linear_attn_config``'s
#: ``head_dim`` (an assumption of the configuration file)
LOW_RANK = 128
#: the residual additions of one period: KDA KDA KDA MLA, each with its
#: feed-forward
PERIOD = 4
#: on latent attention's query weight: the scores' spread
QUERY_GAIN = 2.25


def query_columns(cfg: KimiLinearConfig):
    """``q``'s stored columns of one head: its own, then zeros up to
    whole lanes (``TensorSpec.heads_first``)."""
    return tuple(range(cfg.qk_head_dim)) \
        + (None,) * (cfg.query_lanes - cfg.qk_head_dim)


def tensor_specs(cfg: KimiLinearConfig, num_held: int
                 ) -> Dict[str, Dict[str, TensorSpec]]:
    """{group: {tensor: spec}} with groups ``top`` and ``l<i>``."""
    d, bf, f32 = cfg.hidden_size, "bfloat16", "float32"
    back = 1.0 / math.sqrt(2 * PERIOD)
    inner, shared = cfg.moe_intermediate_size, cfg.shared_intermediate_size
    heads, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    nope, value = cfg.qk_nope_head_dim, cfg.v_head_dim
    width, taps = cfg.kda_dim, cfg.short_conv_kernel_size

    def lin(fan_in, fan_out, scale=1.0):
        return TensorSpec((fan_in, fan_out), bf, "normal",
                          scale / math.sqrt(fan_in))

    def norm(size):
        return TensorSpec((size,), bf, "ones")

    def first():
        return TensorSpec((num_held, inner, d), bf, "normal",
                          1.0 / math.sqrt(d), per_expert=True,
                          transposed=True)

    specs = {"top": {
        "embed": TensorSpec((cfg.vocab_size, d), bf, "normal", 1.0),
        "final_norm": norm(d),
        "head": lin(d, cfg.vocab_size)}}
    for i in range(cfg.num_hidden_layers):
        layer = {"mixer_norm": norm(d), "ffn_norm": norm(d)}
        if cfg.is_attention(i):
            layer.update({
                "q": TensorSpec((heads, d, cfg.query_lanes), bf, "normal",
                                QUERY_GAIN / math.sqrt(d),
                                heads_first=(cfg.qk_head_dim,
                                             query_columns(cfg))),
                "kv_a": lin(d, rank + cfg.qk_rope_head_dim),
                "kv_a_norm": norm(rank),
                "kv_b": lin(rank, heads * (nope + value)),
                "o": lin(heads * value, d, back)})
        else:
            layer.update({
                "in_qkv": lin(d, 3 * width),
                "conv_w": TensorSpec((3 * width, taps), bf, "uniform",
                                     1.0 / math.sqrt(taps)),
                "in_b": lin(d, cfg.kda_num_heads),
                "f_a": lin(d, LOW_RANK),
                "f_b": lin(LOW_RANK, width),
                "dt_bias": TensorSpec((width,), f32, "dt_bias",
                                      steps=TIME_STEPS),
                "a_log": TensorSpec((cfg.kda_num_heads,), f32, "a_log"),
                "g_a": lin(d, LOW_RANK), "g_b": lin(LOW_RANK, width),
                "o_norm": norm(cfg.kda_head_dim),
                "o": lin(width, d, back)})
        if cfg.is_dense(i):
            layer.update({
                "gate": lin(d, cfg.intermediate_size),
                "up": lin(d, cfg.intermediate_size),
                "down": lin(cfg.intermediate_size, d, back)})
        else:
            layer.update({
                "router": lin(d, cfg.router_experts),
                "b_corr": TensorSpec((cfg.router_experts,), f32, "normal",
                                     0.02),
                "gate": first(), "up": first(),
                "down": TensorSpec((num_held, inner, d), bf, "normal",
                                   back / math.sqrt(inner),
                                   per_expert=True),
                "shared_gate": lin(d, shared), "shared_up": lin(d, shared),
                "shared_down": lin(shared, d, back)})
        specs["l%d" % i] = layer
    return specs


def make_params(cfg: KimiLinearConfig, seed: int, held: Sequence[int],
                device, groups: Optional[Sequence[str]] = None):
    """The parameter tree ``network.forward`` reads (or the named
    groups of it), on ``device``."""
    return seeded.make_params(tensor_specs(cfg, len(held)), seed, held,
                              device, groups)


def reference_reader(cfg: KimiLinearConfig, seed: int, device):
    """``read(name, expert_ids=None)``: see ``seeded.reference_reader``."""
    return seeded.reference_reader(tensor_specs(cfg, 1), seed, device)


def save_recipe(path: str, config: dict, seed: int,
                held: Sequence[int]) -> None:
    seeded.save_recipe(path, FAMILY, config, seed, held)


def load_recipe(path: str):
    """-> (KimiLinearConfig, seed, held expert ids)."""
    recipe = seeded.read_recipe(path)
    return (KimiLinearConfig.from_published(recipe["config"]),
            int(recipe["seed"]), tuple(recipe["held_experts"]))
