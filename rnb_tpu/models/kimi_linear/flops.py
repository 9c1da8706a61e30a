"""The operations each mechanism of a Kimi-Linear stack needs, from its
sizes: what the algorithm asks for (2 a multiply-add), independent of
how the program schedules it. Kept equal, by a test, to the count the
benchmark's family file makes on its own."""

from __future__ import annotations

from rnb_tpu.models.kimi_linear.checkpoint import LOW_RANK
from rnb_tpu.models.kimi_linear.network import KimiLinearConfig


def delta_rule_flops_per_token(cfg: KimiLinearConfig) -> int:
    """The recurrence's own, one KDA layer: a head's state of ``Dk x
    Dv`` is decayed (1), read by the key (2), written by the outer
    product (2) and read by the query (2)."""
    return 7 * cfg.kda_num_heads * cfg.kda_head_dim * cfg.kda_head_dim


def kda_params(cfg: KimiLinearConfig) -> int:
    """The products and the convolutions of one KDA mixer."""
    d, width = cfg.hidden_size, cfg.kda_dim
    return d * 3 * width + cfg.short_conv_kernel_size * 3 * width \
        + d * cfg.kda_num_heads + 2 * (d * LOW_RANK + LOW_RANK * width) \
        + width * d


def kda_flops_per_token(cfg: KimiLinearConfig) -> int:
    return 2 * kda_params(cfg) + delta_rule_flops_per_token(cfg)


def attention_params(cfg: KimiLinearConfig) -> int:
    """The four products of one latent-attention layer as published
    (192 query columns a head, not the stored 256)."""
    d, heads = cfg.hidden_size, cfg.num_attention_heads
    return d * heads * cfg.qk_head_dim \
        + d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) \
        + cfg.kv_lora_rank * heads * (cfg.qk_nope_head_dim + cfg.v_head_dim) \
        + heads * cfg.v_head_dim * d


def attention_score_flops_per_token(cfg: KimiLinearConfig,
                                    context: float) -> float:
    """Scores and values of one query against ``context`` keys."""
    return 2.0 * context * cfg.num_attention_heads \
        * (cfg.qk_head_dim + cfg.v_head_dim)


def mlp_flops(cfg: KimiLinearConfig, inner: int) -> int:
    """One gated MLP of width ``inner`` on one token."""
    return 6 * cfg.hidden_size * inner


def expert_flops(cfg: KimiLinearConfig) -> int:
    """One routed expert on one token."""
    return mlp_flops(cfg, cfg.moe_intermediate_size)


def experts_flops_per_token(cfg: KimiLinearConfig,
                            held_per_token: float) -> float:
    """One expert layer: router, the shared expert, and
    ``held_per_token`` routed experts of those a token chose."""
    return 2 * cfg.hidden_size * cfg.router_experts \
        + mlp_flops(cfg, cfg.shared_intermediate_size) \
        + held_per_token * expert_flops(cfg)


def flops_per_token(cfg: KimiLinearConfig, context: float,
                    held_per_token: float) -> int:
    """Every layer held; the head runs once a request and is not
    counted here."""
    attention = sum(cfg.is_attention(i)
                    for i in range(cfg.num_hidden_layers))
    return int(
        (cfg.num_hidden_layers - attention) * kda_flops_per_token(cfg)
        + attention * (2 * attention_params(cfg)
                       + attention_score_flops_per_token(cfg, context))
        + cfg.first_k_dense_replace
        * mlp_flops(cfg, cfg.intermediate_size)
        + cfg.num_expert_layers
        * experts_flops_per_token(cfg, held_per_token))
