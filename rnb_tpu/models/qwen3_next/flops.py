"""The operations each mechanism of a Qwen3-Next stack needs, from its
sizes: what the algorithm asks for (2 a multiply-add), independent of
how the program schedules it. Kept equal, by a test, to the count the
benchmark's family file makes on its own."""

from __future__ import annotations

from rnb_tpu.models.qwen3_next.network import Qwen3NextConfig


def delta_rule_flops_per_token(cfg: Qwen3NextConfig) -> int:
    """The recurrence's own, one DeltaNet layer: a value head's state of
    ``Dk x Dv`` is decayed (1), read by the key (2), written by the
    outer product (2) and read by the query (2)."""
    return 7 * cfg.linear_num_value_heads * cfg.linear_key_head_dim \
        * cfg.linear_value_head_dim


def deltanet_flops_per_token(cfg: Qwen3NextConfig) -> int:
    """One DeltaNet layer's mixer: the three products, the convolution
    and the rule."""
    d = cfg.hidden_size
    proj = 2 * d * (cfg.conv_dim + cfg.value_dim
                    + 2 * cfg.linear_num_value_heads) \
        + 2 * cfg.value_dim * d
    conv = 2 * cfg.linear_conv_kernel_dim * cfg.conv_dim
    return proj + conv + delta_rule_flops_per_token(cfg)


def attention_proj_flops_per_token(cfg: Qwen3NextConfig) -> int:
    """The four products of one attention layer (queries with their
    gates, keys, values, the output)."""
    d, dim = cfg.hidden_size, cfg.head_dim
    hq, hk = cfg.num_attention_heads, cfg.num_key_value_heads
    return 2 * d * (2 * hq + 2 * hk) * dim + 2 * hq * dim * d


def attention_score_flops_per_token(cfg: Qwen3NextConfig,
                                    context: float) -> float:
    """Scores and values of one query against ``context`` keys."""
    return 4.0 * context * cfg.num_attention_heads * cfg.head_dim


def mlp_flops(cfg: Qwen3NextConfig, inner: int) -> int:
    """One gated MLP of width ``inner`` on one token."""
    return 6 * cfg.hidden_size * inner


def expert_flops(cfg: Qwen3NextConfig) -> int:
    """One routed expert on one token."""
    return mlp_flops(cfg, cfg.moe_intermediate_size)


def experts_flops_per_token(cfg: Qwen3NextConfig,
                            held_per_token: float) -> float:
    """One expert layer: router, the shared expert and its gate, and
    ``held_per_token`` routed experts of those a token chose."""
    return 2 * cfg.hidden_size * (cfg.router_experts + 1) \
        + mlp_flops(cfg, cfg.shared_expert_intermediate_size) \
        + held_per_token * expert_flops(cfg)


def flops_per_token(cfg: Qwen3NextConfig, context: float,
                    held_per_token: float) -> int:
    """Every layer held; the head runs once a request and is not
    counted here."""
    return int(
        cfg.deltanet_layers * deltanet_flops_per_token(cfg)
        + cfg.attention_layers
        * (attention_proj_flops_per_token(cfg)
           + attention_score_flops_per_token(cfg, context))
        + cfg.num_hidden_layers
        * experts_flops_per_token(cfg, held_per_token))
