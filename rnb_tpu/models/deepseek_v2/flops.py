"""The operations each mechanism of a DeepSeek-V2 stack needs, from its
sizes: what the algorithm asks for (2 a multiply-add), in the expanded
form of latent attention, independent of how the program schedules it.
Kept equal, by a test, to the count the benchmark's family file makes
on its own."""

from __future__ import annotations

from rnb_tpu.models.deepseek_v2.network import DeepseekV2Config


def attention_proj_flops_per_token(cfg: DeepseekV2Config) -> int:
    """The five projections of one layer's latent attention."""
    d, heads = cfg.hidden_size, cfg.num_attention_heads
    return 2 * (d * cfg.q_lora_rank
                + cfg.q_lora_rank * heads * cfg.qk_head_dim
                + d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
                + cfg.kv_lora_rank * heads * (cfg.qk_nope_head_dim
                                              + cfg.v_head_dim)
                + heads * cfg.v_head_dim * d)


def attention_score_flops_per_token(cfg: DeepseekV2Config,
                                    context: float) -> float:
    """Scores and values of one query against ``context`` keys."""
    return 2.0 * context * cfg.num_attention_heads \
        * (cfg.qk_head_dim + cfg.v_head_dim)


def mlp_flops(cfg: DeepseekV2Config, inner: int) -> int:
    """One gated MLP of width ``inner`` on one token."""
    return 6 * cfg.hidden_size * inner


def expert_flops(cfg: DeepseekV2Config) -> int:
    """One routed expert on one token."""
    return mlp_flops(cfg, cfg.moe_intermediate_size)


def experts_flops_per_token(cfg: DeepseekV2Config,
                            held_per_token: float) -> float:
    """One expert layer: router, shared experts, and ``held_per_token``
    routed experts of those a token chose."""
    return 2 * cfg.hidden_size * cfg.router_experts \
        + mlp_flops(cfg, cfg.shared_intermediate_size) \
        + held_per_token * expert_flops(cfg)


def flops_per_token(cfg: DeepseekV2Config, context: float,
                    held_per_token: float) -> int:
    """Every layer held; the head runs once a request and is not
    counted here."""
    dense = cfg.first_k_dense_replace
    return int(
        cfg.num_hidden_layers
        * (attention_proj_flops_per_token(cfg)
           + attention_score_flops_per_token(cfg, context))
        + dense * mlp_flops(cfg, cfg.intermediate_size)
        + cfg.num_expert_layers
        * experts_flops_per_token(cfg, held_per_token))
