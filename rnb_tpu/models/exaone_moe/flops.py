"""The operations each mechanism of a K-EXAONE stack needs, from its
sizes: what the algorithm asks for (2 a multiply-add), independent of
how the program schedules it. Kept equal, by a test, to the count the
benchmark's family file makes on its own."""

from __future__ import annotations

from rnb_tpu.models.exaone_moe.network import ExaoneMoeConfig


def attention_proj_flops_per_token(cfg: ExaoneMoeConfig) -> int:
    """The four projections of one layer's attention."""
    dim = cfg.head_dim
    return 2 * cfg.hidden_size * dim * (2 * cfg.num_attention_heads
                                        + 2 * cfg.num_key_value_heads)


def attention_score_flops_per_token(cfg: ExaoneMoeConfig,
                                    keys: float) -> float:
    """Scores and values of one query against ``keys`` keys: a full
    layer's mean context, a sliding layer's mean of ``min(context,
    sliding_window)``."""
    return 4.0 * keys * cfg.num_attention_heads * cfg.head_dim


def mlp_flops(cfg: ExaoneMoeConfig, inner: int) -> int:
    """One gated MLP of width ``inner`` on one token."""
    return 6 * cfg.hidden_size * inner


def expert_flops(cfg: ExaoneMoeConfig) -> int:
    """One routed expert on one token."""
    return mlp_flops(cfg, cfg.moe_intermediate_size)


def experts_flops_per_token(cfg: ExaoneMoeConfig,
                            held_per_token: float) -> float:
    """One expert layer: router, shared experts, and ``held_per_token``
    routed experts of those a token chose."""
    return 2 * cfg.hidden_size * cfg.router_experts \
        + mlp_flops(cfg, cfg.shared_intermediate_size) \
        + held_per_token * expert_flops(cfg)


def flops_per_token(cfg: ExaoneMoeConfig, context: float,
                    window_keys: float, held_per_token: float) -> int:
    """Every layer held, at a full layer's mean ``context`` and a
    sliding layer's mean ``window_keys``; the head runs once a request
    and is not counted here."""
    sliding = cfg.sliding_layers
    full = cfg.num_hidden_layers - sliding
    dense = cfg.num_hidden_layers - cfg.num_expert_layers
    return int(
        cfg.num_hidden_layers * attention_proj_flops_per_token(cfg)
        + full * attention_score_flops_per_token(cfg, context)
        + sliding * attention_score_flops_per_token(cfg, window_keys)
        + dense * mlp_flops(cfg, cfg.intermediate_size)
        + cfg.num_expert_layers
        * experts_flops_per_token(cfg, held_per_token))
