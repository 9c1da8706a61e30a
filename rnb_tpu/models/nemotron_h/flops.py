"""The operations and bytes each mechanism of a Nemotron-H stack
needs, from its sizes: what the algorithm asks for (2 a multiply-add),
independent of how the program schedules it. Kept equal, by a test, to
the count the benchmark's family file makes on its own."""

from __future__ import annotations

from rnb_tpu.models.nemotron_h.network import (ATTENTION, EXPERTS, MAMBA,
                                               NemotronHConfig)


def mamba_flops_per_token(cfg: NemotronHConfig) -> int:
    """One M block: the two projections, the convolution and the scan
    in its blocked form at chunk ``Q`` (within-row scores and their
    product with x, the row's end state, the incoming state's part)."""
    d, q = cfg.hidden_size, cfg.chunk_size
    heads, p = cfg.mamba_num_heads, cfg.mamba_head_dim
    g, n = cfg.n_groups, cfg.ssm_state_size
    proj = 2 * d * (cfg.d_inner + cfg.conv_dim + heads) \
        + 2 * cfg.d_inner * d
    conv = 2 * cfg.conv_kernel * cfg.conv_dim
    scan = 2 * g * q * n + 2 * heads * q * p + 2 * 2 * heads * p * n
    return proj + conv + scan


def attention_flops_per_token(cfg: NemotronHConfig, context: float) -> int:
    """One attention block at a mean causal context of ``context``
    keys a query."""
    d = cfg.hidden_size
    hq = cfg.num_attention_heads * cfg.head_dim
    hk = cfg.num_key_value_heads * cfg.head_dim
    return int(2 * d * (hq + 2 * hk) + 2 * hq * d + 4 * context * hq)


def expert_flops(cfg: NemotronHConfig) -> int:
    """One routed expert on one token."""
    return 4 * cfg.hidden_size * cfg.moe_intermediate_size


def experts_flops_per_token(cfg: NemotronHConfig,
                            held_per_token: float) -> int:
    """One E block: router, shared expert, and ``held_per_token``
    routed experts of those a token chose."""
    d = cfg.hidden_size
    return int(2 * d * cfg.router_experts
               + 4 * d * cfg.moe_shared_expert_intermediate_size
               + held_per_token * expert_flops(cfg))


def flops_per_token(cfg: NemotronHConfig, context: float,
                    held_per_token: float) -> int:
    """Every block of the pattern; the head runs once a request and is
    not counted here."""
    total = 0
    for kind in cfg.pattern:
        if kind == MAMBA:
            total += mamba_flops_per_token(cfg)
        elif kind == ATTENTION:
            total += attention_flops_per_token(cfg, context)
        elif kind == EXPERTS:
            total += experts_flops_per_token(cfg, held_per_token)
    return total
