"""The operations each mechanism of a Falcon-H1 stack needs, from its
sizes: what the algorithm asks for (2 a multiply-add), independent of
how the program schedules it. Kept equal, by a test, to the count the
benchmark's family file makes on its own."""

from __future__ import annotations

from rnb_tpu.models.falcon_h1.network import FalconH1Config


def ssm_flops_per_token(cfg: FalconH1Config) -> int:
    """The state-space branch: the two projections, the convolution and
    the scan in its blocked form at chunk ``Q`` (within-row scores and
    their product with x, the row's end state, the incoming state's
    part)."""
    d, q = cfg.hidden_size, cfg.chunk_size
    heads, p = cfg.mamba_n_heads, cfg.mamba_d_head
    g, n = cfg.mamba_n_groups, cfg.mamba_d_state
    proj = 2 * d * sum(cfg.in_proj_parts) + 2 * cfg.d_ssm * d
    conv = 2 * cfg.mamba_d_conv * sum(cfg.conv_parts)
    scan = 2 * g * q * n + 2 * heads * q * p + 4 * heads * p * n
    return proj + conv + scan


def attention_flops_per_token(cfg: FalconH1Config, context: float) -> int:
    """The attention branch at a mean causal context of ``context`` keys
    a query."""
    d = cfg.hidden_size
    hq = cfg.num_attention_heads * cfg.head_dim
    hk = cfg.num_key_value_heads * cfg.head_dim
    return int(2 * d * (hq + 2 * hk) + 2 * hq * d + 4 * context * hq)


def mlp_flops(cfg: FalconH1Config) -> int:
    """The gated MLP on one token."""
    return 6 * cfg.hidden_size * cfg.intermediate_size


def flops_per_token(cfg: FalconH1Config, context: float) -> int:
    """Every block held; the head runs once a request and is not counted
    here."""
    return cfg.num_hidden_layers * (
        ssm_flops_per_token(cfg) + attention_flops_per_token(cfg, context)
        + mlp_flops(cfg))
