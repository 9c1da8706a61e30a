"""The operations each mechanism of a Phi-4-mini-flash stack needs, from
its sizes: what the algorithm asks for (2 a multiply-add) **on the path
the program takes**, the prefill exit — the layers up to the full
attention layer over every token, the cross-decoder once a request —
independent of how the program schedules it. Kept equal, by a test, to
the count the benchmark's family file makes on its own."""

from __future__ import annotations

from rnb_tpu.models.phi4_flash.network import Phi4FlashConfig

#: operations a (channel, state) of the selective scan takes a token:
#: the exponent's multiply, the exponential, two multiplies and an add
#: for the update, a multiply and an add for the read-out
SCAN_OPS_PER_STATE = 7


def mlp_flops(cfg: Phi4FlashConfig) -> int:
    """The gated MLP on one token (or one line)."""
    return 6 * cfg.hidden_size * cfg.intermediate_size


def scan_flops_per_token(cfg: Phi4FlashConfig) -> int:
    """The recurrence alone: every (channel, state) and the skip term."""
    return cfg.d_inner * (SCAN_OPS_PER_STATE * cfg.mamba_d_state + 2)


def mamba_flops_per_token(cfg: Phi4FlashConfig) -> int:
    """A Mamba-1 mixer: the four projections, the convolution, the
    scan, the gate."""
    d, di = cfg.hidden_size, cfg.d_inner
    wide = cfg.dt_rank + 2 * cfg.mamba_d_state
    proj = 2 * d * 2 * di + 2 * di * wide + 2 * cfg.dt_rank * di \
        + 2 * di * d
    return proj + 2 * cfg.mamba_d_conv * di + scan_flops_per_token(cfg) \
        + 4 * di


def pair_flops(cfg: Phi4FlashConfig) -> int:
    """Differential attention's operations a (query, key) over all
    heads: a head's scores over ``d`` columns and its softmax's product
    with the pair's ``2 d`` value columns (384 a head at 64)."""
    return cfg.num_attention_heads * 6 * cfg.head_dim


def attention_flops_per_token(cfg: Phi4FlashConfig, keys: float) -> int:
    """A differential attention mixer at a mean of ``keys`` keys a
    query."""
    d = cfg.hidden_size
    return int(2 * d * sum(cfg.qkv_parts) + 2 * cfg.qkv_parts[0] * d
               + keys * pair_flops(cfg))


def flops_per_token(cfg: Phi4FlashConfig, context: float,
                    window_keys: float) -> int:
    """The layers that run over every token: layers 0 .. n/2 + 1."""
    mambas = cfg.memory_layer // 2 + 1
    windows = cfg.memory_layer // 2
    return mambas * mamba_flops_per_token(cfg) \
        + windows * attention_flops_per_token(cfg, window_keys) \
        + attention_flops_per_token(cfg, context) \
        + (mambas + windows + 1) * mlp_flops(cfg)


def flops_per_request(cfg: Phi4FlashConfig, keys: float) -> int:
    """The cross-decoder on a request's one line, against ``keys`` keys
    (the request's length); the head is not counted, as in no family."""
    d, di = cfg.hidden_size, cfg.d_inner
    pairs = (cfg.num_hidden_layers - cfg.memory_layer - 2) // 2
    gmu = 2 * d * di + di + 2 * di * d
    cross = 2 * 2 * d * cfg.qkv_parts[0] + keys * pair_flops(cfg)
    return int(pairs * (gmu + cross + 2 * mlp_flops(cfg)))
