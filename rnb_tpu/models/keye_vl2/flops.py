"""The operations each mechanism of a Keye-VL-2.0 stack needs, from its
sizes: what the algorithm asks for (2 a multiply-add), independent of
how the program schedules it. Kept equal, by a test, to the count the
benchmark's family file makes on its own."""

from __future__ import annotations

from rnb_tpu.models.keye_vl2.network import KeyeVL2Config


def attention_proj_flops_per_token(cfg: KeyeVL2Config) -> int:
    """The four products of one layer's attention."""
    d, dim = cfg.hidden_size, cfg.head_dim
    hq, hk = cfg.num_attention_heads, cfg.num_key_value_heads
    return 2 * d * (hq + 2 * hk) * dim + 2 * hq * dim * d


def attention_score_flops_per_token(cfg: KeyeVL2Config,
                                    chosen: float) -> float:
    """Scores and values of one query against the ``chosen`` keys of
    its set."""
    return 4.0 * chosen * cfg.num_attention_heads * cfg.head_dim


def indexer_proj_flops_per_token(cfg: KeyeVL2Config) -> int:
    """The indexer's three products: queries, the one key head, the
    heads' weights."""
    heads, dim = cfg.indexer_num_heads, cfg.indexer_head_dim
    return 2 * cfg.hidden_size * (heads * dim + dim + heads)


def indexer_score_flops_per_token(cfg: KeyeVL2Config,
                                  causal: float) -> float:
    """One query's scores over the ``causal`` keys it may read."""
    return 2.0 * causal * cfg.indexer_num_heads * cfg.indexer_head_dim


def expert_flops(cfg: KeyeVL2Config) -> int:
    """One routed expert on one token."""
    return 6 * cfg.hidden_size * cfg.moe_intermediate_size


def experts_flops_per_token(cfg: KeyeVL2Config,
                            held_per_token: float) -> float:
    """One expert layer: the router and ``held_per_token`` routed
    experts of those a token chose."""
    return 2 * cfg.hidden_size * cfg.router_experts \
        + held_per_token * expert_flops(cfg)


def flops_per_token(cfg: KeyeVL2Config, causal: float, chosen: float,
                    held_per_token: float) -> int:
    """Every layer held, at ``causal`` keys a query may read and
    ``chosen`` it reads; the head runs once a request and is not counted
    here."""
    return int(cfg.num_hidden_layers * (
        attention_proj_flops_per_token(cfg)
        + attention_score_flops_per_token(cfg, chosen)
        + indexer_proj_flops_per_token(cfg)
        + indexer_score_flops_per_token(cfg, causal)
        + experts_flops_per_token(cfg, held_per_token)))
