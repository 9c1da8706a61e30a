"""The parameters and the operations each mechanism of a dots3-note
stack needs, from its sizes: what the algorithm asks for (2 a
multiply-add), independent of how the program schedules it. Kept equal,
by a test, to the count the benchmark's family file makes on its own."""

from __future__ import annotations

from rnb_tpu.models.dots3_note.network import Dots3NoteConfig, Geometry


def index_params(cfg: Dots3NoteConfig) -> int:
    """The indexer's three matrices: queries from the query latent, the
    one key head, the heads' weights."""
    heads, dim = cfg.index_n_heads, cfg.index_head_dim
    return cfg.full.q_rank * heads * dim + cfg.hidden_size * (dim + heads)


def mixer_params(cfg: Dots3NoteConfig, geo: Geometry) -> int:
    """One mixer's matrices: the five products and the gate, and in a
    full layer the indexer's."""
    d = cfg.hidden_size
    count = d * geo.q_rank + geo.q_rank * geo.heads * geo.qk_dim \
        + d * (geo.kv_rank + geo.rotary) \
        + geo.kv_rank * geo.heads * (geo.nope + geo.value) \
        + geo.heads * geo.value * d + d * geo.heads
    return count + (index_params(cfg) if geo == cfg.full else 0)


def expert_params(cfg: Dots3NoteConfig) -> int:
    return 3 * cfg.hidden_size * cfg.moe_intermediate_size


def held_params(cfg: Dots3NoteConfig, held: int) -> int:
    """Every matrix of the layers held, ``held`` routed experts a sparse
    layer, embedding and head over ``vocab_size``; the norms' vectors
    and the correction bias left out."""
    d = cfg.hidden_size
    total = 2 * cfg.vocab_size * d
    for i in range(cfg.num_hidden_layers):
        total += mixer_params(cfg, cfg.geometry(i))
        if cfg.is_dense(i):
            total += 3 * d * cfg.intermediate_size
        else:
            total += held * expert_params(cfg) + d * cfg.router_experts \
                + 3 * d * cfg.shared_intermediate_size
    return total


def attention_read_flops(geo: Geometry, keys: float) -> float:
    """Scores and values of one query over ``keys`` keys, every head."""
    return 2.0 * keys * geo.heads * (geo.qk_dim + geo.value)


def index_score_flops(cfg: Dots3NoteConfig, causal: float) -> float:
    """One query's index scores over the ``causal`` keys it may read."""
    return 2.0 * causal * cfg.index_n_heads * cfg.index_head_dim


def experts_flops_per_token(cfg: Dots3NoteConfig,
                            held_per_token: float) -> float:
    """One sparse layer: the router, the shared expert and
    ``held_per_token`` routed experts of those a token chose."""
    return 2 * cfg.hidden_size * cfg.router_experts \
        + 6 * cfg.hidden_size * cfg.shared_intermediate_size \
        + held_per_token * 2 * expert_params(cfg)


def flops_per_token(cfg: Dots3NoteConfig, causal: float, chosen: float,
                    window: float, held_per_token: float) -> int:
    """Every layer held, at ``causal`` keys a full layer's query may
    read, ``chosen`` it reads and ``window`` a sliding layer's reads; the
    head runs once a request and is not counted here."""
    total = 0.0
    for i in range(cfg.num_hidden_layers):
        geo = cfg.geometry(i)
        total += 2 * mixer_params(cfg, geo)
        if cfg.is_sliding(i):
            total += attention_read_flops(geo, window)
        else:
            total += index_score_flops(cfg, causal) \
                + attention_read_flops(geo, chosen)
        total += 6 * cfg.hidden_size * cfg.intermediate_size \
            if cfg.is_dense(i) \
            else experts_flops_per_token(cfg, held_per_token)
    return int(total)
