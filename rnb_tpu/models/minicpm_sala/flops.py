"""The operations each mechanism of a MiniCPM-SALA stack needs, from its
sizes: what the algorithm asks for (2 a multiply-add), independent of
how the program schedules it — the sparse layers' attention over the
keys of the *chosen* blocks, never over the dense triangle. Kept equal,
by a test, to the count the benchmark's family file makes on its own."""

from __future__ import annotations

import numpy as np

from rnb_tpu.models.minicpm_sala.network import (LIGHTNING, SPARSE,
                                                 MinicpmSalaConfig)


def request_reads(cfg: MinicpmSalaConfig, length: int):
    """-> (keys, windows): over the queries of one request of ``length``
    tokens, the keys they attend to (all causal ones under ``dense_len``
    or with ``topk`` blocks or fewer; else ``topk`` blocks, the query's
    own up to itself) and the compressed keys they score (none in a
    request that does not select)."""
    sparse = cfg.sparse
    at = np.arange(int(length), dtype=np.int64)
    if length < sparse.dense_len:
        return int((at + 1).sum()), 0
    size = sparse.block_size
    keys = np.where(at // size + 1 <= sparse.topk, at + 1,
                    (sparse.topk - 1) * size + at % size + 1)
    windows = np.maximum(
        (at + 1 - sparse.kernel_size) // sparse.kernel_stride + 1, 0)
    return int(keys.sum()), int(windows.sum())


def mlp_flops(cfg: MinicpmSalaConfig) -> int:
    """The gated MLP on one token."""
    return 6 * cfg.hidden_size * cfg.intermediate_size


def sparse_proj_flops(cfg: MinicpmSalaConfig) -> int:
    """q, k, v, gate and o of one sparse layer on one token."""
    wide = cfg.num_attention_heads * cfg.head_dim
    narrow = cfg.num_key_value_heads * cfg.head_dim
    return 2 * cfg.hidden_size * (3 * wide + 2 * narrow)


def sparse_read_flops(cfg: MinicpmSalaConfig, keys: float,
                      windows: float) -> float:
    """Scores and values of one query over ``keys`` keys, and its
    scores over ``windows`` compressed keys."""
    wide = cfg.num_attention_heads * cfg.head_dim
    return 4.0 * keys * wide + 2.0 * windows * wide


def lightning_flops(cfg: MinicpmSalaConfig) -> int:
    """One lightning layer's mixer on one token: five projections and
    the blocked scan at ``chunk_size`` (a row's scores, scores x
    values, the row's end state and the incoming state's part)."""
    heads, dim, q = cfg.lightning_nh, cfg.lightning_head_dim, cfg.chunk_size
    return 2 * cfg.hidden_size * 5 * heads * dim \
        + heads * (4 * q * dim + 4 * dim * dim)


def flops_per_token(cfg: MinicpmSalaConfig, keys: float,
                    windows: float) -> int:
    """Every layer held, for a token that attends to ``keys`` keys and
    scores ``windows`` compressed keys in each sparse layer; the head
    runs once a request and is not counted here."""
    return int(
        len(cfg.layers_of(SPARSE))
        * (sparse_proj_flops(cfg) + sparse_read_flops(cfg, keys, windows))
        + len(cfg.layers_of(LIGHTNING)) * lightning_flops(cfg)
        + cfg.num_hidden_layers * mlp_flops(cfg))
