"""The operations each mechanism of a Xing4.0 stack needs, from its
sizes: what the algorithm asks for (2 a multiply-add), independent of
how the program schedules it. The layer's are DeepSeek-V2's
(``models/deepseek_v2/flops.py``); the mappings' are counted here. Kept
equal, by a test, to the count the benchmark's family file makes on its
own."""

from __future__ import annotations

from rnb_tpu.models.deepseek_v2 import flops as deepseek
from rnb_tpu.models.xing4.network import Xing4Config


def hyper_flops_per_token(cfg: Xing4Config) -> int:
    """One sublayer's mappings on one token: the projection ``x^ phi``
    (n C by 2n + n^2), the statistic, ``H_res X`` and ``h_pre X`` with
    ``h_post^T y``. The Sinkhorn steps (n^2 numbers a token) are not
    counted."""
    n, c = cfg.hc_mult, cfg.hidden_size
    return 2 * n * c * (2 * n + n * n) + 2 * n * c + 2 * n * n * c \
        + 2 * n * c


def flops_per_token(cfg: Xing4Config, context: float,
                    held_per_token: float) -> int:
    """Every layer held, its two sublayers' mappings included; the head
    runs once a request and is not counted here."""
    return deepseek.flops_per_token(cfg, context, held_per_token) \
        + cfg.num_sublayers * hyper_flops_per_token(cfg)
