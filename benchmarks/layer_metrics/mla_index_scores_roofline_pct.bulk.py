"""The least time the chip could take for the indexer's scores in the traced
window over the device time of the custom calls named ``index_scores``
(``ops/indexed.index_keys``' kernel) where latent attention's full layers
call it: ``index_n_heads x index_head_dim x 2`` operations a causal pair,
the indexer's operands read and the causal pairs' sort keys written (the
family file's ``"index_scores"``): the larger of operations over the bf16
peak and bytes over the HBM bandwidth. A kernel that computes whole tiles
over the diagonal computes more than that, so the share cannot pass 100."""

NAME = "mla_index_scores_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"

KERNEL = "index_scores"


def read(facts):
    from benchmarks import subscopes
    try:
        return subscopes.roofline_pct(facts, "index_scores", kernel=KERNEL)
    except ValueError:
        # a family whose file counts no ``index_scores``
        return None
