"""Device milliseconds a dispatch under the scope ``attn/select/index`` in the
traced window: the learned indexer by itself (its three products, the key
head's LayerNorm, rotary, and the scores of every causal pair, 16 heads' ReLU
and weighted sum, written as sort keys). What the rest of ``attn/select`` takes
(the thresholds, the sets as bits) is ``sparse_select_ms_per_dispatch.bulk``
less this. Dispatches are counted as the roofline shares count them."""

NAME = "indexer_ms_per_dispatch.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    under = subscopes.seconds_under(facts, "attn/select/index")
    dispatches = subscopes.traced_dispatches(facts)
    if under is None or not dispatches:
        return None
    return 1e3 * under / dispatches
