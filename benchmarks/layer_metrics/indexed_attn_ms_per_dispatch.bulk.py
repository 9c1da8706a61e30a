"""Device milliseconds a dispatch under the scope ``attn/kernel`` in the traced
window: the attention kernel under the indexer's sets, with the tile table each
dispatch builds for it and the copies that lay queries, keys and values out.
Dispatches are counted as the roofline shares count them."""

NAME = "indexed_attn_ms_per_dispatch.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    under = subscopes.seconds_under(facts, "attn/kernel")
    dispatches = subscopes.traced_dispatches(facts)
    if under is None or not dispatches:
        return None
    return 1e3 * under / dispatches
