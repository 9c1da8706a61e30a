"""Device milliseconds a dispatch under the scope ``attn/full`` in the traced
window: the mixers of the layers whose queries read every key of their request
at or before them, in a stack that also has layers with a window (the four
products, the query-key norms, the copies that lay queries, keys and values out
for the kernel, the kernel). Dispatches are counted as the roofline shares
count them."""

NAME = "full_attn_ms_per_dispatch.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    under = subscopes.seconds_under(facts, "attn/full")
    dispatches = subscopes.traced_dispatches(facts)
    if under is None or not dispatches:
        return None
    return 1e3 * under / dispatches
