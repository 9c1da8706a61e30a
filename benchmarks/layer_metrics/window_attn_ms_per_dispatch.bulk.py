"""Device milliseconds a dispatch under the scope ``attn/window`` in the
traced window: the mixers of the layers whose queries read a window of keys
(the four products, the query-key norms, the rotary, the copies that lay
queries, keys and values out for the kernel, the kernel). Dispatches are
counted as the roofline shares count them."""

NAME = "window_attn_ms_per_dispatch.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    under = subscopes.seconds_under(facts, "attn/window")
    dispatches = subscopes.traced_dispatches(facts)
    if under is None or not dispatches:
        return None
    return 1e3 * under / dispatches
