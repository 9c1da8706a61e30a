"""Device milliseconds a dispatch under the scope ``attn/select`` in the
traced window: the sparse layers' choice of key blocks (compressed keys, the
heads' softmaxes over them, block scores, the forced blocks, top-k). Dispatches
are counted as the roofline shares count them."""

NAME = "sparse_select_ms_per_dispatch.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    under = subscopes.seconds_under(facts, "attn/select")
    dispatches = subscopes.traced_dispatches(facts)
    if under is None or not dispatches:
        return None
    return 1e3 * under / dispatches
