"""Device milliseconds a dispatch under the scope ``cross`` in the traced
window: a cross-decoder whole — the layers that hold no token-mixing state of
their own and read an earlier layer's keys, values and scan memory — as the
prefill exit leaves it: one line a request through its Gated Memory Units, its
cross-attention (one query a request against its request's keys) and its MLPs,
whose time is their weights' way from memory. Dispatches are counted as the
roofline shares count them."""

NAME = "cross_decoder_ms_per_dispatch.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "network"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    under = subscopes.seconds_under(facts, "cross")
    dispatches = subscopes.traced_dispatches(facts)
    if under is None or not dispatches:
        return None
    return 1e3 * under / dispatches
