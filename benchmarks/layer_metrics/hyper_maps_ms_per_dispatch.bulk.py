"""Device milliseconds a dispatch under the scope ``hyper/maps`` in the traced
window: the mappings alone — the statistic over a token's stream, its
projection onto 2n + n^2 columns, the sigmoids, ``exp`` and the
``hc_sinkhorn_iters`` Sinkhorn steps on every token's n x n matrix, tokens
along lanes — without the two mixings: the Sinkhorn iteration's own cost, and
what a later PR that fuses the mappings into the way in takes off the path (the
metric then falls silent). Dispatches are counted as the roofline shares count
them."""

NAME = "hyper_maps_ms_per_dispatch.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "residual stream"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    under = subscopes.seconds_under(facts, "hyper/maps")
    dispatches = subscopes.traced_dispatches(facts)
    if under is None or not dispatches:
        return None
    return 1e3 * under / dispatches
