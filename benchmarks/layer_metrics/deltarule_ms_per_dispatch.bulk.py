"""Device milliseconds a dispatch under the scope ``deltanet/rule`` in the
traced window: the gated delta rule of every DeltaNet layer (the scores inside
a row, the triangular solve, the rows' affine maps, the scan over the rows that
carries the states, the read-out). Dispatches are counted as the roofline
shares count them."""

NAME = "deltarule_ms_per_dispatch.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "gated delta rule"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    under = subscopes.seconds_under(facts, "deltanet/rule")
    dispatches = subscopes.traced_dispatches(facts)
    if under is None or not dispatches:
        return None
    return 1e3 * under / dispatches
