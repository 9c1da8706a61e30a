"""Share of the device's operation time spent under the named scope
``deltanet``: the Gated DeltaNet layers' mixers whole (the norm, the two input
products, the convolution, the delta rule, the gated output norm, the output
product and the residual addition)."""

NAME = "deltanet_busy_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "gated delta rule"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    under = subscopes.seconds_under(facts, "deltanet")
    whole = subscopes.device_seconds(facts)
    if under is None or not whole:
        return None
    return 100.0 * under / whole
