"""Share of the device's operation time spent under the named scope ``mlp``:
every layer's gated feed-forward with its norm and residual addition (a dense
model: two thirds of a token's operations at any length)."""

NAME = "mlp_busy_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "network"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    under = subscopes.seconds_under(facts, "mlp")
    whole = subscopes.device_seconds(facts)
    if under is None or not whole:
        return None
    return 100.0 * under / whole
