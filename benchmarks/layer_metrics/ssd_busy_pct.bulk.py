"""Share of the device's operation time spent under the named scope
``ssd``: the Mamba-2 blocks (projections, convolution, scan, gated norm)."""

NAME = "ssd_busy_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "state-space scan"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import scopes
    return scopes.busy_pct(facts, "ssd")
