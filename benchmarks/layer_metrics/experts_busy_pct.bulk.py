"""Share of the device's operation time spent under the named scope
``experts``: the expert blocks (router, grouped product, shared expert)."""

NAME = "experts_busy_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "sparse experts"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import scopes
    return scopes.busy_pct(facts, "experts")
