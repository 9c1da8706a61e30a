"""Share of the device's operation time spent under the named scope
``attn``: the attention blocks (projections and the flash kernel)."""

NAME = "attn_busy_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import scopes
    return scopes.busy_pct(facts, "attn")
