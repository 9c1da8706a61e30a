"""(max - min) / mean of the videos each replica finished inside the window."""

NAME = "replica_imbalance_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "handoff"
MOVES = "videos_per_s"


def read(facts):
    return facts.replica_imbalance_pct()
