"""The least time the chip could take for the lightning layers' mixers in the
traced window (five projections and the blocked scan with unit steps: the
larger of operations over the bf16 peak and bytes over the HBM bandwidth, from
the family file, valid tokens only) over the device time under the scope
``ssd``."""

NAME = "lightning_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "state-space scan"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    return subscopes.roofline_pct(facts, "ssd", path="ssd")
