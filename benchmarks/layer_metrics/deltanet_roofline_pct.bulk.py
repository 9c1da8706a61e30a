"""The least time the chip could take for the DeltaNet layers' mixers in the
traced window (the three products, the convolution and the delta rule by the
recurrence's own count: the larger of operations over the bf16 peak and bytes
over the HBM bandwidth, from the family file, valid tokens only) over the
device time under the scope ``deltanet``."""

NAME = "deltanet_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "gated delta rule"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    return subscopes.roofline_pct(facts, "deltanet", path="deltanet")
