"""FLOPs the traced window's finished clips needed / (summed device time of
the operations that ran x the bf16 peak): how close the stage program runs
to the compute roofline while it runs (compute-bound: 307 GFLOP against
some 0.1 GB a clip)."""

NAME = "net_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "network"
MOVES = "videos_per_s"


def read(facts):
    return facts.net_roofline_pct()
