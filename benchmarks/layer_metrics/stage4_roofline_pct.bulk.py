"""The least time the chip could take for the 256-channel residual stage over
the valid rows of the paired dispatches (the larger of operations over the
bf16 peak and bytes over the HBM bandwidth, ``stage_work`` of the file beside
the family's) over the device time under the named scope ``stage4`` in those
dispatches."""

NAME = "stage4_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "network"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import stages
    return stages.roofline_pct(facts, "stage4")
