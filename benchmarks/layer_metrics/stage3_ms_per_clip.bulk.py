"""Device milliseconds a clip under the named scope ``stage3``: the
128-channel residual stage (4 blocks at 28 x 28 over 16 frames: 87 of a clip's
307 GFLOP). The scope's self times inside the programs that pair with an
``exec{K}.model_call`` span, over those spans' valid rows
(``benchmarks/stages.py``)."""

NAME = "stage3_ms_per_clip.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "network"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import stages
    return stages.ms_per_row(facts, "stage3")
