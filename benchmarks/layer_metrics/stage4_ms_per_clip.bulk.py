"""Device milliseconds a clip under the named scope ``stage4``: the
256-channel residual stage (6 blocks at 14 x 14 over 8 frames: 66 of a clip's
307 GFLOP). The scope's self times inside the programs that pair with an
``exec{K}.model_call`` span, over those spans' valid rows
(``benchmarks/stages.py``)."""

NAME = "stage4_ms_per_clip.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "network"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import stages
    return stages.ms_per_row(facts, "stage4")
