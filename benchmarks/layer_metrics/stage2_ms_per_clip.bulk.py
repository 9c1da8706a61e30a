"""Device milliseconds a clip under the named scope ``stage2``: the 64-channel
residual stage (3 blocks at 56 x 56 over all 32 frames: 133 of a clip's 307
GFLOP). The scope's self times inside the programs that pair with an
``exec{K}.model_call`` span, over those spans' valid rows
(``benchmarks/stages.py``)."""

NAME = "stage2_ms_per_clip.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "network"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import stages
    return stages.ms_per_row(facts, "stage2")
