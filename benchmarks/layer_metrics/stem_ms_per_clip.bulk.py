"""Device milliseconds a clip under the named scope ``stem``: the stem (the
7x7 spatial and 3-frame temporal convolution to 64 channels at half the
frame's size, batch norm, ReLU). The scope's self times inside the programs
that pair with an ``exec{K}.model_call`` span, over those spans' valid rows
(``benchmarks/stages.py``)."""

NAME = "stem_ms_per_clip.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "network"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import stages
    return stages.ms_per_row(facts, "stem")
