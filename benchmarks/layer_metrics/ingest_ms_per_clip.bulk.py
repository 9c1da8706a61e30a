"""Device milliseconds a clip under the named scope ``ingest``: what stands in
front of the first convolution (colour conversion from packed 4:2:0 planes,
normalisation). The scope's self times inside the programs that pair with an
``exec{K}.model_call`` span, over those spans' valid rows
(``benchmarks/stages.py``)."""

NAME = "ingest_ms_per_clip.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "ingest kernels"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import stages
    return stages.ms_per_row(facts, "ingest")
