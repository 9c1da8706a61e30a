"""Share of device operation time in operations that are not convolutions:
the ingest (colour conversion or IDCT, normalisation), reshapes, copies,
elementwise fusions."""

NAME = "nonconv_busy_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "ingest kernels"
MOVES = "videos_per_s"


def read(facts):
    return facts.nonconv_busy_pct()
