"""Peak device memory of the fullest device under open-loop arrivals."""

NAME = "hbm_peak_gib.open"
UNIT = "GiB"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "device"
MOVES = "videos_per_s"


def read(facts):
    return facts.hbm_peak_gib()
