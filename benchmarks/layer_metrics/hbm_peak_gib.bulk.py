"""`memory_stats()["peak_bytes_in_use"]` of the fullest device after the
window, before the reference check: what the size floor is read against."""

NAME = "hbm_peak_gib.bulk"
UNIT = "GiB"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "device"
MOVES = "videos_per_s"


def read(facts):
    return facts.hbm_peak_gib()
