"""Clips of the videos that finished inside the window, per second: the
network's unit of work, independent of the mix of long and short videos."""

NAME = "clips_per_s.bulk"
UNIT = "clips/s"
BETTER = "higher"
SOURCE = "host_clock"
LAYER = "network"
MOVES = "videos_per_s"


def read(facts):
    return facts.clips_per_s()
