"""Videos whose last stage finished inside the window, per second, in an
open-loop cell: at a sustainable rate it equals the offered rate, and a
shortfall says the latency was read while a queue grew."""

NAME = "completed_per_s.open"
UNIT = "videos/s"
BETTER = "higher"
SOURCE = "host_clock"
LAYER = "client"
MOVES = "videos_per_s"


def read(facts):
    return facts.videos_per_s()
