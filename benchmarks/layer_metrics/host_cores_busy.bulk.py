"""Process CPU seconds (every thread: decode pool, loader, runners, client)
over the window, per second of window: how many host cores the serving path
keeps busy."""

NAME = "host_cores_busy.bulk"
UNIT = "cores"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "native decode and host"
MOVES = "videos_per_s"


def read(facts):
    return facts.host_cores_busy()
