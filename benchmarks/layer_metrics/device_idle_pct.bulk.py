"""1 - union of operation intervals / traced window, on the idlest chip."""

NAME = "device_idle_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "videos_per_s"


def read(facts):
    return facts.device_idle_pct()
