"""1 - union of operation intervals / traced window under open-loop
arrivals: the headroom the offered rate leaves."""

NAME = "device_idle_pct.open"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "videos_per_s"


def read(facts):
    return facts.device_idle_pct()
