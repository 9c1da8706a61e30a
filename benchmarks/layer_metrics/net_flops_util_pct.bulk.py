"""clips/s x FLOPs a clip needs / (chips x the chip's bf16 peak): end-to-end
utilisation over the whole window, idle time included. Not a roofline
share."""

NAME = "net_flops_util_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "host_clock"
LAYER = "network"
MOVES = "videos_per_s"


def read(facts):
    return facts.net_flops_util_pct()
