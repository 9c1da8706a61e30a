"""What a caller waits: from the instant a request was due to its
last-stage finish, median over the requests due inside the window. Not
among the bounded end-to-end metrics yet: at 0.80 of the knee the chip
is already 97% busy in small dispatches, the wait is queueing, and six
runs of one code spread by 5-6% of the median (PERF.md, PR 23), which a
bound of at most 10% cannot hold with room."""

NAME = "latency_p50_ms.open"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "batching"
MOVES = "videos_per_s"


def read(facts):
    return facts.latency_ms(50.0)
