"""The tail a caller waits: from the instant a request was due to its
last-stage finish, 95th percentile over the requests due inside the
window (some 3,100, so over 150 lie beyond it). Kept, like the median,
not among the bounded end-to-end metrics because at 0.80 of the knee
the chip is already 97% busy in small dispatches, the wait is queueing,
and six runs of one code spread by 7-13% of the median (PERF.md, PR 23):
no bound up to the 10% a bound may be would hold."""

from benchmarks import stamps

NAME = "latency_p95_ms.open"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "batching"
MOVES = "videos_per_s"


def read(facts):
    return facts.latency_ms(95.0)
