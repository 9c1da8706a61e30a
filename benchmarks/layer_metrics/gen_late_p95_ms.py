"""How late the benchmark's own schedule ran: sent - due, 95th percentile over
requests due inside the window. A starved generator must not read as a fast
server."""

from benchmarks import stamps

NAME = "gen_late_p95_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "client"
MOVES = "videos_per_s"


def read(facts):
    late = facts.gen_late_ms()
    return stamps.percentile(late, 95.0) if len(late) else None
