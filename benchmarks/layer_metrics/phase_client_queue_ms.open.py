"""Mean ms a request spent from `enqueue_filename` (the client's first stamp,
microseconds after the schedule's `sent`) to the loader's executor taking the
request off the filename queue. One of six classes (`benchmarks/hostspans.py`,
`PHASE_CLASSES`) that partition finish - `enqueue_filename`; over the finished
requests due in the window. None on a program that does not stamp the loader's
refinement stamps."""

NAME = "phase_client_queue_ms.open"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "client"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import hostspans
    return hostspans.phase_ms(facts, "client_queue")
