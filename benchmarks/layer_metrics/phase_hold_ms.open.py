"""Mean ms a request spent from its decode being done to its batch closing
(`transfer{i}_start`): waiting for batchmates, at most `max_hold_ms` while the
loader is not busy. One of six classes (`benchmarks/hostspans.py`,
`PHASE_CLASSES`) that partition finish - `enqueue_filename`; over the finished
requests due in the window. None on a program that does not stamp the loader's
refinement stamps."""

NAME = "phase_hold_ms.open"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "batching"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import hostspans
    return hostspans.phase_ms(facts, "hold")
