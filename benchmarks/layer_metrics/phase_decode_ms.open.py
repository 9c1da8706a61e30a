"""Mean ms a request spent from the loader taking the request to its decode
being done (`decode{i}_done`). One of six classes (`benchmarks/hostspans.py`,
`PHASE_CLASSES`) that partition finish - `enqueue_filename`; over the finished
requests due in the window. None on a program that does not stamp the loader's
refinement stamps."""

NAME = "phase_decode_ms.open"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "native decode and host"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import hostspans
    return hostspans.phase_ms(facts, "decode")
