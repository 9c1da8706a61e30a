"""Mean ms a request spent from its batch closing to the batch being handed to
the device (`transfer{i}_done`): assembly and `device_put`. One of six classes
(`benchmarks/hostspans.py`, `PHASE_CLASSES`) that partition finish -
`enqueue_filename`; over the finished requests due in the window. None on a
program that does not stamp the loader's refinement stamps."""

NAME = "phase_transfer_ms.open"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "staging and transfer"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import hostspans
    return hostspans.phase_ms(facts, "transfer")
