"""Mean ms a request spent from the network's executor taking its batch to the
logits being ready (`inference{K}`): dispatch, the program on the chip, the
wake-up. One of six classes (`benchmarks/hostspans.py`, `PHASE_CLASSES`) that
partition finish - `enqueue_filename`; over the finished requests due in the
window. None on a program that does not stamp the loader's refinement stamps."""

NAME = "phase_device_ms.open"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "network"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import hostspans
    return hostspans.phase_ms(facts, "device")
