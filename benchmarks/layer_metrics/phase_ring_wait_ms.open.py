"""Mean ms a request spent from its batch being on the device to the network's
executor taking it (`drain` + `inter_stage_queue`): the wait in the loader's
ready queue and the ring of `num_shared_tensors` batches. One of six classes
(`benchmarks/hostspans.py`, `PHASE_CLASSES`) that partition finish -
`enqueue_filename`; over the finished requests due in the window. None on a
program that does not stamp the loader's refinement stamps."""

NAME = "phase_ring_wait_ms.open"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "batching"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import hostspans
    return hostspans.phase_ms(facts, "ring_wait")
