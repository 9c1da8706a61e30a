"""Mean ms from a batch's `loader.transfer` span opening to its bytes being on
the device: the `jax.device_put` call (which returns once the copy is handed
over) and the wait of the same thread's next `exec{i}.device_sync`, which is
the loader's executor waiting for that array (`benchmarks/hostspans.py`,
`put_ms`)."""

NAME = "put_ms_per_dispatch.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "staging and transfer"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import hostspans
    spans = hostspans.of(facts)
    return spans.put_ms() if spans is not None else None
