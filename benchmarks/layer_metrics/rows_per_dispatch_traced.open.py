"""Rows shipped per dispatch, from `rows` of the `exec{K}.model_call` spans
inside the traced window: `rows_per_dispatch.open` without the ramp and the
drain that the whole-run counter holds."""

NAME = "rows_per_dispatch_traced.open"
UNIT = "rows"
BETTER = "higher"
SOURCE = "program_span"
LAYER = "batching"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import hostspans
    spans = hostspans.of(facts)
    return spans.rows_per_dispatch() if spans is not None else None
