"""Pad rows as a share of rows shipped, from `rows` and `rows_valid` of the
`exec{K}.model_call` spans inside the traced window: `pad_row_pct.bulk` without
the ramp and the drain that the whole-run counter holds."""

NAME = "pad_row_traced_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "batching"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import hostspans
    spans = hostspans.of(facts)
    return spans.pad_row_pct() if spans is not None else None
