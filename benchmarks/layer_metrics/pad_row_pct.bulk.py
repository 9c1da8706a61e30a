"""Pad rows as a share of rows shipped (`Padding:`): work the chip does for
nobody."""

NAME = "pad_row_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "batching"
MOVES = "videos_per_s"


def read(facts):
    return facts.pad_row_pct()
