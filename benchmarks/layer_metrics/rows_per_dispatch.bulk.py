"""Rows shipped per emission of the fusing loader (the program's `Padding:`
counters, whole run, pad rows included)."""

NAME = "rows_per_dispatch.bulk"
UNIT = "rows"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "batching"
MOVES = "videos_per_s"


def read(facts):
    return facts.rows_per_dispatch()
