"""Rows shipped per emission under open-loop arrivals: the hold-and-emit
policy's choice, which sets how long a request waits for its batch."""

NAME = "rows_per_dispatch.open"
UNIT = "rows"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "batching"
MOVES = "videos_per_s"


def read(facts):
    return facts.rows_per_dispatch()
