"""Of the rows the experts' first grouped product multiplied, those it kept:
the (token, expert) pairs the held experts served over the rows of the
kernel's grid steps, one step a (row tile, group) pair that holds rows, each
multiplying the whole tile and storing the group's rows alone (the program's
Experts: line, ``held=`` over ``gmm_rows=``, counted over every expert layer
of every dispatch at the row tile in use). 100 is groups that start and end
on tile edges; a 512-row tile over groups of 320 rows reads 38."""

NAME = "gmm_row_fill_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "sparse experts"
MOVES = "videos_per_s"


def read(facts):
    multiplied = getattr(facts.result, "experts_gmm_rows", 0)
    if not multiplied:
        return None
    return 100.0 * facts.result.experts_held / multiplied
