"""Of the (token, chosen expert) pairs of the expert layers, tokens x k a
layer, the pair rows the held experts' buffers held: the rows gathered into
expert order, passed through the grouped products and read back (the
program's Experts: line, the ``pair_rows_`` pair, counted over every expert
layer of every dispatch of a stack that sizes those buffers by the share of
experts it holds). 100 is a program that moves every pair, held or not; the
share the size gives (25 at an eighth of the experts held) says every layer
of every dispatch took one pass, and anything between how often the held
pairs overflowed the size and took further passes."""

NAME = "pair_rows_moved_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "sparse experts"
MOVES = "videos_per_s"


def read(facts):
    pairs = getattr(facts.result, "experts_pair_rows_all", 0)
    if not pairs:
        return None
    return 100.0 * facts.result.experts_pair_rows_moved / pairs
