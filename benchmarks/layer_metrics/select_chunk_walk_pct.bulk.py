"""Of the (query step, key chunk) visits a count of the thresholds would make
walking every step's rows of sort keys from key 0 of the packed pool to the
step's diagonal, the share the walk makes (the program's Sparse: line,
``chunks_walked=`` over ``chunks_to_diagonal=``, counted over every layer with
an indexer of every dispatch, at the program's own queries a step and keys a
chunk). 100 is a dispatch of one long request, or a program that starts at key
0 whatever the pool holds; what is under it is other requests' keys in front of
a step's first request and the steps none of whose queries has ``topk`` keys to
read, which count nothing."""

NAME = "select_chunk_walk_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    whole = getattr(facts.result, "sparse_chunks_to_diagonal", 0)
    if not whole:
        return None
    return 100.0 * facts.result.sparse_chunks_walked / whole
