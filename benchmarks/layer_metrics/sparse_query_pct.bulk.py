"""Share of the valid queries that belong to requests of ``dense_len`` tokens
or more, and so choose their key blocks (the program's Sparse: line); the rest
attend densely in the same dispatches."""

NAME = "sparse_query_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    queries = getattr(facts.result, "sparse_queries", 0)
    if not queries:
        return None
    return 100.0 * facts.result.sparse_selecting / queries
