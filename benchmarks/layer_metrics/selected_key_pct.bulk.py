"""Of the causal keys the queries of selecting requests (``dense_len`` tokens
or more) could read, the share that lies in the blocks they chose (the
program's Sparse: line, counted over (valid query, key-value head) pairs of
every sparse layer): what block selection leaves of dense attention's work."""

NAME = "selected_key_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    causal = getattr(facts.result, "sparse_causal_keys", 0)
    if not causal:
        return None
    return 100.0 * facts.result.sparse_chosen_keys / causal
