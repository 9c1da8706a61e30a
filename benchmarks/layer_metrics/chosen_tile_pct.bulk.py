"""Of the attention kernel's (query tile, key tile) pairs on or under the
diagonal of the packed pool, at its own tile sizes, the share in which any
query chose any key (the program's Sparse: line, ``tiles_chosen=`` over
``tiles_causal=``, counted over every layer of every dispatch). What is under
100 is other requests' tiles and whatever a token-level choice frees: a kernel
that skips tiles can win no more than this leaves."""

NAME = "chosen_tile_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    causal = getattr(facts.result, "sparse_tiles_causal", 0)
    if not causal:
        return None
    return 100.0 * facts.result.sparse_tiles_chosen / causal
