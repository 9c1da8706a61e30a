"""Of the (query block, key block) tiles on or under the diagonal of the
packed pool, the share the flash kernel ran: those in which some query and
some key may belong to one request, by the block table each dispatch derives
from its segment table (the program's Attention: line, counted over every
attention layer of every dispatch). 100 is a kernel that visits the pool's
whole triangle; what the mix needs token by token lies lower still."""

NAME = "flash_tile_visit_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    causal = getattr(facts.result, "attention_tiles_causal", 0)
    if not causal:
        return None
    return 100.0 * facts.result.attention_tiles_visited / causal
