"""Of the (query block, key block) tiles on or under the diagonal of the
packed pool at the tile sizes of the layers with a window, the share the flash
kernel ran in those layers: the tiles in which some query may read some key of
its request inside its window, by the table each dispatch derives from its
segment table and the window (the program's Attention: line, the ``window_``
pair, counted over every such layer of every dispatch). 100 is a kernel that
walks the pool's whole triangle in a layer that needs a band of it."""

NAME = "window_tile_visit_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    causal = getattr(facts.result, "window_tiles_causal", 0)
    if not causal:
        return None
    return 100.0 * facts.result.window_tiles_visited / causal
