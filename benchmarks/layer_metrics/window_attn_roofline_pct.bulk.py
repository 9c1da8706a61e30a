"""The least time the chip could take for the *window's own* work in the
traced window over the device time under the scope ``attn/window/kernel`` (the
attention kernel's calls of the layers with a window, with the table each
dispatch builds for them). The work is every valid query against the at most
``sliding_window`` keys of its request it may read (4 x heads x head size x
keys operations) and queries, keys and values read and the result written once
in bfloat16: the larger of operations over the bf16 peak and bytes over the HBM
bandwidth, from the family file. Less than any tiled form computes, so the
share reads the same work whatever implements the window and cannot pass
100."""

NAME = "window_attn_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    return subscopes.roofline_pct(facts, "window_attn",
                                  path="attn/window/kernel")
