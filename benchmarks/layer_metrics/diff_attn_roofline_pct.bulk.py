"""The least time the chip could take for the *full differential attention
layer's own* work in the traced window over the device time under the scope
``attn/full/kernel`` (the flash kernel's calls of the one layer whose two
softmaxes a head pair read the whole context). The work is the family file's
``"diff_attn"``: every valid query against the keys of its request at or before
it, 384 operations a pair and query head (a head's scores over 64 columns, its
softmax's product with the pair's 128 value columns), queries, keys, values and
the result once in bfloat16: the larger of operations over the bf16 peak and
bytes over the HBM bandwidth. Less than any tiled form computes (the kernel
pads a head's 64 key columns to the lanes and walks whole tiles), so the share
cannot pass 100."""

NAME = "diff_attn_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    try:
        return subscopes.roofline_pct(facts, "diff_attn",
                                      path="attn/full/kernel")
    except ValueError:
        # a family whose file counts no ``diff_attn``
        return None
