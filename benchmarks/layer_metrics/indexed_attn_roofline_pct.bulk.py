"""The least time the chip could take for attention over the *chosen* keys in
the traced window over the device time under the scope ``attn/kernel``. The
work is every valid query against the ``min(t + 1, topk)`` keys of its set
(4 x heads x head size operations a pair) and queries, keys and values read and
the result written once in bfloat16: the larger of operations over the bf16
peak and bytes over the HBM bandwidth, from the family file. Less than any
kernel that walks whole causal tiles computes, so the share reads the same work
whatever implements the sets and cannot pass 100."""

NAME = "indexed_attn_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    return subscopes.roofline_pct(facts, "indexed_attn", path="attn/kernel")
