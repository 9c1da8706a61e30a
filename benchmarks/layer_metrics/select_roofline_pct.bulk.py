"""The least time the chip could take for the *indexer's own* work in the
traced window over the device time under the scope ``attn/select`` (everything
that decides a query's keys: the indexer's products and scores, the thresholds,
the sets as bits). The work is the indexer's three products and 2 x 16 x 64
operations a causal (query, key) pair of a request, its operands read and
written once: the larger of operations over the bf16 peak and bytes over the
HBM bandwidth, from the family file. Choosing the 2,048 adds nothing to the
yardstick, so the share reads the same work whether the scores are written and
sorted, thresholded or fused into the attention kernel, and cannot pass 100."""

NAME = "select_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    return subscopes.roofline_pct(facts, "select", path="attn/select")
