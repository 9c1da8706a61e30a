"""The least time the chip could take for the sparse layers' scores and values
in the traced window — every valid query against the keys of the blocks it
*chose* only (all causal keys of a request under ``dense_len``), at the mix's
mean: the larger of operations over the bf16 peak and bytes over the HBM
bandwidth, from the family file — over the device time of the Pallas kernel's
calls (``%block_sparse_attention`` custom calls). The kernel visits every key
tile in which any query of a 128-query tile chose a block, which under seeded
random weights is nearly the whole causal triangle of each request: the share
reads low for as long as the kernel visits more than was chosen."""

NAME = "sparse_attn_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    return subscopes.roofline_pct(facts, "sparse_attn",
                                  kernel="block_sparse_attention")
