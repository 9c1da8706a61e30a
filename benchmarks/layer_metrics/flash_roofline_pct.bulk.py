"""The least time the chip could take for the attention blocks' scores and
values in the traced window (every valid query against the keys of its own
request at or before it, at the mix's mean context: the larger of operations
over the bf16 peak and bytes over the HBM bandwidth, from the family file) over
the device time of the Pallas flash kernel's calls (``%splash_mqa_fwd...``
custom calls). The kernel covers the causal triangle of the whole pool, other
requests' keys and padding included, so the share understates its speed."""

NAME = "flash_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import scopes
    return scopes.roofline_pct(
        facts, "flash", kernel="splash_mqa_fwd_segmented_no_residuals")
