"""The least time the chip could take for the attention branches' scores and
values in the traced window of a family without experts (every valid query
against the keys of its own request at or before it, at the mix's mean context:
the larger of operations over the bf16 peak and bytes over the HBM bandwidth,
from the family file's ``mechanism_work(..., "flash", ...)``) over the device
time of the Pallas flash kernel's calls (``%splash_mqa_fwd...`` custom calls).
What ``flash_roofline_pct.bulk`` reads for the expert families, whose reader
gives a run without expert assignments nothing. The kernel covers the causal
triangle of the tiles its block table lets run, other requests' keys and
padding included, so the share understates its speed. None where the family's
file counts no ``flash`` by this signature or the run has no such kernel."""

NAME = "hybrid_flash_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"

KERNEL = "splash_mqa_fwd_segmented_no_residuals"


def read(facts):
    from benchmarks import subscopes
    try:
        return subscopes.roofline_pct(facts, "flash", kernel=KERNEL)
    except (ValueError, TypeError):
        # a family whose file counts no ``flash``, or counts it with the
        # held assignments (the expert families)
        return None
