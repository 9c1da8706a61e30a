"""The least time the chip could take for the experts' grouped products in
the traced window (both projections of every held (token, expert) pair: the
larger of operations over the bf16 peak and bytes over the HBM bandwidth,
from the family file) over the device time of the Pallas kernel's calls
(``%gmm`` custom calls)."""

NAME = "gmm_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "sparse experts"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import scopes
    return scopes.roofline_pct(facts, "gmm", kernel="gmm")
