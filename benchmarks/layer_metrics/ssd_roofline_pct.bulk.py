"""The least time the chip could take for the ``ssd`` blocks' work in the
traced window (the larger of operations over the bf16 peak and bytes over
the HBM bandwidth; both from the family file, valid tokens and held
assignments only) over the device time under the scope."""

NAME = "ssd_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "state-space scan"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import scopes
    return scopes.roofline_pct(facts, "ssd")
