"""Share of the (valid token, chosen expert) pairs whose expert this chip
holds (the program's Experts: line): near the share of experts held, 50%
of 128 here; the rest is the absent chip's and is left out."""

NAME = "held_assignment_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "sparse experts"
MOVES = "videos_per_s"


def read(facts):
    routed = getattr(facts.result, "experts_assignments", 0)
    if not routed:
        return None
    return 100.0 * facts.result.experts_held / routed
