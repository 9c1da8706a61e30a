"""The least time the chip could take for the gated delta rule alone in the
traced window over the device time under the scope ``deltanet/rule``
(everything between the normalised ``q``, ``k``, ``v``, the decays and steps,
and ``o``). The operations are the *recurrence's own* (7 x Dk x Dv a token a
value head: the state decayed, read by the key, written, read by the query)
and the bytes its operands read and its result written once, valid tokens
only: less than any blocked form computes, so the share reads the same work
whatever implements the rule and cannot pass 100."""

NAME = "deltarule_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "gated delta rule"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    return subscopes.roofline_pct(facts, "deltarule", path="deltanet/rule")
