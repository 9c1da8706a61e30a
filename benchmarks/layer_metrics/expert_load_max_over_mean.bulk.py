"""Assignments served by the busiest held expert of any E block over the
mean of all held experts, over the run (the program's Experts: line): the
imbalance the grouped product absorbs without dropping a token."""

NAME = "expert_load_max_over_mean.bulk"
UNIT = "x"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "sparse experts"
MOVES = "videos_per_s"


def read(facts):
    mean = getattr(facts.result, "experts_mean_per_expert", 0)
    if not mean:
        return None
    return facts.result.experts_max_per_expert / mean
