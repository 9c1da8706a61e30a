"""The largest distance of a row or column sum of any valid token's ``H_res``
from 1 over the run, in units of 1e-9 (the program's Tokens: line,
``res_defect_e9``): how far the Sinkhorn iteration of ``hc_sinkhorn_iters``
steps is from a doubly stochastic matrix at the worst (token, sublayer) of the
(valid token, sublayer) mixings the run made (``mixes``). A program that stops
the iteration early reads orders of magnitude more; the run's check holds each
sample's to ten times the float32 reference's own."""

NAME = "hyper_res_defect_e9.bulk"
UNIT = "1e-9"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "residual stream"
MOVES = "videos_per_s"


def read(facts):
    if not getattr(facts.result, "tokens_mixes", 0):
        return None
    return float(facts.result.tokens_res_defect_e9)
