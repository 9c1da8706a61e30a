"""Of the valid tokens served, the lines sent through the cross-decoder (the
program's Tokens: line, ``cross_lines`` of ``valid``): one a request with the
prefill exit — about 0.01% at prompts of 10k tokens — and 100% where every
position runs every layer."""

NAME = "cross_line_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "network"
MOVES = "videos_per_s"


def read(facts):
    lines = getattr(facts.result, "tokens_cross_lines", 0)
    valid = getattr(facts.result, "tokens_valid", 0)
    if not lines or not valid:
        return None
    return 100.0 * lines / valid
