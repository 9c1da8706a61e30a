"""Share of the (valid token, expert layer) pairs in which the token sent the
held routing group anything: at least one of its chosen experts is held here
(the program's Experts: line, ``group_tokens``). What the expert-parallel
exchange would bring this chip. Group-limited routing lets a token choose
from 3 of 8 groups, so 37.5 is the ceiling under even routing; a token whose
third group's best expert misses its top 6 sends that group nothing."""

NAME = "group_token_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "sparse experts"
MOVES = "videos_per_s"


def read(facts):
    sent = getattr(facts.result, "experts_group_tokens", 0)
    routed = getattr(facts.result, "experts_assignments", 0)
    if not sent or not routed:
        return None
    # assignments = valid tokens x experts a token x expert layers
    return 100.0 * sent * facts.config["num_experts_per_tok"] / routed
