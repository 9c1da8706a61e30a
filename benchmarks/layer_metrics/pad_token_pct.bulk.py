"""Share of the tokens shipped to the final stage (rows x tokens a row,
bucket padding included) that were padding: the tails of requests' last
rows and the pad rows of the bucket (the program's Tokens: line)."""

NAME = "pad_token_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "batching"
MOVES = "videos_per_s"


def read(facts):
    shipped = getattr(facts.result, "tokens_shipped", 0)
    if not shipped:
        return None
    return 100.0 * (1.0 - facts.result.tokens_valid / shipped)
