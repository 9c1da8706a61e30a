"""Sum of the self times of `setup.s{step}.first_call` on the critical instance's thread:
each row bucket's warm-up calls to `block_until_ready`, and with the first of them
whatever device work set-up had left behind the host (`benchmarks/setup_account.py`)."""

NAME = "setup_first_call_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def read(facts):
    from benchmarks import setup_account
    return setup_account.read(facts, NAME)
