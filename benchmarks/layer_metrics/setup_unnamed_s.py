"""`setup_s` less the six metrics above less the mix's ramp (`window[0]` less the start
barrier's release): the launch, the constructor's own lines, the scope tables, the
wait at the barrier, whatever no span names yet. The seven and the ramp add up to
`notes.setup_s`; the reader returns None for all where they do not, to 1 ms
(`benchmarks/setup_account.py`)."""

NAME = "setup_unnamed_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def read(facts):
    from benchmarks import setup_account
    return setup_account.read(facts, NAME)
