"""`setup.jax.compile` spans of the whole process with `cache_hit` 0, from
`run_benchmark`'s first line to the start barrier: what a run that is called warm
still compiled (programs under the cache's one-second floor, or a key that moved)
(`benchmarks/setup_account.py`)."""

NAME = "setup_compiled_programs"
UNIT = "programs"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "set-up"
MOVES = "setup_s"


def read(facts):
    from benchmarks import setup_account
    return setup_account.read(facts, NAME)
