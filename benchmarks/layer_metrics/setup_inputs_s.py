"""`setup.entered` to where the program's `setup.run` span opens (`run_benchmark`'s
first line): the request files, the weights' file or recipe, the pipeline's
configuration and the schedule, all the benchmark's own work between the runtime's
start and the program's (`benchmarks/setup_account.py`)."""

NAME = "setup_inputs_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def read(facts):
    from benchmarks import setup_account
    return setup_account.read(facts, NAME)
