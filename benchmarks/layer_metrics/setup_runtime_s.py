"""`T_PROCESS` (the first line of `benchmarks/run.py`) to the program's `setup.entered`
stamp, which `enable_compilation_cache()` takes right behind `jax.devices()`: the
family's build, the imports, JAX and the TPU runtime's start. No PR of this repo
moves it; it is the part that differs between two runs of one program
(`benchmarks/setup_account.py`)."""

NAME = "setup_runtime_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def read(facts):
    from benchmarks import setup_account
    return setup_account.read(facts, NAME)
