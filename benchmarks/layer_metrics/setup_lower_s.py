"""Sum of JAX's `setup.jax.trace` and `setup.jax.lower` spans (self times) on the
critical instance's thread: Python tracing and the lowering to StableHLO, paid whether
the compile cache is warm or cold, growing with the kernel bodies a program holds
(`benchmarks/setup_account.py`)."""

NAME = "setup_lower_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def read(facts):
    from benchmarks import setup_account
    return setup_account.read(facts, NAME)
