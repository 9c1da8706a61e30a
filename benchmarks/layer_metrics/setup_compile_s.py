"""Sum of JAX's `setup.jax.compile` spans (self times) on the critical instance's
thread: the cache key's hash and the cache's read with the executable's load where it
hit (`cache_hit` 1, `retrieval_s`), the compiler where it did not
(`benchmarks/setup_account.py`)."""

NAME = "setup_compile_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def read(facts):
    from benchmarks import setup_account
    return setup_account.read(facts, NAME)
