"""Self time of `setup.s{step}.weights` on the critical instance (the stage whose
constructor ended last): recipe or checkpoint to parameters handed to the device. Its
compilations are JAX's own spans inside it and count under `setup_lower_s` and
`setup_compile_s`; the draw's device work runs on behind the host and shows in
`setup_first_call_s` (`benchmarks/setup_account.py`)."""

NAME = "setup_weights_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def read(facts):
    from benchmarks import setup_account
    return setup_account.read(facts, NAME)
