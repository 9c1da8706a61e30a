"""Input bytes a consuming replica moved device to device from the loader's
chip, over all input bytes put on the device (`Handoff:`)."""

NAME = "rehomed_byte_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "handoff"
MOVES = "videos_per_s"


def read(facts):
    return facts.rehomed_byte_pct()
