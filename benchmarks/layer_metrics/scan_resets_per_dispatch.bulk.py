"""Rows a dispatch that open a request (the program's Tokens: line,
``scan_resets=`` over the dispatches the batcher emitted): where the scan's
kernel zeroes its carried state and the convolution its history
(``rnb_tpu.ops.ssd``'s ``row_first``), pad rows not counted — the requests a
dispatch packs. None where the run's program counts no such rows."""

NAME = "scan_resets_per_dispatch.bulk"
UNIT = "rows"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "state-space scan"
MOVES = "videos_per_s"


def read(facts):
    resets = getattr(facts.result, "tokens_scan_resets", 0)
    emissions = getattr(facts.result, "pad_emissions", 0)
    if not resets or not emissions:
        return None
    return resets / emissions
