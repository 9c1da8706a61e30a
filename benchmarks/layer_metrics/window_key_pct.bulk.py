"""Of the causal (valid query, key) pairs of the layers with a window, the
share the window keeps (the program's Attention: line, ``window_keys_kept``
of ``window_keys_causal``, summed over the sliding layers): what the window
leaves of causal attention's work."""

NAME = "window_key_pct.bulk"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    causal = getattr(facts.result, "window_keys_causal", 0)
    if not causal:
        return None
    return 100.0 * facts.result.window_keys_kept / causal
