"""Device milliseconds a dispatch under the scope ``attn`` in the traced
window: the attention branch of every block (its four products, the rotary, the
relayouts and the flash kernel). Beside ``ssd_busy_pct.bulk`` and
``attn_busy_pct.bulk`` it is the balance of the two mixers that share a block
and a normed input. Dispatches are counted as the roofline shares count them.
None where the run's program has no such scope."""

NAME = "attn_branch_ms_per_dispatch.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    under = subscopes.seconds_under(facts, "attn")
    dispatches = subscopes.traced_dispatches(facts)
    if under is None or not dispatches:
        return None
    return 1e3 * under / dispatches
