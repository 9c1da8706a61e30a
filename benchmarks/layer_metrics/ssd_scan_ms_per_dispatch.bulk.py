"""Device milliseconds a dispatch under the scope ``ssd/scan`` in the traced
window: the state-space scan of every M block (Nemotron-H) or lightning layer
(MiniCPM-SALA) — the running sums of the log decays, the scores inside a row,
the carried states, the read-out — without the projections, the convolution,
the gate and the norm that share the scope ``ssd``. Dispatches are counted as
the roofline shares count them."""

NAME = "ssd_scan_ms_per_dispatch.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "state-space scan"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    under = subscopes.seconds_under(facts, "ssd/scan")
    dispatches = subscopes.traced_dispatches(facts)
    if under is None or not dispatches:
        return None
    return 1e3 * under / dispatches
