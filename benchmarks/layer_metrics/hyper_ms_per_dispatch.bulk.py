"""Device milliseconds a dispatch under the scope ``hyper`` in the traced
window: a residual stream several wide whole — every sublayer's mappings (the
statistic, the projection, the sigmoids, ``exp`` and the Sinkhorn steps), the
way into the sublayer (``u = h_pre X``) and the way out (``X' = H_res X +
h_post^T y``), and the embedding's repeat into the streams. The memory system's
work: at 4 streams of 3,584 channels a token's stream is 28.7 KB, and 12
sublayers of 8,192 tokens that read it once and write it once with ``u`` and
``y`` move 7.05 GB, 8.6 ms at the v5e's 819 GB/s. Dispatches are counted as the
roofline shares count them."""

NAME = "hyper_ms_per_dispatch.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "residual stream"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    under = subscopes.seconds_under(facts, "hyper")
    dispatches = subscopes.traced_dispatches(facts)
    if under is None or not dispatches:
        return None
    return 1e3 * under / dispatches
