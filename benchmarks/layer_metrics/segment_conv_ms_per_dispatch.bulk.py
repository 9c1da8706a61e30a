"""Device milliseconds a dispatch under the scope ``ssd/conv`` (Nemotron-H's M
blocks) or ``deltanet/conv`` (Qwen3-Next's DeltaNet layers), whichever the
run's family has, in the traced window: the causal depthwise convolution in
front of the scan or of the delta rule, with its bias and its SiLU
(``rnb_tpu.ops.ssd.segment_conv1d``) — without the projections, the scan or
the rule, the gate and the norms that share the scope. Dispatches are counted
as the roofline shares count them."""

NAME = "segment_conv_ms_per_dispatch.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "state-space scan"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    dispatches = subscopes.traced_dispatches(facts)
    for path in ("ssd/conv", "deltanet/conv"):
        under = subscopes.seconds_under(facts, path)
        if under is not None and dispatches:
            return 1e3 * under / dispatches
    return None
