"""Device milliseconds a dispatch under the scope ``deltanet/gate`` in the
traced window: what makes the vector gate of Kimi Delta Attention outside the
rule's kernel — the low-rank pair (hidden -> 128 -> heads x Dk), the softplus
with its bias a channel and ``-exp(A_log)`` a head, in float32 — without the
projections, the convolution, the rule, the output gate and the norm that
share the scope ``deltanet``. Dispatches are counted as the roofline shares
count them. None where the run's program has no such scope."""

NAME = "kda_gate_ms_per_dispatch.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "gated delta rule"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    under = subscopes.seconds_under(facts, "deltanet/gate")
    dispatches = subscopes.traced_dispatches(facts)
    if under is None or not dispatches:
        return None
    return 1e3 * under / dispatches
