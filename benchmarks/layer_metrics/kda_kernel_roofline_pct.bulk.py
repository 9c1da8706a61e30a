"""The least time the chip could take for the delta rule alone in the traced
window over the device time of the vector gate's kernel's own calls (the
custom calls named ``channel_gated_delta_rule``: Kimi Delta Attention's rule,
``rnb_tpu.ops.deltanet``). The operations are the *recurrence's own* (7 x Dk x
Dv a token a head) and the bytes its operands read and its result written once
(``q``, ``k``, ``v`` in bfloat16, ``log alpha`` a channel and ``beta`` a head
in float32, the result in float32), valid tokens only: less than any blocked
form computes, so the share reads the same work whatever the kernel does
inside and cannot pass 100; it reads at or above ``deltarule_roofline_pct``,
whose time is the whole scope ``deltanet/rule``. None where the run's program
has no such kernel."""

NAME = "kda_kernel_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "gated delta rule"
MOVES = "videos_per_s"

KERNEL = "channel_gated_delta_rule"


def read(facts):
    from benchmarks import subscopes
    try:
        return subscopes.roofline_pct(facts, "deltarule", kernel=KERNEL)
    except ValueError:
        # a family whose file counts no ``deltarule``
        return None
