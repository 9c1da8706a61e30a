"""The least time the chip could take for the Mamba-2 recurrence alone in the
traced window over the device time of the scan's kernel's own calls (the custom
calls named ``ssd_scan``, ``rnb_tpu.ops.ssd``). The operations are the
*recurrence's own* (a token's decay of a head's P x N state, its update and its
read-out: 5 P N, and the skip term) and the bytes x, z and y in bfloat16, B, C
and the steps once, valid tokens only: less than any blocked form computes and
less than the kernel reads, so the share reads the same work whatever the
kernel does inside and cannot pass 100. None where the family's file counts no
``scan`` or the run's program has no such kernel."""

NAME = "ssd_kernel_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "state-space scan"
MOVES = "videos_per_s"

KERNEL = "ssd_scan"


def read(facts):
    from benchmarks import subscopes
    try:
        return subscopes.roofline_pct(facts, "scan", kernel=KERNEL)
    except (ValueError, TypeError):
        # a family whose file counts no ``scan``, or counts by another
        # signature
        return None
