"""The least time the chip could take for Falcon-H1's state-space branches in
the traced window (``in_proj`` and ``out_proj``, the convolution, the blocked
scan, the gate and the gated norm of every block: the larger of operations over
the bf16 peak and bytes over the HBM bandwidth, from the family file's
``mechanism_work(..., "ssm", ...)``, valid tokens only) over the device time
under the scope ``ssd``. None where the family's file counts no ``ssm`` or the
run's program has no such scope."""

NAME = "ssm_branch_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "state-space scan"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    try:
        return subscopes.roofline_pct(facts, "ssm", path="ssd")
    except (ValueError, TypeError):
        # a family whose file counts no ``ssm``, or counts by another
        # signature
        return None
