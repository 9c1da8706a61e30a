"""The least time the chip could take for the residual stream's mappings and
mixings in the traced window over the device time under the scope ``hyper``.
The work is the family file's ``"hyper"``: a token's stream read once and
written once a sublayer with the sublayer's input and output in bfloat16 —
(2n + 2) C 2 bytes a token a sublayer — and ``phi`` once a dispatch; the
projection, the statistic and the two mixings' operations over the bf16 peak
(the bytes bound it: 21 operations a byte). That is the least any implementation
can move, whatever it fuses, so the share cannot pass 100."""

NAME = "hyper_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "residual stream"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    try:
        return subscopes.roofline_pct(facts, "hyper", path="hyper")
    except (ValueError, TypeError):
        # a family whose file counts no ``hyper``, or by another signature
        return None
