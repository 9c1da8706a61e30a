"""The least time the chip could take for the gated MLP of every block in the
traced window (three products of hidden x intermediate a token: the larger of
operations over the bf16 peak and bytes over the HBM bandwidth, from the family
file's ``mechanism_work(..., "mlp", ...)``, valid tokens only) over the device
time under the scope ``mlp`` (which holds the norm in front and the residual
addition too): how near the matrix unit's peak a dense model's largest share
runs. None where the family's file counts no ``mlp`` or the run's program has
no such scope."""

NAME = "mlp_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "network"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    try:
        return subscopes.roofline_pct(facts, "mlp", path="mlp")
    except (ValueError, TypeError):
        # a family whose file counts no ``mlp``, or counts by another
        # signature
        return None
