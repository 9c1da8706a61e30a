"""The least time the chip could take for the *selective scan's own* work in
the traced window over the device time of the custom calls named
``selective_scan`` (Mamba-1's recurrence, one kernel a Mamba layer). The work
is the family file's ``"selective_scan"``: a decay, an update and a read-out of
every (channel, state) a valid token and layer and the skip term; x, z and y
once in bfloat16, the steps in float32, B and C once — the same work whatever
the kernel does inside: the larger of operations over the bf16 peak and bytes
over the HBM bandwidth. The recurrence has no matrix product: its operations
are the vector and transcendental units', for which ``benchmarks/peaks.py``
has no rate (ROADMAP D10 (bp)), so against the matrix unit's peak the bytes
bound it and the share reads low; it cannot pass 100."""

NAME = "selective_scan_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "state-space scan"
MOVES = "videos_per_s"

KERNEL = "selective_scan"


def read(facts):
    from benchmarks import subscopes
    try:
        return subscopes.roofline_pct(facts, "selective_scan", kernel=KERNEL)
    except ValueError:
        # a family whose file counts no ``selective_scan``
        return None
