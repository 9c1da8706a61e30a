"""The least time the chip could take for *the kernel's own* share of the
residual stream's work in the traced window over the device time of the custom
calls named ``hyper_mix`` (``ops/hyper.py``: one call a sublayer — the first
sublayer's way in, then every way out with the next sublayer's way in). The work
is the family file's ``"hyper_mix"``: the stream read once and written once a
call with the sublayer's input and output in bfloat16 ((n + 1) C 2 bytes a token
for the first call, which writes no stream), ``phi`` once a call; the
projection, the statistic and the mixings' operations over the bf16 peak (the
bytes bound it). What the kernel moves beyond that — the sublayer's output in
float32 as its product wrote it, the logits and the coefficients — is its own
cost, so the share cannot pass 100. ``hyper_roofline_pct.bulk`` is the scope's,
with ``hyper/maps`` and the first sublayer's ``tile`` in its time."""

NAME = "hyper_mix_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "residual stream"
MOVES = "videos_per_s"

KERNEL = "hyper_mix"


def read(facts):
    from benchmarks import subscopes
    try:
        return subscopes.roofline_pct(facts, "hyper_mix", kernel=KERNEL)
    except (ValueError, TypeError):
        # a family whose file counts no ``hyper_mix``, or by another
        # signature
        return None
