"""The least time the chip could take for latent attention over the pairs the
window *keeps* in the traced window, over the device time of the custom
calls named ``latent_banded_attention``: every head's two products over
``min(t + 1, sliding_window_size)`` keys a query (the family file's
``"window_attn"``; queries, keys, values and result once in bfloat16), the
larger of operations over the bf16 peak and bytes over the HBM bandwidth.
The kernel computes two key blocks a step where the window keeps one and a
key, so the share stands under a half and cannot pass 100."""

NAME = "mla_window_attn_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"

KERNEL = "latent_banded_attention"


def read(facts):
    from benchmarks import subscopes
    try:
        return subscopes.roofline_pct(facts, "window_attn", kernel=KERNEL)
    except ValueError:
        # a family whose file counts no ``window_attn``
        return None
