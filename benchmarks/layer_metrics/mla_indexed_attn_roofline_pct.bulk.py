"""The least time the chip could take for latent attention over the *causal*
pairs in the traced window, over the device time of the custom calls named
``latent_indexed_attention``: every head's two products over every key at
or before the query (the family file's ``"full_attn"``: what a kernel that
walks every causal tile of a request visits, whatever its tiles, without
the pairs over the diagonal inside them; queries, keys, values and result
once in bfloat16), the larger of operations over the bf16 peak and bytes
over the HBM bandwidth. Tiles a packed pool adds and the pairs over the
diagonal can only lower the share; it cannot pass 100."""

NAME = "mla_indexed_attn_roofline_pct.bulk"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"

KERNEL = "latent_indexed_attention"


def read(facts):
    from benchmarks import subscopes
    try:
        return subscopes.roofline_pct(facts, "full_attn", kernel=KERNEL)
    except ValueError:
        # a family whose file counts no ``full_attn``
        return None
