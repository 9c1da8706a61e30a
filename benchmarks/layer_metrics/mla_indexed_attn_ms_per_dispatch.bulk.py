"""Device milliseconds a dispatch of the custom calls named
``latent_indexed_attention`` in the traced window: latent attention under
the indexer's sets, the full layers' kernel alone, from
``ops/mla.queries``' result to the output product's operand. Dispatches
are counted as the roofline shares count them."""

NAME = "mla_indexed_attn_ms_per_dispatch.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"

KERNEL = "latent_indexed_attention"


def read(facts):
    from benchmarks import scopes, subscopes
    spent = scopes.kernel_seconds(facts, KERNEL)
    dispatches = subscopes.traced_dispatches(facts)
    if spent is None or not dispatches:
        return None
    return 1e3 * spent / dispatches
