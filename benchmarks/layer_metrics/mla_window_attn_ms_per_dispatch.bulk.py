"""Device milliseconds a dispatch of the custom calls named
``latent_banded_attention`` in the traced window: latent attention under a
window, the sliding layers' kernel alone, from ``ops/mla.queries``' result
to the output product's operand. Dispatches are counted as the roofline
shares count them."""

NAME = "mla_window_attn_ms_per_dispatch.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"

KERNEL = "latent_banded_attention"


def read(facts):
    from benchmarks import scopes, subscopes
    spent = scopes.kernel_seconds(facts, KERNEL)
    dispatches = subscopes.traced_dispatches(facts)
    if spent is None or not dispatches:
        return None
    return 1e3 * spent / dispatches
