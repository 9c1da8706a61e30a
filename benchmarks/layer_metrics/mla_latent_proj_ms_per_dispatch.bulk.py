"""Device milliseconds a dispatch under the scope ``attn/mla_proj`` in the
traced window: latent attention's plain products by themselves — the queries
and the keys-values down to their latents and up again at the layer type's
sizes, the latents' norms with their rescale, the shared key's rotary, the
output product — in every layer, full or sliding; no attention kernel, no
indexer, no gate. (``mla_proj_ms_per_dispatch.bulk`` is the scope ``attn`` less
a splash kernel's calls by name, for the families whose attention is that
kernel; this one reads the scope a family names for the products.) Dispatches
are counted as the roofline shares count them."""

NAME = "mla_latent_proj_ms_per_dispatch.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"


def read(facts):
    from benchmarks import subscopes
    under = subscopes.seconds_under(facts, "attn/mla_proj")
    dispatches = subscopes.traced_dispatches(facts)
    if under is None or not dispatches:
        return None
    return 1e3 * under / dispatches
