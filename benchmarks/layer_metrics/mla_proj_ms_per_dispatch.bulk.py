"""Device milliseconds a dispatch under the named scope ``attn`` outside the
flash kernel's calls, in the traced window: latent attention's five
projections (queries and keys-values down to their latents and up again, the
output), the latents' norms, the rotary embedding and the copies that lay
queries, keys and values out for the kernel. Dispatches are counted as the
roofline shares count them: the valid tokens of the requests that finished
inside the traced window over the run's mean valid tokens a dispatch."""

NAME = "mla_proj_ms_per_dispatch.bulk"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "packed attention"
MOVES = "videos_per_s"

KERNEL = "splash_mqa_fwd_segmented_no_residuals"


def read(facts):
    from benchmarks import scopes
    tokens = scopes.traced_tokens(facts)
    under = (scopes.scope_seconds(facts) or {}).get("attn")
    flash = scopes.kernel_seconds(facts, KERNEL)
    result = facts.result
    if not tokens or under is None or flash is None \
            or not result.tokens_valid or not result.pad_emissions:
        return None
    dispatches = tokens * result.pad_emissions / result.tokens_valid
    return 1e3 * (under - flash) / dispatches
