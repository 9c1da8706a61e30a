"""dots3-note-prev's language model (``model_type`` ``dots3_note``):
latent attention (MLA) in two geometries in one stack — in the *full*
layers 128 heads of 128 + 64 / 128 from latents of 1,024 and 512 under a
learned indexer (64 heads of 128 on one shared key head, from the query
latent) that chooses each query's 2,048 keys, in the *sliding* layers 64
wider heads of 192 + 64 / 128 from latents of 1,024 and 1,024 under a
window of 513 keys, each type with its own rotary base — the latents
rescaled, a head-wise sigmoid gate on every head's result, a leading
dense layer and then 256 sigmoid-routed gated experts (top-8 of score +
bias, renormalised) with one shared expert, of which a chip holds a
share; served as prefill over packed token rows through the token
families' shared stages (``rnb_tpu/models/token_stages.py``). The vision
and audio towers and the prediction module are not here: prompts are
text. ``network``: the forward pass; ``checkpoint``: the tensors, made
from a seed on the device; ``flops``: the operations each mechanism
needs."""
