"""Kimi-Linear: Kimi Delta Attention (a delta rule whose gate is a
vector, one decay a key channel, behind three short convolutions:
``ops/deltanet.channel_gated_delta_rule``) in three layers of four and
latent attention (MLA) with no positions in the fourth, a dense gated
MLP in the first layer and sparse experts behind it (sigmoid router
with a correction bias, the largest eight renormalised and scaled,
gated experts, one shared expert), served as prefill over packed token
rows through the token families' shared stages
(``rnb_tpu/models/token_stages.py``). ``network``: the forward pass;
``checkpoint``: the tensors, made from a seed on the device; ``flops``:
the operations each mechanism needs."""
