"""MiniCPM-SALA: a dense stack that alternates block-selected sparse
attention (``minicpm4`` layers: InfLLM-V2, grouped-query heads, QK-norm,
an output gate, no rotary) with lightning linear attention
(``lightning-attn`` layers: a constant decay a head, rotary, QK-norm, an
output norm and gate), each followed by a gated MLP, under MiniCPM's
scalings of the embedding, the residual additions and the logits. Served
as prefill over packed token rows through the token families' shared
stages (``rnb_tpu/models/token_stages.py``); it has no experts.
``network``: the forward pass; ``checkpoint``: the tensors, made from a
seed on the device; ``flops``: the operations each mechanism needs."""
