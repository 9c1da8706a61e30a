"""Keye-VL-2.0's language model: grouped-query attention (32 / 4 heads of
128, QK-norm, rotary over the whole head) under a *learned* choice of
keys — DeepSeek-Sparse-Attention's lightning indexer, a scoring network
of 16 heads of 64 on one shared key head beside the attention, whose
2,048 best keys of a query every head reads (``ops/indexed.py``) — and,
in every layer, 128 softmax-routed gated experts of width 768, the
largest eight renormalised, all held on the chip; served as prefill over
packed token rows through the token families' shared stages
(``rnb_tpu/models/token_stages.py``). The vision tower is not here:
prompts are text. ``network``: the forward pass; ``checkpoint``: the
tensors, made from a seed on the device; ``flops``: the operations each
mechanism needs."""
