"""Qwen3-Next: Gated DeltaNet (a gated delta rule behind a short
convolution, ``ops/deltanet.py``) in three layers of four and gated
softmax attention (QK-norm, rotary on a quarter of a head's columns, a
sigmoid gate on the result) in the fourth, every layer followed by
sparse experts (softmax router, the largest ten renormalised, gated
experts, one sigmoid-gated shared expert), served as prefill over packed
token rows through the token families' shared stages
(``rnb_tpu/models/token_stages.py``). ``network``: the forward pass;
``checkpoint``: the tensors, made from a seed on the device; ``flops``:
the operations each mechanism needs."""
