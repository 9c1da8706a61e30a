"""Phi-4-mini-flash-reasoning ("SambaY", arXiv:2507.06607): a
self-decoder of Mamba-1 layers (``ops/selective_scan.py``) alternating
with *differential* attention (two softmaxes a head pair over one value,
arXiv:2410.05258) under a window of 512 (``ops/banded.py``) and, in its
last layer, over the whole context (``ops/segattn.py``); then a
cross-decoder that holds no token-mixing state of its own: its
attention layers read the self-decoder's last layer's keys and values,
its Gated Memory Units the last Mamba layer's scan output. So the last
position's logits need the cross-decoder at the last position alone
(YOCO's prefill exit, arXiv:2405.05254): ``network.forward`` runs the
first half and two layers over every token and the rest over one line a
request. LayerNorm with bias, a dense gated MLP in every layer, tied
embeddings, no positions anywhere. Served as prefill over packed token
rows through the token families' shared stages
(``rnb_tpu/models/token_stages.py``).
``network``: the forward pass; ``checkpoint``: the tensors, made from a
seed on the device; ``flops``: the operations each mechanism needs."""
