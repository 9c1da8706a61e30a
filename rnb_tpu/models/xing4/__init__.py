"""Xing4.0-29B-A4B: DeepSeek-V3's layer (latent attention under YaRN,
sigmoid-routed experts chosen with a correction bias beside a shared
one) on a residual stream four wide, mixed into and out of every
sublayer by per-token mappings (manifold-constrained hyper-connections,
``rnb_tpu/ops/hyper.py``), served as prefill over packed token rows
through the token families' shared stages
(``rnb_tpu/models/token_stages.py``). ``network``: the forward pass;
``checkpoint``: the tensors, made from a seed on the device; ``flops``:
the operations each mechanism needs."""
