"""DeepSeek-V2: latent attention (MLA) with decoupled YaRN rotary keys
and, after a leading dense layer, sparse-expert layers (softmax router,
group-limited greedy choice, gated experts, shared experts), served as
prefill over packed token rows through the token families' shared
stages (``rnb_tpu/models/token_stages.py``). ``network``: the forward
pass; ``checkpoint``: the tensors, made from a seed on the device;
``flops``: the operations each mechanism needs."""
