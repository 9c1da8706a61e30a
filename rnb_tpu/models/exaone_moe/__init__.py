"""K-EXAONE (``exaone_moe``): grouped-query attention with a window of
128 keys in three layers of four and over the whole request in the
fourth (QK-norm; rotary on the window layers alone), norms behind the
mixer and the feed-forward, a leading dense layer, then sparse experts
(sigmoid router with a correction bias, the largest eight renormalised
and scaled, gated experts, one shared expert), served as prefill over
packed token rows through the token families' shared stages
(``rnb_tpu/models/token_stages.py``). ``network``: the forward pass;
``checkpoint``: the tensors, made from a seed on the device; ``flops``:
the operations each mechanism needs."""
