"""Falcon-H1: a Mamba-2 scan (``ops/ssd.py``) and grouped-query
attention with rotary positions (``ops/segattn.py``, ``ops/rope.py``)
*side by side in every block*, on one normed input, both added to the
stream; a dense gated MLP behind them; muP multipliers, scalars of the
configuration, on the embedding, on both mixers' inputs and outputs, on
the columns of the scan's input projection, on the keys, inside the MLP
and on the logits. Served as prefill over packed token rows through the
token families' shared stages (``rnb_tpu/models/token_stages.py``).
``network``: the forward pass; ``checkpoint``: the tensors, made from a
seed on the device; ``flops``: the operations each mechanism needs."""
