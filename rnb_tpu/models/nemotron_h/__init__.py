"""Nemotron-H: a hybrid of Mamba-2 (``M``), sparse-expert (``E``) and
grouped-query attention (``*``) blocks, served as prefill over packed
token rows (``network``: the forward pass; ``checkpoint``: weights
from a seed, on the device; ``stages``: the pipeline stages; ``flops``:
the operations and bytes each mechanism needs)."""
