"""Custom TPU ops (Pallas kernels) with portable jnp fallbacks.

The compute path of this framework is almost entirely XLA-compiled
Flax/jnp code — XLA already fuses elementwise work into the conv/matmul
HLOs that dominate R(2+1)D, the ingest normalization among them
(``preprocess.normalize_u8``, plain jnp: uint8 decode output ->
normalized bfloat16 activations; reference analog: the uint8->float
cast + permute after NVVL decode, reference
models/r2p1d/model.py:149-151). The ops package holds the hand-written
Pallas kernels for what XLA cannot do as well alone.

An op with a kernel exposes one public entry point that dispatches to
the Pallas kernel on TPU backends and to an identical jnp formulation
elsewhere (CPU tests, interpret mode), so numerics are defined once.

The token-sequence families (rnb_tpu.models.nemotron_h,
rnb_tpu.models.deepseek_v2, rnb_tpu.models.minicpm_sala,
rnb_tpu.models.qwen3_next, rnb_tpu.models.exaone_moe,
rnb_tpu.models.keye_vl2, rnb_tpu.models.kimi_linear,
rnb_tpu.models.falcon_h1: eight) add eight
mechanisms, each over a packed pool
of rows with state confined to requests: ``ssd`` (the blocked Mamba-2
scan — one Pallas kernel that walks the rows with a step's states in
VMEM; lightning linear attention is its case of unit steps — and the
causal convolution in front of it, a second Pallas kernel: the taps, the
bias and the SiLU in one pass over the activations), ``deltanet`` (the gated delta rule, whose
transition is a matrix: one Pallas kernel that walks the rows with a
head group's states in VMEM, a triangular solve inside each row; and
its form under a *vector* gate, one decay a key channel — Kimi Delta
Attention — a second kernel of the module, in which the decay stands
inside the two score products and every exponent is kept at or under
zero),
``blocksparse`` (every query's own top-k blocks of keys from
mean-compressed keys, and a Pallas flash kernel under that block
mask), ``indexed`` (every query's own top-k *keys* by a learned
indexer's scores: the scores as sort keys, a threshold a query found bit
by bit, and a Pallas flash kernel under the sets), ``segattn``
(causal attention inside requests: JAX's Pallas splash kernel over the
pool; values may be narrower than keys, latent attention's expanded
form, with rotary keys (DeepSeek-V2) or with no positions at all
(Kimi-Linear)), ``banded`` (attention under a window, K-EXAONE's
sliding layers: a query reads the last so many keys of its request; one
Pallas kernel with no table, a key-value head's query heads against the
two key blocks of a band in one step, the head norms and the rotary its
first lines, operands read as the products wrote them), ``rope``
(rotary positions that restart at each request, YaRN's frequencies) and ``moe`` (routing over all experts by the family's rule
and the held experts' part, plain or gated, whose grouped product is
JAX's Pallas megablox kernel).
"""

from rnb_tpu.ops.preprocess import normalize_u8  # noqa: F401
