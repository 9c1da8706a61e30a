"""The toy family's program: a loader whose request is a token file and
a runner that turns tokens into logits, built on ``StageModel`` as
``tests/pipeline_helpers.py`` builds its stages. They stand where a
real family's package under ``rnb_tpu/models/`` would stand; the
configuration names them by class path, and the family file puts this
directory on ``sys.path``."""

import numpy as np

from rnb_tpu.stage import PaddedBatch, StageModel


class TokenLoader(StageModel):
    """First stage: reads a request's token file (``rows x length``
    int32, ``.npy``) instead of decoding frames."""

    def __init__(self, device, max_rows=4, length=8, **kwargs):
        super().__init__(device)
        self.max_rows = int(max_rows)

    @classmethod
    def output_shape_for(cls, max_rows=4, length=8, **kwargs):
        return ((int(max_rows), int(length)),)

    @classmethod
    def output_dtype_for(cls, **kwargs):
        return "int32"

    def __call__(self, tensors, non_tensors, time_card):
        tokens = np.load(str(non_tensors))
        time_card.num_clips = int(tokens.shape[0])
        return (PaddedBatch.from_rows(tokens, self.max_rows),), \
            non_tensors, time_card


def apply(params, tokens):
    """bf16 embedding rows, mean over the sequence, a bf16 head with
    float32 accumulation: logits (rows, classes) in float32."""
    import jax.numpy as jnp
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    x = jnp.mean(x, axis=1).astype(jnp.bfloat16)
    return jnp.dot(x, params["head"], preferred_element_type=jnp.float32)


def load_params(ckpt_path, device):
    import jax
    import jax.numpy as jnp
    with np.load(ckpt_path) as f:
        return {k: jax.device_put(jnp.asarray(f[k], jnp.bfloat16), device)
                for k in ("embed", "head")}


class TokenRunner(StageModel):
    """Final stage: the jitted ``apply`` over a padded batch of token
    rows, warmed at its one shape; like the video family's final stage
    it discards the logits."""

    def __init__(self, device, ckpt_path=None, max_rows=4, length=8,
                 **kwargs):
        super().__init__(device)
        import jax
        self._shape = (int(max_rows), int(length))
        self._params = load_params(ckpt_path, device.resolve())
        self._apply = jax.jit(apply)
        jax.block_until_ready(self._apply(
            self._params, np.zeros(self._shape, np.int32)))

    def input_shape(self):
        return (self._shape,)

    @classmethod
    def input_shape_for(cls, max_rows=4, length=8, **kwargs):
        return ((int(max_rows), int(length)),)

    @classmethod
    def input_dtype_for(cls, **kwargs):
        return "int32"

    def __call__(self, tensors, non_tensors, time_card):
        import jax
        jax.block_until_ready(self._apply(
            self._params, np.asarray(tensors[0].data, np.int32)))
        return None, non_tensors, time_card
