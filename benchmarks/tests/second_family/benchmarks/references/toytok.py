"""The toy family's plain reference: mean of the embedding rows, then
the head, in float32 at ``highest`` precision. It reads the weights
the family made (float32 arrays) and imports nothing of the stages."""


def forward(weights, tokens):
    import jax.numpy as jnp
    x = jnp.take(jnp.asarray(weights["embed"], jnp.float32), tokens, axis=0)
    return jnp.dot(jnp.mean(x, axis=1),
                   jnp.asarray(weights["head"], jnp.float32),
                   precision="highest")
