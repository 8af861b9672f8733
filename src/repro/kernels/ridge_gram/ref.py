"""Pure-jnp oracle for the ridge Gram kernel.  HIGHEST precision: a TPU
runs a default-precision f32 dot as one bf16 pass, and the ridge solve
downstream is ill-conditioned enough to amplify that error."""
import jax
import jax.numpy as jnp


def gram(x, y):
    return jnp.matmul(x.astype(jnp.float32).T, y.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
