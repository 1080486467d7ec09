"""Pure-jnp oracle for the gram kernel."""

import jax
import jax.numpy as jnp


def gram(x: jax.Array) -> jax.Array:
    """G = X^T X with f32 accumulation."""
    x = x.astype(jnp.float32)
    return jnp.einsum(
        "nf,ng->fg", x, x, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
