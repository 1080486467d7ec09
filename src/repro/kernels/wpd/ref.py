"""Pure-jnp oracle for the wpd kernel: the decimating filter as a gather
per tap (repro.signal.wavelet holds the production formulations)."""

import jax
import jax.numpy as jnp


def wpd_level(x: jax.Array, h: jax.Array, g: jax.Array) -> tuple[jax.Array, jax.Array]:
    """a[b, n] = sum_k h[k] x[b, (2n+k) % N]; same with g for d.

    Taps accumulate in ascending k from zero -- the kernel's schedule, so
    interpret mode agrees bit-for-bit."""
    x = x.astype(jnp.float32)
    h = h.astype(jnp.float32)
    g = g.astype(jnp.float32)
    n = x.shape[-1]
    base = 2 * jnp.arange(n // 2, dtype=jnp.int32)
    a = jnp.zeros(x.shape[:-1] + (n // 2,), jnp.float32)
    d = jnp.zeros_like(a)
    for k in range(h.shape[0]):
        xk = x[..., (base + k) % n]
        a = a + h[k] * xk
        d = d + g[k] * xk
    return a, d
