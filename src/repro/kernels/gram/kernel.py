"""Pallas TPU kernel: tiled Gram matrix  G = X^T X.

The covariance hot-spot of (MS)PCA and the rotation-subset PCA. A
(N, F) x (N, F) -> (F, F) contraction tiled for the MXU:

  grid = (F/bf, F/bf, N/bn)   -- reduction axis innermost so the output
  block (bf, bf) stays resident in VMEM while partial products accumulate.

Block shapes default to 128/256 -- MXU-aligned (multiples of 128 on the
contracting and output dims). The f32 accumulation happens in the output
ref itself (one (bf, bf) f32 tile in VMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gram_kernel(x_i_ref, x_j_ref, out_ref):
    """One (i, j, k) grid step: out[i, j] += x[k, i]^T @ x[k, j]."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    xi = x_i_ref[...]  # (bn, bf_i)
    xj = x_j_ref[...]  # (bn, bf_j)
    out_ref[...] += jax.lax.dot_general(
        xi, xj,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(
    jax.jit, static_argnames=("block_f", "block_n", "interpret")
)
def gram(
    x: jax.Array,
    *,
    block_f: int = 128,
    block_n: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """G = X^T X for X (N, F), f32 accumulation.

    N and F are padded up to block multiples (zero rows/cols contribute
    nothing to the contraction; padded output columns are sliced off).
    """
    n, f = x.shape
    x = x.astype(jnp.float32)

    pad_n = (-n) % block_n
    pad_f = (-f) % block_f
    if pad_n or pad_f:
        x = jnp.pad(x, ((0, pad_n), (0, pad_f)))
    np_, fp = x.shape

    grid = (fp // block_f, fp // block_f, np_ // block_n)

    out = pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_f), lambda i, j, k: (k, i)),
            pl.BlockSpec((block_n, block_f), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_f, block_f), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((fp, fp), jnp.float32),
        interpret=interpret,
    )(x, x)
    return out[:f, :f]
