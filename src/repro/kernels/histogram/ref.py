"""Pure-jnp oracle for the class-histogram kernel.

The level-synchronous tree grower needs, at every depth, the weighted
class histogram

    hist[t, f, b, c] = sum_n [codes[t, n, f] == b] * wy[t, n, c]

where ``codes`` holds each sample's flat (node-local * n_bins + bin)
bucket id and ``wy[t, n] = w[t, n] * onehot(y[n])`` is the per-sample
class mass. A scatter-add computes this directly but does not map to the
TPU; the kernel formulation used here instead *densifies* the scatter
into a matmul: per (tree, feature) the one-hot bucket matrix
``O[n, b] = [codes[n] == b]`` turns the histogram into ``O^T @ wy`` --
an MXU contraction over the sample axis (the trick Chen et al.'s Spark
RF uses for its vectorized in-node histogram build, adapted to matmul
hardware).

Samples are consumed in fixed ``block_n`` slabs accumulated in ascending
order -- the exact schedule of the Pallas kernel's innermost grid axis --
so interpret mode is expected to be BIT-EXACT against this reference.
Out-of-range codes (>= n_buckets, e.g. the padding sentinel) match no
bucket and contribute nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PAD_CODE_SENTINEL = -1  # any code outside [0, n_buckets) is ignored


def block_histogram(codes: jax.Array, wy: jax.Array, n_buckets: int) -> jax.Array:
    """One slab's contribution: codes (T, n, F) int32, wy (T, n, C) f32
    -> (T, F, n_buckets, C) via the one-hot matmul (no accumulation).

    ``lax.map`` over (tree, feature) pairs, NOT vmap: each iteration
    issues the SAME plain (B, n) x (n, C) dot the kernel issues per grid
    step. A vmapped formulation lowers to a batched dot_general whose
    CPU accumulation order can differ from the plain dot by an f32 ulp
    at some shapes -- this oracle trades throughput for bit-exactness
    (production histograms go through the scatter path or the kernel,
    never through here).
    """
    t, n, f = codes.shape
    c = wy.shape[-1]
    iota = jnp.arange(n_buckets, dtype=jnp.int32)
    codes_flat = codes.transpose(0, 2, 1).reshape(t * f, n)
    wy_rep = jnp.repeat(wy, f, axis=0)  # (t*f, n, C), row i == its tree's wy

    def one(args):
        codes_tf, wy_t = args
        onehot_t = (iota[:, None] == codes_tf[None, :]).astype(jnp.float32)
        return jnp.dot(onehot_t, wy_t, preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)  # (B, C)

    out = jax.lax.map(one, (codes_flat, wy_rep))  # (t*f, B, C)
    return out.reshape(t, f, n_buckets, c)


def class_histogram(
    codes: jax.Array, wy: jax.Array, n_buckets: int, *, block_n: int = 256
) -> jax.Array:
    """codes (T, N, F) int32 bucket ids, wy (T, N, C) f32 class mass
    -> (T, F, n_buckets, C) f32 weighted class histogram.

    N is zero-padded to a ``block_n`` multiple (sentinel codes, zero
    mass) and slabs accumulate in ascending order -- the kernel's
    schedule, kept here so the two paths agree bit-for-bit.
    """
    t, n, f = codes.shape
    c = wy.shape[-1]
    pad = (-n) % block_n
    if pad:
        codes = jnp.pad(codes, ((0, 0), (0, pad), (0, 0)),
                        constant_values=PAD_CODE_SENTINEL)
        wy = jnp.pad(wy, ((0, 0), (0, pad), (0, 0)))
    n_blocks = codes.shape[1] // block_n
    out = jnp.zeros((t, f, n_buckets, c), jnp.float32)
    for i in range(n_blocks):
        sl = slice(i * block_n, (i + 1) * block_n)
        out = out + block_histogram(codes[:, sl], wy[:, sl], n_buckets)
    return out


def class_histogram_scatter(
    codes: jax.Array, wy: jax.Array, n_buckets: int
) -> jax.Array:
    """Scatter-add formulation (the grower's default non-kernel path):
    semantically identical to ``class_histogram`` -- low-order f32 bits
    may differ because the sample-axis reduction order differs."""
    t, n, f = codes.shape
    c = wy.shape[-1]
    safe = jnp.where((codes >= 0) & (codes < n_buckets), codes, n_buckets)
    hist = jnp.zeros((t, f, n_buckets + 1, c), jnp.float32)
    hist = hist.at[
        jnp.arange(t)[:, None, None],
        jnp.arange(f)[None, None, :],
        safe,
    ].add(wy[:, :, None, :])
    return hist[:, :, :n_buckets]
