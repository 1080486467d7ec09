"""Pallas TPU kernel: weighted class histograms for the tree grower.

The scatter-add at the heart of level-synchronous histogram tree
building does not lower to TPU; this kernel computes the identical
result as a dense one-hot contraction (see ref.py):

    hist[t, f, b, c] = sum_n [codes[t, n, f] == b] * wy[t, n, c]

Grid: (T, F, N / block_n) with the sample axis innermost, so each
(n_buckets, C) output tile stays resident in VMEM while every sample
slab accumulates into it -- the output is written once per (tree,
feature) instead of once per slab. Per step the kernel materializes the
(n_buckets, block_n) transposed one-hot bucket matrix with a
branch-free VPU compare of a lane-dense (1, block_n) code row against a
broadcasted iota and contracts it against the slab's (block_n, C)
class-mass tile on the MXU at f32 precision (the histogram decides
splits, so no bf16 pass).

VMEM per step (f32): codes (1, block_n) + wy (block_n, C) + onehot
(n_buckets, block_n) + out (n_buckets, C). Worst case in this repo
(depth-6 level 5, 32 bins: n_buckets = 1024, block_n = 256) is ~1.3 MiB
-- comfortable with double buffering. The output tile's last dim is C
(= 2 for seizure scoring); ``tests/test_tpu_compile.py`` compiles the
kernel for a v5e chip at the paper's widths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hist_kernel(codes_ref, wy_ref, out_ref, *, n_buckets: int):
    i = pl.program_id(2)
    codes = codes_ref[...]                   # (1, block_n) int32
    wy = wy_ref[...]                         # (block_n, C) f32
    block_n = codes.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (n_buckets, block_n), 0)
    onehot_t = (iota == codes).astype(jnp.float32)   # (B, block_n)
    part = jnp.dot(onehot_t, wy, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = part

    @pl.when(i > 0)
    def _accum():
        out_ref[...] = out_ref[...] + part


@functools.partial(
    jax.jit, static_argnames=("n_buckets", "block_n", "interpret")
)
def class_histogram(
    codes: jax.Array,
    wy: jax.Array,
    *,
    n_buckets: int,
    block_n: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """codes (T, N, F) int32 bucket ids, wy (T, N, C) f32 class mass
    -> (T, F, n_buckets, C) f32 (same contract as ref.class_histogram).
    N is padded to a block multiple; out-of-range codes are ignored.
    On the TPU block_n must be a multiple of 128 (the lane width)."""
    t, n, f = codes.shape
    c = wy.shape[-1]
    pad = (-n) % block_n
    if pad:
        # Sentinel codes match no bucket; zero mass double-guards them.
        codes = jnp.pad(codes, ((0, 0), (0, pad), (0, 0)),
                        constant_values=-1)
        wy = jnp.pad(wy, ((0, 0), (0, pad), (0, 0)))
    n_blocks = codes.shape[1] // block_n
    # Sample-minor codes, (T, F, 1, N): each step reads one lane-dense
    # (1, block_n) row of one (tree, feature) pair.
    codes_t = jnp.swapaxes(codes.astype(jnp.int32), 1, 2)[:, :, None, :]

    return pl.pallas_call(
        functools.partial(_hist_kernel, n_buckets=n_buckets),
        grid=(t, f, n_blocks),
        in_specs=[
            pl.BlockSpec(
                (None, None, 1, block_n), lambda ti, fi, ni: (ti, fi, 0, ni)
            ),
            pl.BlockSpec((None, block_n, c), lambda ti, fi, ni: (ti, ni, 0)),
        ],
        out_specs=pl.BlockSpec(
            (None, None, n_buckets, c), lambda ti, fi, ni: (ti, fi, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((t, f, n_buckets, c), jnp.float32),
        interpret=interpret,
    )(codes_t, wy.astype(jnp.float32))
