"""Pallas TPU kernel: one wavelet-packet analysis level (paper eqs. 2-3).

Computes, for a batch of rows x (B, N) and QMF filters h, g (L taps):

    a[b, n] = sum_k h[k] * x[b, (2n + k) mod N]
    d[b, n] = sum_k g[k] * x[b, (2n + k) mod N]

TPU adaptation: instead of a decimating convolution (a gather per output
element -- hostile to the VPU), the row is split into its even and odd
polyphase components with static strided slices in XLA, outside the
kernel (a lane de-interleave inside the kernel is a shape cast the TPU
cannot lower). Tap k then reads component k%2 circularly shifted by
k//2; each shift is two static slices + a concat, so the whole level is
2L multiply-adds over VMEM-resident tiles -- memory-bound, which is the
filterbank's roofline anyway (arithmetic intensity ~ L/4 flops/byte).

Grid: (B / block_b,). Each step owns two (block_b, N/2) tiles in VMEM
(8 s x 256 Hz windows: N = 2048 -> 8 KiB/row f32; block_b = 256 rows ->
2 MiB, comfortably inside the ~16 MiB v5e VMEM with double buffering).
The filter taps ride in SMEM and are read as scalars.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _roll_rows(x: jax.Array, s: int) -> jax.Array:
    """Circular left-shift by static s along the last axis (2 slices)."""
    if s == 0:
        return x
    return jnp.concatenate([x[:, s:], x[:, :s]], axis=1)


def _wpd_level_kernel(even_ref, odd_ref, h_ref, g_ref, a_ref, d_ref, *,
                      taps: int):
    # even[b, n] = x[b, 2n], odd[b, n] = x[b, 2n + 1].
    even = even_ref[...]
    odd = odd_ref[...]
    a = jnp.zeros(even.shape, jnp.float32)
    d = jnp.zeros(even.shape, jnp.float32)
    for k in range(taps):
        # x[b, 2n + k] = (k even ? even : odd) shifted left by k // 2.
        lane = even if k % 2 == 0 else odd
        shifted = _roll_rows(lane, k // 2)
        a = a + h_ref[k] * shifted
        d = d + g_ref[k] * shifted
    a_ref[...] = a
    d_ref[...] = d


@functools.partial(
    jax.jit, static_argnames=("taps", "block_b", "interpret")
)
def wpd_level(
    x: jax.Array,
    h: jax.Array,
    g: jax.Array,
    *,
    taps: int,
    block_b: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One analysis level for x (B, N) -> (approx, detail) each (B, N/2).

    B is padded to a block multiple; N must be even.
    """
    b, n = x.shape
    if n % 2:
        raise ValueError(f"row length {n} must be even")
    x = x.astype(jnp.float32)
    pad_b = (-b) % block_b
    if pad_b:
        x = jnp.pad(x, ((0, pad_b), (0, 0)))
    bp = x.shape[0]
    half = n // 2

    kern = functools.partial(_wpd_level_kernel, taps=taps)
    rows = pl.BlockSpec((block_b, half), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    a, d = pl.pallas_call(
        kern,
        grid=(bp // block_b,),
        in_specs=[rows, rows, smem, smem],
        out_specs=[rows, rows],
        out_shape=[
            jax.ShapeDtypeStruct((bp, half), jnp.float32),
            jax.ShapeDtypeStruct((bp, half), jnp.float32),
        ],
        interpret=interpret,
    )(x[:, 0::2], x[:, 1::2], h.astype(jnp.float32), g.astype(jnp.float32))
    return a[:b], d[:b]
