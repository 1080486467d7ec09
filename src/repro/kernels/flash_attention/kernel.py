"""Pallas TPU kernel: flash attention (prefill), online softmax.

This is the fused form of the model zoo's dominant memory-roofline term:
the dry-runs show the unfused jnp attention writes O(S^2) score tensors
through HBM (EXPERIMENTS.md §Roofline); on TPU this kernel keeps each
(block_q x block_k) score tile in VMEM/VREGs.

Layout: q, k, v are (BH, S, hd), batch*heads flattened (GQA expansion in
ops.py).  Grid = (BH, S/block_q, S/block_k); the k axis is the innermost
("arbitrary") grid dim, with running max / sum / output accumulators in
VMEM scratch carried across k steps (the classic flash-attention-2
schedule, one q tile resident per core).

VMEM budget per step (bf16 in, f32 accum):
  q (block_q x hd) + k,v (block_k x hd) + acc (block_q x hd f32)
  + scores (block_q x block_k f32); defaults 512x512x128
  -> ~1.4 MiB with double buffering, MXU-aligned (multiples of 128).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  sm_scale: float, causal: bool, block_q: int, block_k: int,
                  n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body():
        q = q_ref[0]                       # (bq, hd)
        k = k_ref[0]                       # (bk, hd)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (bq, bk)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_scr[...]                # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])    # (bq, bk) f32
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = (acc_scr[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    if causal:
        # skip fully-masked tiles (upper triangle)
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(body)
    else:
        body()

    @pl.when(ki == n_k - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 512,
                    block_k: int = 512, interpret: bool = False
                    ) -> jax.Array:
    """q, k, v: (BH, S, hd) -> (BH, S, hd)."""
    bh, s, hd = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    n_q = s // block_q
    n_k = s // block_k
    sm_scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, n_k=n_k)

    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),       # running max m
            pltpu.VMEM((block_q,), jnp.float32),       # running sum l
            pltpu.VMEM((block_q, hd), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
