"""Pallas TPU kernel: batched rotation-forest traversal.

Replaces per-tree pointer-chasing inference with one (B, n_trees) pass
over the packed forest (see ref.py for the packing). The kernel works on
sample-minor tiles, so samples fill the 128 lanes: per grid step it
evaluates every split of one tree for a (F, block_b) tile of raw
features (an f32 sum in ascending feature order, the order of
``ref.split_values``, so routing is bit-identical to the reference
whatever the tiling), resolves leaf membership with the
``ref.path_selector`` matmul, and gathers the leaf class mass into a
lane-dense (C, block_b) output tile with a one-hot matmul.

Grid: (B / block_b, T) with the tree axis innermost, so each output tile
stays resident while all T trees accumulate into it -- the output is
written once per batch tile instead of once per (tile, tree).

VMEM per step (f32): x (F, block_b) + proj^T (L, F) + leaf^T (C, L) + the
(L, block_b) split-value tile. Defaults block_b = 256, F = 288, L = 64:
~0.5 MiB. On the TPU block_b must be a multiple of 128 (the lane width).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.forest.ref import path_selector


def _forest_kernel(x_ref, proj_ref, thr_ref, leaf_ref, out_ref):
    t = pl.program_id(1)
    n_feat, l_leaves = x_ref.shape[0], proj_ref.shape[0]
    # val[l, b] = sum_f proj[f, l] * x[b, f], ascending f from zero
    # (ref.split_values).
    val = jnp.zeros((l_leaves, x_ref.shape[1]), jnp.float32)
    for f in range(n_feat):
        val = val + proj_ref[:, f : f + 1] * x_ref[f : f + 1, :]
    dirs = (val > thr_ref[...]).astype(jnp.float32)  # thr block is (L, 1)
    sel_t, lefts_t = path_selector(l_leaves, transposed=True)
    agree = jnp.dot(sel_t, dirs, preferred_element_type=jnp.float32)
    match = (agree + lefts_t == l_leaves.bit_length() - 1).astype(jnp.float32)
    probs = jnp.dot(
        leaf_ref[...], match, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # (C, block_b)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = probs

    @pl.when(t > 0)
    def _accum():
        out_ref[...] = out_ref[...] + probs


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def forest_traverse(
    x: jax.Array,
    proj: jax.Array,
    thr: jax.Array,
    leaf_probs: jax.Array,
    *,
    block_b: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """x (B, F), proj (T, F, L), thr (T, L), leaf_probs (T, L, C)
    -> (B, C) summed-over-trees leaf probabilities (same contract as
    ref.forest_traverse). B is padded to a block multiple."""
    b, f = x.shape
    n_trees, _, l_leaves = proj.shape
    n_classes = leaf_probs.shape[-1]
    x = x.astype(jnp.float32)
    pad_b = (-b) % block_b
    if pad_b:
        x = jnp.pad(x, ((0, pad_b), (0, 0)))
    bp = x.shape[0]

    out = pl.pallas_call(
        _forest_kernel,
        grid=(bp // block_b, n_trees),
        in_specs=[
            pl.BlockSpec((f, block_b), lambda i, t: (0, i)),
            pl.BlockSpec((None, l_leaves, f), lambda i, t: (t, 0, 0)),
            pl.BlockSpec((None, l_leaves, 1), lambda i, t: (t, 0, 0)),
            pl.BlockSpec((None, n_classes, l_leaves), lambda i, t: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((n_classes, block_b), lambda i, t: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n_classes, bp), jnp.float32),
        interpret=interpret,
    )(
        x.T,
        jnp.swapaxes(proj.astype(jnp.float32), 1, 2),
        thr.astype(jnp.float32)[:, :, None],
        jnp.swapaxes(leaf_probs.astype(jnp.float32), 1, 2),
    )
    return out.T[:b]
