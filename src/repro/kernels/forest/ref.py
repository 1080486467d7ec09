"""Pure-jnp oracle for the forest-traversal kernel.

A fitted rotation forest is *packed* (ops.pack_forest) into three dense
tensors so that inference is linear algebra instead of pointer chasing:

  proj       (T, F, L) -- column i is the rotated-space split feature of
               heap node i, pulled back into raw feature space: the
               rotation column rot[:, split_feature[i]]. One pass
               ``split_values(x, proj[t])`` evaluates EVERY node's split
               value at once.
  thr        (T, L)    -- the raw-space threshold of node i (the quantile
               bin edge the training-time split chose); +inf for dead
               nodes, so they always route left.
  leaf_probs (T, L, C) -- class distribution per leaf.

Traversal then has no data-dependent control flow: a sample reaches leaf
l iff at every level its go-right decision equals the corresponding bit
of l (heap indexing), which ``leaf_match`` evaluates with broadcasting
and one small selection matmul -- the formulation the Pallas kernel tiles
for the VPU and MXU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def path_selector(l_leaves: int, *, transposed: bool = False):
    """The (L, L) selection matrix and (1, L) left-turn counts that
    ``leaf_match`` contracts decisions with.

    ``sel[i, l]`` is +1 where heap node i is an ancestor of leaf l that
    the path to l leaves to the right, -1 where it leaves to the left;
    ``lefts[l]`` counts the left turns on that path. Leaf l's ancestor at
    level j is heap id 2**j + (l >> (depth - j)) and the direction taken
    out of it is bit (depth - 1 - j) of l. Built from iotas, so it traces
    inside a Pallas kernel too. ``transposed`` returns ``sel.T`` and
    ``lefts.T`` (leaf-major), the layout the kernel's (L, B) tiles use."""
    depth = l_leaves.bit_length() - 1
    node_axis, leaf_axis = (1, 0) if transposed else (0, 1)
    shape = (l_leaves, l_leaves)
    node = jax.lax.broadcasted_iota(jnp.int32, shape, node_axis)
    leaf = jax.lax.broadcasted_iota(jnp.int32, shape, leaf_axis)
    sel = jnp.zeros(shape, jnp.float32)
    lefts = jnp.zeros(shape, jnp.float32)
    for j in range(depth):
        right = (leaf >> (depth - 1 - j)) & 1
        anc = (1 << j) + (leaf >> (depth - j))
        sel = sel + jnp.where(node == anc, 2.0 * right - 1.0, 0.0)
        lefts = lefts + (1.0 - right)
    # Every row (column, transposed) of ``lefts`` holds the same counts.
    lefts = lefts[:, 0:1] if transposed else lefts[0:1, :]
    return sel, lefts


def leaf_match(dirs: jax.Array) -> jax.Array:
    """(..., L) per-heap-node go-right booleans -> (..., L) one-hot leaf
    membership. L = 2**depth; heap ids: root = 1, children of i = 2i, 2i+1;
    slot 0 is unused; leaf l is heap id L + l.

    ``dirs @ sel + lefts`` (``path_selector``) counts the levels whose
    decision agrees with the path to each leaf; the sample reaches the
    leaf where all ``depth`` agree. Every term is a small integer, so the
    count is exact at any matmul precision."""
    l_leaves = dirs.shape[-1]
    sel, lefts = path_selector(l_leaves)
    agree = jnp.dot(
        dirs.astype(jnp.float32), sel, preferred_element_type=jnp.float32
    )
    return agree + lefts == l_leaves.bit_length() - 1


# Features per ``lax.scan`` step of ``split_values``.
_SPLIT_GROUP = 8


def split_values(x: jax.Array, proj: jax.Array) -> jax.Array:
    """(B, F) raw features, (F, L) node projections -> (B, L) split values.

    The value is compared against a threshold, so it decides a branch: it
    is summed in f32 in ascending feature order from zero, with no MXU
    pass. A matmul's accumulation order (and, on the TPU, its bf16 passes)
    depends on the tiling and the backend, and a sample sitting on a bin
    edge would then route differently in the kernel and in this
    reference. Features go ``_SPLIT_GROUP`` at a time through a
    ``lax.scan`` so the compiled program stays small at F in the
    hundreds."""
    group = _SPLIT_GROUP
    n_grp = x.shape[1] // group

    def add(acc, xs, ps):
        for j in range(xs.shape[1]):
            acc = acc + xs[:, j : j + 1] * ps[j : j + 1, :]
        return acc

    acc = jnp.zeros((x.shape[0], proj.shape[1]), jnp.float32)
    if n_grp:
        head = n_grp * group
        acc, _ = jax.lax.scan(
            lambda a, xp: (add(a, *xp), None), acc,
            (
                jnp.swapaxes(x[:, :head].reshape(-1, n_grp, group), 0, 1),
                proj[:head].reshape(n_grp, group, -1),
            ),
        )
    return add(acc, x[:, n_grp * group :], proj[n_grp * group :])


def forest_traverse(
    x: jax.Array, proj: jax.Array, thr: jax.Array, leaf_probs: jax.Array
) -> jax.Array:
    """x (B, F), packed forest (T, ...) -> (B, C) SUMMED leaf probabilities
    over trees (callers divide by T for the ensemble mean)."""

    def one_tree(proj_t, thr_t, leaf_t):
        match = leaf_match(split_values(x, proj_t) > thr_t[None, :])
        return jnp.dot(
            match.astype(jnp.float32), leaf_t,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )

    probs = jax.vmap(one_tree)(proj, thr, leaf_probs)  # (T, B, C)
    # Sequential (ascending-tree) accumulation, NOT jnp.sum: matches the
    # kernel's out += probs_t schedule bit-for-bit in f32.
    total = probs[0]
    for t in range(1, probs.shape[0]):
        total = total + probs[t]
    return total
