"""Distributed Rotation Forest training (the paper's MapReduce TRAIN phase).

PRs 1-2 built the serving half (fused scoring, continuous batching);
this module is the training half: the paper's Hadoop schedule

  map    : each input split trains a sub-forest on its own shard of the
           recording (feature extraction riding inside the map task);
  reduce : the ensemble is the UNION of the sub-forests
           (``mapreduce.reduce_concat`` == ``rotation_forest.merge``).

One wrinkle the paper's Weka job glosses over: z-score feature
normalization must use GLOBAL statistics or the shards' trees disagree
about feature scales at serve time. The map task therefore combines
global moments across shards BEFORE fitting -- two all-gathers of (F,)
vectors, summed in shard order -- after which every shard normalizes
identically and the union forest is directly servable.

Two execution modes, one set of per-shard stages (wired directly onto
``shard_map`` / ``lax.map`` rather than through the ``MapReduce`` class
because the union reduce runs INSIDE the map, after the global stats):

  * ``fit_mapreduce(..., mesh=mesh)``       -- real SPMD ``shard_map``.
  * ``fit_mapreduce(..., n_shards=S)``      -- one-device emulation that
    runs each stage shard by shard under ``lax.map``, bit-identical to
    an S-device mesh run (same per-shard programs, same shard-ordered
    sums, same per-shard RNG fold-in).

Each shard trains ``ceil(n_trees / S)`` trees by default -- a union of
``S * ceil(n_trees / S)`` trees: exactly ``cfg.n_trees`` when S divides
it, slightly more otherwise (pass ``trees_per_shard`` to pin the count).
Every sub-forest fit runs the fused grower
(``decision_tree.fit_forest_binned``) -- the distribution axis
multiplies the fusion win instead of replacing it.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import mapreduce as mr
from repro.core import rotation_forest as rf


class DistributedFitResult(NamedTuple):
    """What ``fit_mapreduce`` returns (replicated on every shard).

    forest    : union of the per-shard sub-forests (leading axis = tree).
    feat_mean : (F,) GLOBAL feature means (combined across shards).
    feat_std  : (F,) global feature stds.
    """

    forest: rf.RotationForestParams
    feat_mean: jax.Array
    feat_std: jax.Array


def _ordered_sum(stacked: jax.Array) -> jax.Array:
    """Sum a (S, ...) stack of per-shard partials in shard order.

    The all-reduce behind ``psum`` picks its own summation order, which
    differs between a device mesh and the one-device emulation, and an
    f32 ulp in the global mean is enough to move a quantile bin edge.
    Gathering the partials and adding them 0, 1, ..., S-1 gives every
    shard, in both modes, the same bits."""
    total = stacked[0]
    for i in range(1, stacked.shape[0]):
        total = total + stacked[i]
    return total


def _row_sum(rows: jax.Array) -> jax.Array:
    """Column sums of (n, F) rows, added row by row in order. A
    ``jnp.sum`` leaves the order to the compiler, which picks it by the
    fusion the reduction lands in -- and that differs between the mesh
    program and the emulation."""
    total, _ = jax.lax.scan(
        lambda acc, row: (acc + row, None), jnp.zeros_like(rows[0]), rows
    )
    return total


def _moment_partials(feats: jax.Array, mean: jax.Array | None = None):
    """One shard's contribution to the global moments: (row count, column
    sums) for the first pass, column sums of squares about the global
    ``mean`` for the second."""
    if mean is None:
        return jnp.asarray(feats.shape[0], jnp.float32), _row_sum(feats)
    return _row_sum((feats - mean) ** 2)


def global_moments(feats, combine) -> tuple[jax.Array, jax.Array]:
    """Per-shard (n, F) features -> global (mean, std).

    ``combine(partial_fn, feats)`` applies ``partial_fn`` to every
    shard's rows and sums the results in shard order: an all-gather plus
    ``_ordered_sum`` on a mesh (``feats`` is one shard's rows), a
    ``lax.map`` plus ``_ordered_sum`` in the emulation (``feats`` is the
    (S, n, F) stack).

    TWO-PASS: combine the mean first, then the centered squares -- two
    O(F) reductions instead of one. The single-pass ``E[x^2] - mean^2``
    shortcut cancels catastrophically in f32 for high-mean/low-variance
    features (this repo's WPD power features reach |mean|/std ~ 130,
    where the shortcut is already ~1000 ulp off; at |mean|/std ~ 1e5 it
    clamps the variance to zero and the 1e-6 std floor blows the
    normalized feature up ~1e4x). Centered, this matches
    ``signal.features.normalize`` (biased std + 1e-6 floor) to f32
    rounding.
    """
    with jax.named_scope("moments"):
        count, total = combine(_moment_partials, feats)
        mean = total / count
        centered_sq = combine(lambda f: _moment_partials(f, mean), feats)
        return mean, jnp.sqrt(centered_sq / count) + 1e-6


def _shard_trees(n_trees: int, n_shards: int) -> int:
    return max(1, -(-n_trees // n_shards))


def fit_mapreduce(
    key: jax.Array,
    x: jax.Array,
    y: jax.Array,
    cfg: rf.RotationForestConfig,
    *,
    mesh: Mesh | None = None,
    n_shards: int | None = None,
    trees_per_shard: int | None = None,
    feature_fn: Callable[[jax.Array], jax.Array] | None = None,
    axis_name: str = "data",
) -> DistributedFitResult:
    """Train a rotation forest MapReduce-style over row shards of (x, y).

    x : (N, ...) training rows, sharded on the leading axis along
        ``axis_name``. With ``feature_fn`` given, x can be RAW data
        (e.g. EEG windows) and the map task extracts features per shard
        -- the paper's signal-processing map riding with training.
    y : (N,) int labels, sharded identically.

    Exactly one of ``mesh`` (SPMD ``shard_map`` over the mesh's
    ``axis_name`` axis) or ``n_shards`` (single-device lax.map emulation,
    bit-identical) selects the execution mode. N must divide evenly by
    the shard count; when ``feature_fn`` carries cross-row context
    (e.g. per-chunk MSPCA denoise), align shard boundaries with it.

    Each shard trains ``trees_per_shard`` trees (default
    ``ceil(cfg.n_trees / S)``, so the union holds ``cfg.n_trees`` trees
    when S divides it and slightly more otherwise) with an
    ``axis_index``-folded key -- the map; ``reduce_concat`` unions the
    sub-forests -- the reduce. Returns the replicated union forest plus
    the global normalization stats.
    """
    if (mesh is None) == (n_shards is None):
        raise ValueError("pass exactly one of mesh= or n_shards=")
    shards = mesh.shape[axis_name] if mesh is not None else int(n_shards)
    n_rows = x.shape[0]
    if n_rows % shards != 0:
        raise ValueError(
            f"{n_rows} training rows do not shard evenly over {shards} "
            f"shards; pad or trim to a multiple (rows are sharded on the "
            "leading axis)"
        )
    if trees_per_shard is not None and trees_per_shard < 1:
        raise ValueError(f"trees_per_shard={trees_per_shard} must be >= 1")
    shard_cfg = cfg._replace(
        n_trees=trees_per_shard if trees_per_shard is not None
        else _shard_trees(cfg.n_trees, shards)
    )

    def featurize(x_s):
        with jax.named_scope("featurize"):
            feats = feature_fn(x_s) if feature_fn is not None else x_s
            return feats.astype(jnp.float32)

    def fit_shard(shard, normed, y_s):
        return rf.fit(
            jax.random.fold_in(key, shard), normed,
            y_s.astype(jnp.int32), shard_cfg,
        )

    if mesh is not None:
        def combine(partial_fn, feats):
            parts = jax.lax.all_gather(partial_fn(feats), axis_name)
            return jax.tree.map(_ordered_sum, parts)

        def shard_fit(x_s, y_s):
            feats = featurize(x_s)
            mean, std = global_moments(feats, combine)
            sub = fit_shard(
                jax.lax.axis_index(axis_name), (feats - mean) / std, y_s
            )
            # The reduce: union of sub-forests, replicated on every shard.
            with jax.named_scope("gather"):
                forest = mr.reduce_concat(sub, axis_name)
            return DistributedFitResult(
                forest=forest, feat_mean=mean, feat_std=std,
            )

        fn = jax.shard_map(
            shard_fit, mesh=mesh,
            in_specs=(P(axis_name), P(axis_name)),
            out_specs=P(), check_vma=False,
        )
        return fn(x, y)

    # One-device emulation. Every per-shard stage runs under ``lax.map``,
    # one shard at a time at the shard's own shapes -- exactly the
    # program each device runs under ``shard_map``. A ``vmap`` would add
    # a batch dimension to every matmul and eigh, and batched kernels do
    # not round like unbatched ones.
    def split(t):
        return t.reshape((shards, t.shape[0] // shards) + t.shape[1:])

    def per_shard(fn, *stacked):
        return jax.lax.map(lambda args: fn(*args), stacked)

    def combine(partial_fn, feats):
        parts = per_shard(partial_fn, feats)
        return jax.tree.map(_ordered_sum, parts)

    feats = per_shard(featurize, split(x))
    mean, std = global_moments(feats, combine)
    subs = per_shard(
        fit_shard, jnp.arange(shards), (feats - mean) / std, split(y)
    )
    forest = jax.tree.map(
        lambda t: t.reshape((-1,) + t.shape[2:]), subs
    )
    return DistributedFitResult(forest=forest, feat_mean=mean, feat_std=std)
