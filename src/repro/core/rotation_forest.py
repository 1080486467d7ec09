"""Rotation Forest (Rodriguez, Kuncheva & Alonso 2006) in pure JAX.

Paper Sec. 2.3.1: for every base tree, the feature set F is randomly split
into K subsets; PCA is applied to each subset on a bootstrap subsample;
*all* principal components are kept; the K rotations are assembled into a
sparse (F, F) rotation matrix R; the tree is trained on X @ R.

Everything is static-shaped: feature subsets are encoded as a permutation
(so the block-diagonal PCA in permuted space is an exact rotation in the
original space), and bootstrap subsampling is a 0/1 weight mask. A forest
fit is ``vmap`` over per-tree RNG keys; the MapReduce layer further shards
trees/data across the device mesh -- the paper's map phase.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import decision_tree as dt
from repro.core import pca
from repro.kernels.forest import ops as forest_ops

# The rotation covariance (before eigh) and the rotated features (whose
# quantiles become the split thresholds) decide discrete outcomes, so
# their matmuls run at f32 precision: on the TPU the default would be a
# single bf16 pass. A no-op on the CPU.
_EXACT = jax.lax.Precision.HIGHEST


class RotationForestConfig(NamedTuple):
    n_trees: int = 10
    n_subsets: int = 3          # K in the paper
    depth: int = 6
    n_classes: int = 2
    n_bins: int = 32
    bootstrap_frac: float = 0.75  # paper/ Weka default: 75% instance subsample
    min_samples: int = 2
    # Route the grower's per-level histogram through the Pallas
    # scatter-add kernel (kernels/histogram; interpret mode off-TPU).
    use_hist_kernel: bool = False


class RotationForestParams(NamedTuple):
    """Batched (leading axis = tree) parameters."""

    rotation: jax.Array          # (T, F, F)
    trees: dt.TreeParams         # all fields have leading T axis


def _build_rotation(key: jax.Array, x: jax.Array, cfg: RotationForestConfig) -> jax.Array:
    """One tree's (F, F) rotation matrix.

    The feature axis is permuted, chopped into K contiguous blocks, PCA is
    fit per block on a bootstrap subsample, and the block-diagonal matrix
    of components is un-permuted. Feature counts not divisible by K are
    handled by padding the permutation with repeats of the last block's
    features masked out of the PCA (we instead require F % K == 0 at the
    caller and pad features upstream -- see ``fit``).
    """
    n, f = x.shape
    k = cfg.n_subsets
    m = f // k
    perm_key, boot_key = jax.random.split(key)
    perm = jax.random.permutation(perm_key, f)

    xp = x[:, perm]  # (N, F) permuted features
    blocks = xp.reshape(n, k, m).transpose(1, 0, 2)  # (K, N, M)

    boot_keys = jax.random.split(boot_key, k)

    def block_pca(bkey, xb):
        # Bootstrap subsample as a weight mask (static shape).
        mask = (
            jax.random.uniform(bkey, (n,)) < cfg.bootstrap_frac
        ).astype(jnp.float32)
        # Weighted mean/cov via masked rows.
        wsum = jnp.maximum(jnp.sum(mask), 2.0)
        mean = jnp.sum(xb * mask[:, None], 0) / wsum
        xc = (xb - mean) * mask[:, None]
        cov = jnp.matmul(xc.T, xc, precision=_EXACT) / (wsum - 1.0)
        # Descending components with pca's sign convention: eigh fixes no
        # eigenvector's sign, and backends pick them differently.
        return pca._eig_sorted(cov)[1]  # (M, M), all components kept

    comps = jax.vmap(block_pca)(boot_keys, blocks)  # (K, M, M)

    # Assemble block-diagonal in permuted space.
    rot_p = jnp.zeros((f, f), jnp.float32)
    for i in range(k):
        rot_p = jax.lax.dynamic_update_slice(rot_p, comps[i], (i * m, i * m))
    # Un-permute rows/cols: R = P^T R_p P where P permutes features.
    inv = jnp.argsort(perm)
    return rot_p[inv][:, inv]


def _prepare_one(key: jax.Array, x: jax.Array, y: jax.Array, cfg: RotationForestConfig):
    """One tree's data prep: rotation, bootstrap mask, rotated binning.

    Split out of the fit so the expensive part -- the level-synchronous
    histogram grow -- can run once for the WHOLE forest
    (``dt.fit_forest_binned``) instead of per tree. The RNG schedule
    (split into rotation key + bootstrap key) is the historical
    ``_fit_one`` stream, so fits are reproducible across the refactor.
    """
    rot_key, tree_key = jax.random.split(key)
    rot = _build_rotation(rot_key, x, cfg)
    xr = jnp.matmul(x, rot, precision=_EXACT)
    # Per-tree bootstrap of training instances (bagging on top of rotation,
    # as in the Weka implementation the paper used).
    w = (
        jax.random.uniform(tree_key, (x.shape[0],)) < cfg.bootstrap_frac
    ).astype(jnp.float32)
    edges = dt.compute_bin_edges(xr, cfg.n_bins)
    xb = dt.bin_features(xr, edges)
    return rot, xb, w, edges


def _fit_one(key: jax.Array, x: jax.Array, y: jax.Array, cfg: RotationForestConfig):
    """Per-tree oracle (the pre-fusion path): kept for tests/benchmarks."""
    rot, xb, w, edges = _prepare_one(key, x, y, cfg)
    tree = dt.fit_binned(
        xb, y, w,
        depth=cfg.depth, n_classes=cfg.n_classes, n_bins=cfg.n_bins,
        min_samples=cfg.min_samples, bin_edges=edges,
    )
    return rot, tree


def _pad_features(x: jax.Array, n_subsets: int) -> jax.Array:
    if x.shape[1] % n_subsets != 0:
        pad = n_subsets - x.shape[1] % n_subsets
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x


@functools.partial(jax.jit, static_argnames=("cfg",))
def fit(key: jax.Array, x: jax.Array, y: jax.Array, cfg: RotationForestConfig) -> RotationForestParams:
    """Fit ``cfg.n_trees`` rotation trees with the fused forest grower.

    Per-tree work (rotation build, bootstrap, quantile binning) is
    vmapped over tree RNGs; the tree growing itself is ONE
    ``dt.fit_forest_binned`` call -- a single (T, F, nodes*bins, C)
    histogram per level for the whole forest rather than one histogram
    per level per tree. Bit-identical to the per-tree ``fit_per_tree``
    oracle on the same key.

    x : (N, F) float features -- F must be divisible by ``cfg.n_subsets``
        (pad features with zeros upstream otherwise; ``features.pad_to``).
    y : (N,) int labels in [0, n_classes).
    """
    x = _pad_features(x.astype(jnp.float32), cfg.n_subsets)
    y = y.astype(jnp.int32)
    keys = jax.random.split(key, cfg.n_trees)
    with jax.named_scope("rotate"):
        rots, xbs, ws, edges = jax.vmap(
            lambda k: _prepare_one(k, x, y, cfg)
        )(keys)
    with jax.named_scope("grow"):
        trees = dt.fit_forest_binned(
            xbs, y, ws,
            depth=cfg.depth, n_classes=cfg.n_classes, n_bins=cfg.n_bins,
            min_samples=cfg.min_samples, bin_edges=edges,
            use_kernel=cfg.use_hist_kernel,
        )
    return RotationForestParams(rotation=rots, trees=trees)


@functools.partial(jax.jit, static_argnames=("cfg",))
def fit_per_tree(
    key: jax.Array, x: jax.Array, y: jax.Array, cfg: RotationForestConfig
) -> RotationForestParams:
    """Reference (and benchmark-baseline) grower: vmap of independent
    single-tree fits -- T histograms per level. Semantically identical to
    ``fit``; kept as the oracle the fused grower is tested against and
    as the per-tree baseline the training benchmark times."""
    x = _pad_features(x.astype(jnp.float32), cfg.n_subsets)
    y = y.astype(jnp.int32)
    keys = jax.random.split(key, cfg.n_trees)
    rots, trees = jax.vmap(lambda k: _fit_one(k, x, y, cfg))(keys)
    return RotationForestParams(rotation=rots, trees=trees)


# Packed-forest cache: predict/predict_proba used to re-pack the forest
# on EVERY call; concrete params now pack once. Keyed on the identity of
# EVERY leaf (rotation AND tree tensors -- params sharing a rotation but
# carrying different trees must not collide), with the keying leaves held
# strongly so their ids cannot be recycled while the entry lives. Tracers
# (vmap/jit traces, e.g. core.ensemble's member vmap) bypass the cache
# entirely -- caching a tracer would leak it out of its trace.
_PACK_CACHE: dict[tuple, tuple[list, forest_ops.PackedForest]] = {}
_PACK_CACHE_MAX = 32


def pack(params: RotationForestParams) -> forest_ops.PackedForest:
    """Dense inference-only packing for the fused batched traversal
    (kernels/forest). Cached on params identity: pack once, score many
    batches. ``serving.api.ScoringProgram`` is the serving-path owner of
    the packed artifact; this cache covers ad-hoc ``predict*`` calls."""
    leaves = jax.tree.leaves(params)
    if any(isinstance(leaf, jax.core.Tracer) for leaf in leaves):
        return forest_ops.pack_forest(params)
    key = tuple(map(id, leaves))
    hit = _PACK_CACHE.get(key)
    if hit is not None and all(a is b for a, b in zip(hit[0], leaves)):
        return hit[1]
    packed = forest_ops.pack_forest(params)
    if len(_PACK_CACHE) >= _PACK_CACHE_MAX:
        _PACK_CACHE.pop(next(iter(_PACK_CACHE)))
    _PACK_CACHE[key] = (leaves, packed)
    return packed


def predict_proba(
    params: RotationForestParams,
    x: jax.Array,
    *,
    use_pallas: bool | None = False,
    packed: forest_ops.PackedForest | None = None,
) -> jax.Array:
    """(N, C) ensemble-averaged class probabilities via the fused single
    (N, n_trees) traversal -- no per-tree loop. ``use_pallas=None`` picks
    the Pallas kernel on TPU; the default False keeps the pure-JAX
    formulation (bit-stable under vmap, e.g. core.ensemble). Pass a
    pre-packed forest (``pack``/``ScoringProgram``) to skip packing."""
    if packed is None:
        packed = pack(params)
    return forest_ops.forest_predict_proba(
        packed, x.astype(jnp.float32), use_pallas=use_pallas
    )


def predict_proba_per_tree(params: RotationForestParams, x: jax.Array) -> jax.Array:
    """Reference (and benchmark-baseline) path: a Python loop over trees,
    each doing rotate -> quantile-bin -> heap walk. Semantically identical
    to ``predict_proba``; kept as the oracle the fused path is tested
    against and as the unfused baseline bench_serving times."""
    x = x.astype(jnp.float32)
    f = params.rotation.shape[-1]
    if x.shape[1] < f:
        x = jnp.pad(x, ((0, 0), (0, f - x.shape[1])))
    n_trees = params.rotation.shape[0]
    probs = [
        dt.predict_proba(
            jax.tree.map(lambda t: t[i], params.trees),
            jnp.matmul(x, params.rotation[i], precision=_EXACT),
        )
        for i in range(n_trees)
    ]
    return jnp.mean(jnp.stack(probs), axis=0)


def predict(params: RotationForestParams, x: jax.Array) -> jax.Array:
    return jnp.argmax(predict_proba(params, x), axis=-1)


def accuracy(params: RotationForestParams, x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.mean((predict(params, x) == y).astype(jnp.float32))


def merge(a: RotationForestParams, b: RotationForestParams) -> RotationForestParams:
    """Union of two forests (the MapReduce *reduce* step for training:
    each map shard trains a sub-forest; the ensemble is their union)."""
    return jax.tree.map(lambda u, v: jnp.concatenate([u, v], axis=0), a, b)
