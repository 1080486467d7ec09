"""The plain reference of the seizure pipeline, written from its
description (Jukic & Subasi 2017, Sec. 2.1-2.6) and sharing no code with
the program under test.

  raw chunk (60, 3, 2048) -> MSPCA denoise of the 2048 x 180 matrix
  (periodized db4 DWT to level 5 along samples, PCA across the 180
  columns at every scale keeping 30 components, inverse DWT)
  -> level-4 db4 wavelet packet per window and channel, six statistics
  per terminal node (288 features) -> z-score -> rotation forest
  (per-tree rotation, quantile-bin thresholds, depth-6 heap walk, mean
  leaf distribution, argmax) -> majority vote per chunk -> 3-of-5 alarm.

Everything is float32 with every matrix product at ``HIGHEST``. The
control runs the same code with ``low=True``: every stage's output and
every product's operands are rounded to bfloat16 (products accumulate in
float32, as the chip's one-pass default does), the precision a later
change might be tempted to serve in.

The training half follows the MapReduce fit (Sec. 2.4): per-shard
features, global moments, per-shard sub-forests grown level by level on
32 quantile bins with Gini splits, union of the sub-forests. It draws the
same random numbers from the same keys as the configuration's fit
(per-shard key ``fold_in(key, shard)``, per-tree permutation, block
bootstraps and instance bootstrap), so that a sound fit and this one grow
the same trees wherever their features round alike.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# Daubechies-4 scaling filter (8 taps, sum sqrt(2)).
DB4 = np.array([
    0.23037781330885523, 0.7148465705525415, 0.6308807679295904,
    -0.02798376941698385, -0.18703481171888114, 0.030841381835986965,
    0.032883011666982945, -0.010597401784997278,
], np.float32)
DB4_HIGH = np.array(
    [(-1.0) ** k * DB4[len(DB4) - 1 - k] for k in range(len(DB4))], np.float32
)

MSPCA_LEVEL = 5
MSPCA_KEEP = 30
WPD_LEVEL = 4
ALARM_K, ALARM_M = 3, 5


class Numerics(NamedTuple):
    """How the reference rounds: f32 at HIGHEST, or the bf16 control."""

    low: bool = False

    def q(self, x):
        return x.astype(jnp.bfloat16).astype(jnp.float32) if self.low else x

    def einsum(self, spec: str, a, b):
        """A product at HIGHEST; the control first rounds its operands to
        bfloat16 (a bfloat16 product with float32 accumulation, the
        chip's one-pass default, on any backend)."""
        return jnp.einsum(spec, self.q(jnp.asarray(a)), self.q(jnp.asarray(b)),
                          precision=jax.lax.Precision.HIGHEST)


EXACT = Numerics(False)
CONTROL = Numerics(True)


# ---------------------------------------------------------------------------
# Wavelets: periodized orthogonal analysis a[n] = sum_k h[k] x[(2n+k) mod N]
# and its transpose.
# ---------------------------------------------------------------------------

def _taps_index(n: int) -> np.ndarray:
    return (2 * np.arange(n // 2)[:, None] + np.arange(len(DB4))[None, :]) % n


def analysis(x, num: Numerics):
    """x (..., N) -> (approximation, detail), each (..., N/2)."""
    win = x[..., _taps_index(x.shape[-1])]                    # (..., N/2, 8)
    a = num.einsum("...nk,k->...n", win, DB4)
    d = num.einsum("...nk,k->...n", win, DB4_HIGH)
    return num.q(a), num.q(d)


def synthesis(a, d, num: Numerics):
    """Transpose of ``analysis``: (..., N/2) x 2 -> (..., N)."""
    n = 2 * a.shape[-1]
    contrib = a[..., :, None] * DB4 + d[..., :, None] * DB4_HIGH
    out = jnp.zeros(a.shape[:-1] + (n,), jnp.float32)
    return num.q(out.at[..., _taps_index(n)].add(contrib))


# ---------------------------------------------------------------------------
# Front end
# ---------------------------------------------------------------------------

def _pca_keep(c, num: Numerics):
    """c (P, n): PCA across the P variables over n samples, projected on
    the MSPCA_KEEP leading components and back."""
    mean = jnp.mean(c, axis=1, keepdims=True)
    xc = c - mean
    cov = num.q(num.einsum("pn,qn->pq", xc, xc)
                / (c.shape[1] - 1))
    _, vecs = jnp.linalg.eigh(cov)                    # ascending eigenvalues
    top = num.q(vecs[:, -MSPCA_KEEP:])
    proj = num.einsum("pk,pn->kn", top, xc)
    return num.q(num.einsum("pk,kn->pn", top, proj)
                 + mean)


def denoise_chunk(chunk, num: Numerics):
    """(60, 3, 2048) raw windows -> (60, 3, 2048) MSPCA-denoised."""
    w, c, n = chunk.shape
    cols = chunk.reshape(w * c, n).astype(jnp.float32)   # one column per row
    mean = jnp.mean(cols, axis=1, keepdims=True)
    cur = cols - mean
    details = []
    for _ in range(MSPCA_LEVEL):
        cur, d = analysis(cur, num)
        details.append(d)
    cur = _pca_keep(cur, num)
    for d in reversed(details):
        cur = synthesis(cur, _pca_keep(d, num), num)
    return (cur + mean).reshape(w, c, n)


def node_stats(coeffs):
    """(..., M) -> (..., 6): mean |c|, power, std, skewness, kurtosis and
    the Shannon entropy of the normalized energy."""
    eps = 1e-8
    mu = jnp.mean(coeffs, -1, keepdims=True)
    cc = coeffs - mu
    var = jnp.mean(cc ** 2, -1)
    std = jnp.sqrt(var + eps)
    energy = coeffs ** 2
    p = energy / (jnp.sum(energy, -1, keepdims=True) + eps)
    return jnp.stack([
        jnp.mean(jnp.abs(coeffs), -1),
        jnp.mean(energy, -1),
        std,
        jnp.mean(cc ** 3, -1) / (std ** 3 + eps),
        jnp.mean(cc ** 4, -1) / (var ** 2 + eps),
        -jnp.sum(p * jnp.log(p + eps), -1),
    ], axis=-1)


def wpd_features(wins, num: Numerics):
    """(W, 3, 2048) windows -> (W, 288): 16 terminal nodes per channel in
    natural order (node 2i is the low branch of node i), 6 stats each."""
    nodes = [wins]
    for _ in range(WPD_LEVEL):
        nxt = []
        for x in nodes:
            a, d = analysis(x, num)
            nxt += [a, d]
        nodes = nxt
    stats = num.q(node_stats(jnp.stack(nodes, axis=-2)))   # (W, 3, 16, 6)
    return stats.reshape(wins.shape[0], -1)


@functools.partial(jax.jit, static_argnames=("num",))
def chunk_features(chunks, num: Numerics = EXACT):
    """(K, 60, 3, 2048) chunks -> (K, 60, 288) feature rows."""
    return jax.vmap(lambda ch: wpd_features(denoise_chunk(ch, num), num))(
        chunks)


BLOCK_CHUNKS = 32


def features_in_blocks(chunks, num: Numerics = EXACT, block: int | None = None):
    """(K, 60, 3, 2048) chunks, host or device -> (K, 60, 288) on the
    device, ``block`` (default ``BLOCK_CHUNKS``) chunks at a time so that
    the reference's memory stays small; the last block is padded with
    repeats so that one program serves every block."""
    block = block or BLOCK_CHUNKS
    out = []
    for s in range(0, chunks.shape[0], block):
        part = jnp.asarray(chunks[s:s + block])
        n = part.shape[0]
        if n < block:
            part = jnp.concatenate(
                [part, jnp.repeat(part[-1:], block - n, axis=0)])
        out.append(chunk_features(part, num)[:n])
    return jnp.concatenate(out)


# ---------------------------------------------------------------------------
# Forest
# ---------------------------------------------------------------------------

class Forest(NamedTuple):
    """A rotation forest in plain form (leading axis = tree).

    rotation  : (T, F, F) -- tree t splits on columns of x @ rotation[t].
    feature   : (T, L) int32 -- rotated feature of heap node i (root 1,
                children 2i, 2i+1; -1 = no split).
    threshold : (T, L) float32 -- go right iff value > threshold
                (+inf = no split).
    leaf      : (T, L, C) float32 -- class distribution of leaf l
                (heap id L + l).
    """

    rotation: jax.Array
    feature: jax.Array
    threshold: jax.Array
    leaf: jax.Array


@jax.jit
def forest_proba(forest: Forest, x) -> jax.Array:
    """(N, F) normalized features -> (N, C) mean leaf distribution."""
    n_leaves = forest.feature.shape[1]
    depth = n_leaves.bit_length() - 1

    def one_tree(rot, feat, thr, leaf):
        node = jnp.ones((x.shape[0],), jnp.int32)
        for _ in range(depth):
            f = feat[node]
            col = rot[:, jnp.maximum(f, 0)]                     # (F, N)
            v = jnp.einsum("nf,fn->n", x, col,
                           precision=jax.lax.Precision.HIGHEST)
            right = (f >= 0) & (v > thr[node])
            node = 2 * node + right.astype(jnp.int32)
        return leaf[node - n_leaves]

    probs = jax.vmap(one_tree)(*forest)
    return jnp.mean(probs, axis=0)


def predict(forest: Forest, x) -> jax.Array:
    """(N, F) -> (N,) labels; a tie goes to class 0."""
    return jnp.argmax(forest_proba(forest, x), axis=-1).astype(jnp.int32)


def normalize(feats, mean, std):
    return (feats - mean) / std


def moments(feats):
    """Mean and standard deviation (plus the 1e-6 floor) per column."""
    mean = jnp.mean(feats, axis=0)
    return mean, jnp.sqrt(jnp.mean((feats - mean) ** 2, axis=0)) + 1e-6


# ---------------------------------------------------------------------------
# Votes and alarms (host side, over every event of a session)
# ---------------------------------------------------------------------------

def chunk_vote(window_preds: np.ndarray) -> tuple[int, float]:
    """Majority vote of a chunk's window labels and its preictal share."""
    frac = float(np.mean(np.asarray(window_preds, np.float32)))
    return int(frac > 0.5), frac


def alarm_sequence(votes) -> list[int]:
    """The 3-of-5 rule over a session's chunk votes: alarm after chunk t
    iff at least 3 of chunks t-4 .. t voted preictal."""
    out = []
    for t in range(len(votes)):
        out.append(int(sum(votes[max(0, t - ALARM_M + 1): t + 1]) >= ALARM_K))
    return out


def alarm_events(alarms) -> list[tuple[str, int]]:
    """("raised" | "cleared", chunk index) at each change of the alarm."""
    events, prev = [], 0
    for t, a in enumerate(alarms):
        if a != prev:
            events.append(("raised" if a else "cleared", t))
        prev = a
    return events


# ---------------------------------------------------------------------------
# Training: the MapReduce fit
# ---------------------------------------------------------------------------

class FitConfig(NamedTuple):
    shards: int = 4
    trees_per_shard: int = 3
    subsets: int = 3
    depth: int = 6
    bins: int = 32
    classes: int = 2
    bootstrap: float = 0.75
    min_samples: int = 2


def _rotation(key, x, cfg: FitConfig, num: Numerics):
    """One tree's (F, F) rotation: features permuted, cut into ``subsets``
    blocks, PCA of each block on a bootstrap of the rows (all components,
    descending, each with its largest-magnitude entry positive), laid
    block-diagonally and permuted back."""
    n, f = x.shape
    m = f // cfg.subsets
    perm_key, boot_key = jax.random.split(key)
    perm = jax.random.permutation(perm_key, f)
    rot = jnp.zeros((f, f), jnp.float32)
    for b, bkey in enumerate(jax.random.split(boot_key, cfg.subsets)):
        cols = perm[b * m:(b + 1) * m]
        xb = x[:, cols]
        keep = (jax.random.uniform(bkey, (n,)) < cfg.bootstrap).astype(
            jnp.float32)
        count = jnp.maximum(jnp.sum(keep), 2.0)
        mean = jnp.sum(xb * keep[:, None], 0) / count
        xc = (xb - mean) * keep[:, None]
        cov = num.q(num.einsum("nf,ng->fg", xc, xc)
                    / (count - 1.0))
        vals, vecs = jnp.linalg.eigh(cov)
        vecs = vecs[:, jnp.argsort(-vals)]
        big = vecs[jnp.argmax(jnp.abs(vecs), axis=0), jnp.arange(m)]
        vecs = vecs * jnp.where(big < 0, -1.0, 1.0)[None, :]
        rot = rot.at[cols[:, None], cols[None, :]].set(num.q(vecs))
    return rot


def _gini_gain(left, parent):
    """Minus the weighted Gini impurity of the two children (higher is
    better). left, parent: (..., C) class weights."""
    right = parent - left
    n_l, n_r = jnp.sum(left, -1), jnp.sum(right, -1)

    def impurity(h, cnt):
        p = h / jnp.maximum(cnt[..., None], 1e-12)
        return 1.0 - jnp.sum(p * p, -1)

    return -(n_l * impurity(left, n_l) + n_r * impurity(right, n_r)) / (
        jnp.maximum(n_l + n_r, 1e-12))


def _grow(codes, y, w, cfg: FitConfig):
    """One depth-``depth`` tree on bin codes (N, F): at each level every
    node takes the (feature, bin) of largest Gini gain (first in
    feature-major order on ties), where a split sends bins <= b left,
    needs weight on both sides, a node of at least ``min_samples``
    weight and an impure node. Returns (feature, bin, leaf) heap arrays;
    a node that does not split has feature -1 and bin = bins."""
    n, f = codes.shape
    leaves = 2 ** cfg.depth
    feature = jnp.full((leaves,), -1, jnp.int32)
    split_bin = jnp.full((leaves,), cfg.bins, jnp.int32)
    node = jnp.ones((n,), jnp.int32)
    onehot = jax.nn.one_hot(y, cfg.classes) * w[:, None]        # (N, C)
    for level in range(cfg.depth):
        width = 2 ** level
        local = node - width
        hist = jnp.zeros((width, f, cfg.bins, cfg.classes), jnp.float32)
        hist = hist.at[local[:, None], jnp.arange(f)[None, :], codes].add(
            onehot[:, None, :])
        parent = jnp.sum(hist[:, 0], axis=1)                      # (W, C)
        left = jnp.cumsum(hist, axis=2)
        gain = _gini_gain(left, parent[:, None, None, :])         # (W, F, B)
        n_left = jnp.sum(left, -1)
        n_all = jnp.sum(parent, -1)[:, None, None]
        ok = (n_left > 0) & (n_all - n_left > 0)
        ok = ok.at[:, :, -1].set(False)
        gain = jnp.where(ok, gain, -jnp.inf).reshape(width, -1)
        best = jnp.argmax(gain, axis=1)
        best_gain = jnp.max(gain, axis=1)
        node_n = jnp.sum(parent, -1)
        p = parent / jnp.maximum(node_n[:, None], 1e-12)
        split = ((node_n >= cfg.min_samples) & jnp.isfinite(best_gain)
                 & (1.0 - jnp.sum(p * p, -1) > 1e-9))
        feat_l = jnp.where(split, best // cfg.bins, -1).astype(jnp.int32)
        bin_l = jnp.where(split, best % cfg.bins, cfg.bins).astype(jnp.int32)
        feature = feature.at[width + jnp.arange(width)].set(feat_l)
        split_bin = split_bin.at[width + jnp.arange(width)].set(bin_l)
        f_at, b_at = feat_l[local], bin_l[local]
        code = jnp.take_along_axis(codes, jnp.maximum(f_at, 0)[:, None], 1)[:, 0]
        node = 2 * node + (code > b_at).astype(jnp.int32)
    leaf_w = jnp.zeros((leaves, cfg.classes), jnp.float32).at[
        node - leaves].add(onehot)
    prior = jnp.sum(leaf_w, 0)
    prior = prior / jnp.maximum(jnp.sum(prior), 1e-12)
    leaf = (leaf_w + 1e-3 * prior[None, :]) / (
        jnp.sum(leaf_w, -1, keepdims=True) + 1e-3)
    return feature, split_bin, leaf


def _fit_tree(key, x, y, cfg: FitConfig, num: Numerics):
    rot_key, boot_key = jax.random.split(key)
    rot = _rotation(rot_key, x, cfg, num)
    xr = num.q(num.einsum("nf,fg->ng", x, rot))
    w = (jax.random.uniform(boot_key, (x.shape[0],)) < cfg.bootstrap).astype(
        jnp.float32)
    qs = jnp.linspace(0.0, 1.0, cfg.bins + 1)[1:-1]
    edges = jnp.quantile(xr, qs, axis=0).T                     # (F, bins-1)
    codes = jnp.sum(xr[:, :, None] > edges[None], axis=-1).astype(jnp.int32)
    feature, split_bin, leaf = _grow(codes, y, w, cfg)
    live = (feature >= 0) & (split_bin < cfg.bins - 1)
    thr = edges[jnp.maximum(feature, 0), jnp.minimum(split_bin, cfg.bins - 2)]
    return Forest(rot, feature, jnp.where(live, thr, jnp.inf), leaf)


@functools.partial(jax.jit, static_argnames=("cfg", "num"))
def fit(key, feats, labels, cfg: FitConfig, num: Numerics = EXACT):
    """The MapReduce fit from feature rows: (N, F) features of a training
    set whose rows are split into ``shards`` contiguous shards, labels
    (N,). Returns (union Forest, global mean, global std)."""
    feats = num.q(feats)
    mean, std = moments(feats)
    x = num.q(normalize(feats, mean, std))
    n = x.shape[0] // cfg.shards
    trees = []
    for s in range(cfg.shards):
        keys = jax.random.split(jax.random.fold_in(key, s), cfg.trees_per_shard)
        xs, ys = x[s * n:(s + 1) * n], labels[s * n:(s + 1) * n]
        for k in keys:
            trees.append(_fit_tree(k, xs, ys, cfg, num))
    forest = Forest(*(jnp.stack(parts) for parts in zip(*trees)))
    return forest, mean, std


class Served(NamedTuple):
    """A forest as a serving cell scores with it: what the reference
    walks, the same trees in binned form, and the z-score statistics."""

    forest: Forest
    split_bin: jax.Array           # (T, L) bin of each split, bins = none
    edges: jax.Array               # (T, F, bins - 1) quantile edges
    mean: jax.Array                # (F,)
    std: jax.Array


@functools.partial(jax.jit, static_argnames=("cfg",))
def served_forest(key, feats, labels, cfg: FitConfig) -> Served:
    """The forest a serving cell scores with, grown from (N, F) features
    and (N,) labels: ``cfg.trees_per_shard`` trees with ``fit``'s
    rotations and quantile bins, whose split features are drawn at random
    and whose split bins are drawn between the 12th and the 88th
    percentile of the rotated feature, so that thresholds sit where
    windows are dense and a change in how the features round shows in
    the labels. Each leaf holds the class shares of the training windows
    that reach it."""
    mean, std = moments(feats)
    x = normalize(feats, mean, std)
    n, f = x.shape
    leaves = 2 ** cfg.depth
    qs = jnp.linspace(0.0, 1.0, cfg.bins + 1)[1:-1]
    onehot = jax.nn.one_hot(labels, cfg.classes)

    def tree(k):
        k_rot, k_feat, k_bin = jax.random.split(k, 3)
        rot = _rotation(k_rot, x, cfg, EXACT)
        xr = EXACT.einsum("nf,fg->ng", x, rot)
        edges = jnp.quantile(xr, qs, axis=0).T                 # (F, bins-1)
        feature = jax.random.randint(k_feat, (leaves,), 0, f).at[0].set(-1)
        split_bin = jax.random.randint(
            k_bin, (leaves,), cfg.bins // 8, cfg.bins - cfg.bins // 8
        ).at[0].set(cfg.bins)
        thr = jnp.where(feature >= 0,
                        edges[jnp.maximum(feature, 0),
                              jnp.minimum(split_bin, cfg.bins - 2)], jnp.inf)
        node = jnp.ones((n,), jnp.int32)
        for _ in range(cfg.depth):
            v = jnp.take_along_axis(xr, feature[node][:, None], 1)[:, 0]
            node = 2 * node + (v > thr[node]).astype(jnp.int32)
        counts = jnp.zeros((leaves, cfg.classes)).at[node - leaves].add(onehot)
        prior = jnp.sum(counts, 0) / n
        leaf = (counts + 1e-3 * prior) / (jnp.sum(counts, -1, keepdims=True)
                                          + 1e-3)
        return Forest(rot, feature, thr, leaf), split_bin, edges

    forest, split_bin, edges = jax.vmap(tree)(
        jax.random.split(key, cfg.trees_per_shard))
    return Served(forest, split_bin, edges, mean, std)
