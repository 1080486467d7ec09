"""Statistical features from WPD terminal nodes (paper Sec. 2.2 / 2.6).

Following Kevric & Subasi's WPD feature set for EEG: per terminal node we
compute six statistics; the feature vector of an 8-second window is the
concatenation over nodes and channels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.signal import wavelet

FEATURES_PER_NODE = 6


def node_features(coeffs: jax.Array) -> jax.Array:
    """coeffs (..., M) -> (..., 6): [mean|c|, power, std, skew, kurt, entropy]."""
    eps = 1e-8
    mean_abs = jnp.mean(jnp.abs(coeffs), -1)
    power = jnp.mean(coeffs**2, -1)
    mu = jnp.mean(coeffs, -1, keepdims=True)
    cc = coeffs - mu
    var = jnp.mean(cc**2, -1)
    std = jnp.sqrt(var + eps)
    skew = jnp.mean(cc**3, -1) / (std**3 + eps)
    kurt = jnp.mean(cc**4, -1) / (var**2 + eps)
    # Shannon entropy of the normalized energy distribution within the node.
    p = coeffs**2 / (jnp.sum(coeffs**2, -1, keepdims=True) + eps)
    entropy = -jnp.sum(p * jnp.log(p + eps), -1)
    return jnp.stack([mean_abs, power, std, skew, kurt, entropy], axis=-1)


@functools.partial(
    jax.jit,
    static_argnames=("level", "wavelet_name", "use_kernel", "reference_kernels"),
)
def wpd_features(
    windows: jax.Array,
    level: int = 4,
    wavelet_name: str = "db4",
    use_kernel: bool = False,
    reference_kernels: bool = False,
) -> jax.Array:
    """Windows (..., C, N) -> features (..., C * 2**level * 6).

    The per-window feature extraction of Sec. 2.6: WPD to ``level`` and
    six statistics per terminal node, flattened over channels and nodes.
    ``reference_kernels=True`` runs the WPD through the pre-megabatch
    gather + matmul analysis formulation (``wavelet.analysis_step``'s
    ``reference`` path).
    """
    with jax.named_scope("wpd"):
        nodes = wavelet.wpd(
            windows, level, wavelet_name, use_kernel=use_kernel,
            reference=reference_kernels,
        )
        feats = node_features(nodes)  # (..., C, 2**level, 6)
    lead = windows.shape[:-2]
    return feats.reshape(lead + (-1,))


def feature_dim(n_channels: int, level: int = 4) -> int:
    return n_channels * (2**level) * FEATURES_PER_NODE


def normalize(
    feats: jax.Array, mean: jax.Array | None = None, std: jax.Array | None = None
):
    """Z-score features; returns (normed, mean, std) so the training-set
    statistics can be reused at test time (strict train/test separation,
    Sec. 2.6)."""
    if mean is None:
        mean = jnp.mean(feats, axis=0)
        std = jnp.std(feats, axis=0) + 1e-6
    return (feats - mean) / std, mean, std
