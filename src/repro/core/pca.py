"""Principal Component Analysis in JAX.

Used three ways in this framework (mirroring the paper):
  * MSPCA denoising  -- PCA across channels at each wavelet scale (eq. 1).
  * Rotation Forest  -- per-feature-subset PCA rotations (Sec. 2.3.1).
  * General utility  -- whitening / dimensionality reduction.

The covariance (Gram) computation can be routed through the Pallas
``kernels/gram`` tiled kernel for large feature counts; the default is a
plain ``jnp`` einsum which XLA maps to the MXU anyway.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class PCAState(NamedTuple):
    """Fitted PCA parameters.

    components : (F, F) columns are principal directions, sorted by
                 decreasing eigenvalue.
    mean       : (F,) feature means.
    variances  : (F,) eigenvalues (explained variance per component).
    """

    components: jax.Array
    mean: jax.Array
    variances: jax.Array


def _sym_cov(xc: jax.Array, use_kernel: bool = False) -> jax.Array:
    """(F, F) covariance of centered data ``xc`` of shape (N, F)."""
    n = xc.shape[0]
    if use_kernel:
        # Lazy import: the Pallas kernel is optional on the fit path.
        from repro.kernels.gram import ops as gram_ops

        g = gram_ops.gram(xc)
    else:
        g = jnp.einsum("nf,ng->fg", xc, xc, preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
    return g / jnp.maximum(n - 1, 1)


def _eig_sorted(cov: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Descending-eigenvalue eigendecomposition with the framework's
    deterministic sign convention (largest-|.| entry of each component
    positive, so fits are reproducible across backends)."""
    with jax.named_scope("eigh"):
        # eigh returns ascending eigenvalues; flip to descending.
        evals, evecs = jnp.linalg.eigh(cov)
        order = jnp.argsort(-evals)
        evals = jnp.take(evals, order)
        evecs = jnp.take(evecs, order, axis=1)
        signs = jnp.sign(evecs[jnp.argmax(jnp.abs(evecs), axis=0),
                               jnp.arange(evecs.shape[1])])
        evecs = evecs * jnp.where(signs == 0, 1.0, signs)[None, :]
    return evals, evecs


def fit(x: jax.Array, use_kernel: bool = False) -> PCAState:
    """Fit PCA on ``x`` of shape (N, F). All components are kept --
    Rotation Forest requires the full rotation (Sec. 2.3.1: "All principal
    components are kept because of preserving the variability data
    information")."""
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=0)
    xc = x - mean
    cov = _sym_cov(xc, use_kernel=use_kernel)
    evals, evecs = _eig_sorted(cov)
    return PCAState(components=evecs, mean=mean, variances=jnp.maximum(evals, 0.0))


def fit_T(xT: jax.Array) -> PCAState:
    """Fit PCA on ``xT`` of shape (F, N) -- the TRANSPOSED layout, where
    columns are samples. Same result as ``fit(xT.T)`` up to float32
    reduction order, without materializing the transpose: MSPCA's
    per-scale loop holds wavelet coefficients variable-major, so
    fitting in that layout skips two full-matrix transposes per scale
    (a measurable share of the denoise stage on CPU)."""
    xT = xT.astype(jnp.float32)
    mean = jnp.mean(xT, axis=1)
    xc = xT - mean[:, None]
    cov = jnp.einsum(
        "pn,qn->pq", xc, xc, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ) / jnp.maximum(xT.shape[1] - 1, 1)
    evals, evecs = _eig_sorted(cov)
    return PCAState(components=evecs, mean=mean, variances=jnp.maximum(evals, 0.0))


def fit_gram_T(xT: jax.Array) -> PCAState:
    """Gram-side twin of ``fit_T`` for ``xT`` of shape (F, N) with fewer
    samples than features (N < F). The centred data has rank at most
    N - 1, so the (F, F) covariance and the (N, N) Gram matrix
    ``xcᵀ xc / (N - 1)`` share their nonzero eigenvalues, and only the
    smaller one is decomposed. ``components`` is then (N, N): its
    columns are directions in SAMPLE space (the right singular vectors
    of the centred data), which ``reconstruct_gram_T`` pairs with;
    ``variances`` holds the N leading eigenvalues (the F - N left out
    are zero)."""
    xT = xT.astype(jnp.float32)
    mean = jnp.mean(xT, axis=1)
    xc = xT - mean[:, None]
    gram = jnp.einsum(
        "pn,pm->nm", xc, xc, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ) / jnp.maximum(xT.shape[1] - 1, 1)
    evals, evecs = _eig_sorted(gram)
    return PCAState(components=evecs, mean=mean, variances=jnp.maximum(evals, 0.0))


def transform(state: PCAState, x: jax.Array, n_components: int | None = None) -> jax.Array:
    comps = state.components if n_components is None else state.components[:, :n_components]
    return (x - state.mean) @ comps


def inverse_transform(state: PCAState, scores: jax.Array) -> jax.Array:
    k = scores.shape[-1]
    return scores @ state.components[:, :k].T + state.mean


def reconstruct(
    state: PCAState,
    x: jax.Array,
    keep: jax.Array | int,
    *,
    masked: bool | None = None,
) -> jax.Array:
    """Project onto the leading components and back (used by MSPCA).

    ``keep`` may be a traced integer -- components are then MASKED
    instead of sliced so the function stays jittable with a dynamic
    component count. A static Python int ``keep`` takes the sliced
    fast path instead: both GEMMs shrink from (N, F) @ (F, F) to
    (N, F) @ (F, k), which only drops terms the mask zeroed exactly
    (equal up to float32 summation grouping). ``masked=True`` forces
    the historical full-width masked form -- the pre-megabatch
    formulation, pinned by the serving bench's serial-replay leg.
    """
    if masked is None:
        masked = not isinstance(keep, int)
    if not masked:
        comps = state.components[:, : min(int(keep), state.components.shape[1])]
        scores = (x - state.mean) @ comps  # (N, k)
        return scores @ comps.T + state.mean
    scores = (x - state.mean) @ state.components  # (N, F)
    f = state.components.shape[1]
    mask = (jnp.arange(f) < keep).astype(scores.dtype)
    return (scores * mask) @ state.components.T + state.mean


def reconstruct_T(
    state: PCAState, xT: jax.Array, keep: jax.Array | int
) -> jax.Array:
    """Transposed-layout ``reconstruct``: (F, N) -> (F, N), columns are
    samples (pairs with ``fit_T``). A static Python int ``keep`` takes
    the sliced fast path; a traced count masks the score rows instead.
    """
    xc = xT - state.mean[:, None]
    if isinstance(keep, int):
        comps = state.components[:, : min(keep, state.components.shape[1])]
        return comps @ (comps.T @ xc) + state.mean[:, None]
    scores = state.components.T @ xc  # (F, N)
    mask = (jnp.arange(scores.shape[0]) < keep).astype(scores.dtype)
    return state.components @ (scores * mask[:, None]) + state.mean[:, None]


def reconstruct_gram_T(
    state: PCAState, xT: jax.Array, keep: jax.Array | int
) -> jax.Array:
    """``reconstruct_T`` for a ``fit_gram_T`` state: (F, N) -> (F, N).
    With xc = U S Vᵀ, the projection onto the leading k principal
    directions is Uₖ Uₖᵀ xc = xc Vₖ Vₖᵀ, so it runs on the sample side.
    A static Python int ``keep`` slices Vₖ; a traced count masks the
    score columns instead."""
    xc = xT - state.mean[:, None]
    if isinstance(keep, int):
        v = state.components[:, : min(keep, state.components.shape[1])]
        return (xc @ v) @ v.T + state.mean[:, None]
    scores = xc @ state.components  # (F, N)
    mask = (jnp.arange(scores.shape[1]) < keep).astype(scores.dtype)
    return (scores * mask[None, :]) @ state.components.T + state.mean[:, None]


def n_components_for_variance(state: PCAState, frac: float = 0.95) -> jax.Array:
    """Smallest k capturing ``frac`` of total variance (traceable)."""
    total = jnp.sum(state.variances)
    cum = jnp.cumsum(state.variances)
    return jnp.sum(cum < frac * jnp.maximum(total, 1e-12)) + 1


def kaiser_rule(state: PCAState, n_features: int | None = None) -> jax.Array:
    """Number of components with eigenvalue above the mean eigenvalue --
    the classical selection rule used by MSPCA implementations.

    ``n_features``: the width the mean is taken over, for a
    ``fit_gram_T`` state, whose F - N zero eigenvalues are not stored:
    the mean stays trace / F, as the covariance side computes it."""
    if n_features is None:
        mean = jnp.mean(state.variances)
    else:
        mean = jnp.sum(state.variances) / n_features
    return jnp.maximum(jnp.sum(state.variances > mean), 1)
