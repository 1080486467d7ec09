"""MSPCA's Gram-side bands: a band with fewer samples than variables
(n < P) is decomposed through its n x n Gram matrix instead of its P x P
covariance (``pca.fit_gram_T`` / ``reconstruct_gram_T``, under the
``dual`` scope). With xc = U S Vᵀ the projection Uₖ Uₖᵀ xc equals
xc Vₖ Vₖᵀ, so both sides reconstruct the same band.

At the paper's shape (60 windows x 3 channels x 2048 samples, level 5)
the bands are n = 1024, 512, 256, 128, 64, 64 over P = 180 variables:
D4, D5 and A5 take the Gram side. With a 4-window halo P = 192 and the
same three do.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

from repro.core import pca
from repro.signal import eeg_data, mspca, wavelet

KEEP = 30        # denoise's default component count
GRAM_BANDS = (3, 4, 5)  # D4 (n=128), D5 (n=64), A5 (n=64) at level 5
HALOS = (0, 4)   # P = 180 and P = 192


def _lowrank_stream(key, n_windows, rank=KEEP, noise=0.01):
    """(n_windows, 3, 2048) windows of ``rank`` white latent sources with
    scales from 1 to 1/2 through an orthonormal mixing, plus white noise:
    every band's top ``rank`` subspace is well separated (s30/s31 >= 30)
    and unit-RMS, so float32 noise is the only difference left."""
    k_src, k_mix, k_noise = jax.random.split(key, 3)
    p = n_windows * eeg_data.N_CHANNELS
    n = eeg_data.WINDOW
    src = jax.random.normal(k_src, (n, rank))
    mix = jnp.linalg.qr(jax.random.normal(k_mix, (p, rank)))[0].T
    scales = jnp.linspace(1.0, 0.5, rank)
    scales = scales / jnp.sqrt(jnp.mean(scales**2))
    mat = (src * scales) @ mix * jnp.sqrt(p / rank)
    mat = mat + noise * jax.random.normal(k_noise, (n, p))
    return mat.reshape(n, n_windows, eeg_data.N_CHANNELS).transpose(1, 2, 0)


def _bands(windows):
    """denoise's per-scale (P, n) bands of a (W, C, N) window stack."""
    w, c, n = windows.shape
    mat = windows.transpose(2, 0, 1).reshape(n, w * c)
    return wavelet.dwt((mat - jnp.mean(mat, axis=0)).T, 5, "db4")


@pytest.fixture(scope="module")
def lowrank():
    """64 windows: the last 60 are the chunk, the first 4 its halo."""
    return _lowrank_stream(jax.random.PRNGKey(7), 64)


@pytest.fixture(scope="module")
def eeg_chunk():
    return eeg_data.generate_windows(
        jax.random.PRNGKey(0), jnp.int32(0), eeg_data.INTERICTAL, 64
    )


def _svd_projection(cT: np.ndarray, k: int) -> np.ndarray:
    c64 = np.asarray(cT, np.float64)
    mean = c64.mean(axis=1, keepdims=True)
    u, _, _ = np.linalg.svd(c64 - mean, full_matrices=False)
    uk = u[:, :k]
    return uk @ (uk.T @ (c64 - mean)) + mean


def _rel_err(rec, ref) -> float:
    return float(np.linalg.norm(np.asarray(rec, np.float64) - ref)
                 / np.linalg.norm(ref))


@pytest.mark.parametrize("band", GRAM_BANDS)
@pytest.mark.parametrize("h", HALOS)
def test_gram_side_band_matches_float64_projector(lowrank, h, band):
    cT = _bands(lowrank[4 - h:])[band]
    p, n = cT.shape
    assert n < p
    ref = _svd_projection(cT, KEEP)
    primal = pca.reconstruct_T(pca.fit_T(cT), cT, KEEP)
    st = pca.fit_gram_T(cT)
    gram = pca.reconstruct_gram_T(st, cT, KEEP)
    err_primal, err_gram = _rel_err(primal, ref), _rel_err(gram, ref)
    assert err_gram <= 2 * err_primal + 1e-6, (err_gram, err_primal)
    # A traced count (the Kaiser path) masks instead of slicing.
    masked = pca.reconstruct_gram_T(st, cT, jnp.int32(KEEP))
    np.testing.assert_allclose(np.asarray(masked), np.asarray(gram),
                               atol=1e-5)
    # The dispatching band reconstruction takes the Gram side here.
    np.testing.assert_array_equal(
        np.asarray(mspca._pca_reconstruct_T(cT, KEEP)), np.asarray(gram)
    )


@pytest.mark.parametrize("h", HALOS)
def test_denoise_windows_matches_reference_kernels(lowrank, h):
    """The shape-chosen path against the pinned primal, full-width masked
    reference, at ``test_reference_kernels_path_is_equal_up_to_fp_order``'s
    tolerance."""
    halo = lowrank[4 - h:4] if h else None
    fast = mspca.denoise_windows(lowrank[4:], halo=halo)
    ref = mspca.denoise_windows(
        lowrank[4:], halo=halo, reference_kernels=True
    )
    np.testing.assert_allclose(
        np.asarray(fast), np.asarray(ref), atol=1e-5, rtol=1e-5
    )


@pytest.mark.parametrize("keep", [7, 8, 12])
def test_gram_side_keep_at_least_rank_is_identity(keep):
    """keep >= n - 1 keeps the whole centred band (its rank is n - 1):
    both sides give the band back."""
    cT = jax.random.normal(jax.random.PRNGKey(11), (20, 8)) * 2.0 + 0.5
    st = pca.fit_gram_T(cT)
    gram = pca.reconstruct_gram_T(st, cT, keep)
    primal = pca.reconstruct_T(pca.fit_T(cT), cT, keep)
    np.testing.assert_allclose(np.asarray(gram), np.asarray(cT), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gram), np.asarray(primal),
                               atol=1e-5)
    # A traced count masks instead of slicing: the same reconstruction.
    masked = pca.reconstruct_gram_T(st, cT, jnp.int32(keep))
    np.testing.assert_allclose(np.asarray(masked), np.asarray(gram),
                               atol=1e-5)


@pytest.mark.parametrize("h", HALOS)
def test_gram_side_kaiser_count_equals_primal(eeg_chunk, h):
    """The mean eigenvalue stays trace / P (the P - n zero eigenvalues
    included), so Kaiser keeps the same count on both sides."""
    for band in GRAM_BANDS:
        cT = _bands(eeg_chunk[4 - h:])[band]
        p = cT.shape[0]
        primal = int(pca.kaiser_rule(pca.fit_T(cT)))
        st = pca.fit_gram_T(cT)
        gram = int(pca.kaiser_rule(st, n_features=p))
        assert gram == primal, (band, gram, primal)
        # MSPCA's Kaiser band keeps that count.
        np.testing.assert_allclose(
            np.asarray(mspca._pca_reconstruct_T(cT, "kaiser")),
            np.asarray(pca.reconstruct_gram_T(st, cT, jnp.int32(primal))),
            atol=1e-5, rtol=1e-6,
        )


def _eigh_operands(jaxpr, stack: str = "") -> list[tuple[int, str]]:
    """(width, scope path) of every ``eigh`` in ``jaxpr``, nested
    jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        path = "/".join(s for s in (stack, str(eqn.source_info.name_stack))
                        if s)
        if eqn.primitive.name == "eigh":
            out.append((eqn.invars[0].aval.shape[-1], path))
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                if isinstance(sub, jex_core.ClosedJaxpr):
                    out += _eigh_operands(sub.jaxpr, path)
                elif isinstance(sub, jex_core.Jaxpr):
                    out += _eigh_operands(sub, path)
    return out


@pytest.mark.parametrize("h,reference,widths", [
    (0, False, [180, 180, 180, 128, 64, 64]),
    (4, False, [192, 192, 192, 128, 64, 64]),
    (0, True, [180] * 6),
])
def test_eigh_widths_follow_band_shape(h, reference, widths):
    windows = jax.ShapeDtypeStruct((60, 3, 2048), jnp.float32)
    halo = jax.ShapeDtypeStruct((h, 3, 2048), jnp.float32) if h else None
    jaxpr = jax.make_jaxpr(
        lambda w, hw: mspca.denoise_windows(
            w, halo=hw, reference_kernels=reference)
    )(windows, halo)
    eighs = _eigh_operands(jaxpr.jaxpr)
    assert sorted(w for w, _ in eighs) == sorted(widths)
    for width, path in eighs:
        scopes = path.split("/")
        gram_side = not reference and width < (60 + h) * 3
        assert ("dual" in scopes) == gram_side, (width, path)
        assert "eigh" in scopes, path
