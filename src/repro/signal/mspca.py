"""Multiscale PCA denoising (Bakshi 1998; paper Sec. 2.1).

Input: a data matrix X (N samples x P variables). The paper's variables
are channel-window columns of the 2048 x 180 matrix (8 minutes of 8-second
windows x 3 channels).

Algorithm:
  1. DWT each column to ``level`` (db4 by default) -- wavelet.dwt is
     applied along the sample axis.
  2. At every scale (each detail D_j and the final approximation A_L),
     run PCA across the P variables and reconstruct keeping only the
     components selected by the Kaiser rule (eigenvalue > mean eigenvalue).
  3. Optionally hard-threshold detail coefficients (universal threshold
     sigma * sqrt(2 log N), sigma from the finest-scale MAD) -- Bakshi's
     wavelet-thresholding step.
  4. Inverse DWT; a final full-scale PCA reconstruction (Kaiser rule).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import pca
from repro.signal import wavelet


def _pca_reconstruct(mat: jax.Array, keep, reference: bool = False) -> jax.Array:
    """PCA across columns of ``mat`` (N, P); keep components; reconstruct.

    ``keep``: "kaiser" (eigenvalue > mean -- Bakshi's rule; content-
    dependent) or an int (fixed count -- keeps the train/test transform
    comparable, which matters for downstream classification; see
    EXPERIMENTS.md ablation). A fixed count takes
    ``pca.reconstruct``'s sliced fast path; ``reference=True`` pins the
    historical full-width masked form instead (the pre-megabatch
    serial-replay leg of the serving bench)."""
    st = pca.fit(mat)
    if keep == "kaiser":
        k = jnp.minimum(pca.kaiser_rule(st), mat.shape[1])
        return pca.reconstruct(st, mat, k)
    return pca.reconstruct(st, mat, int(keep), masked=reference)


def _pca_reconstruct_T(cT: jax.Array, keep) -> jax.Array:
    """Variable-major twin of ``_pca_reconstruct``: ``cT`` is (P, n) --
    exactly the layout ``wavelet.dwt`` hands back per scale -- so the
    fit and the projection run without the two full-matrix transposes
    the sample-major form pays per scale.

    The side of the eigendecomposition follows the band's static shape:
    a band with fewer samples than variables (n < P: D4, D5 and A5 of
    the paper's 2048 x 180 matrix at level 5) decomposes the n x n Gram
    matrix instead of the P x P covariance (``pca.fit_gram_T``), under
    the ``dual`` scope; the projection onto the leading components is
    the same. Every other band takes the covariance side."""
    p, n = cT.shape
    if n < p:
        with jax.named_scope("dual"):
            st = pca.fit_gram_T(cT)
            if keep == "kaiser":
                k = pca.kaiser_rule(st, n_features=p)
                return pca.reconstruct_gram_T(st, cT, k)
            return pca.reconstruct_gram_T(st, cT, int(keep))
    st = pca.fit_T(cT)
    if keep == "kaiser":
        k = jnp.minimum(pca.kaiser_rule(st), cT.shape[0])
        return pca.reconstruct_T(st, cT, k)
    return pca.reconstruct_T(st, cT, int(keep))


def _hard_threshold(d: jax.Array, sigma: jax.Array, n: int) -> jax.Array:
    """Universal threshold over ``n`` samples (layout-agnostic: ``d`` may
    be sample-major or variable-major, the rule only needs ``n``)."""
    thr = sigma * jnp.sqrt(2.0 * jnp.log(jnp.asarray(n, jnp.float32)))
    return jnp.where(jnp.abs(d) > thr, d, 0.0)


@functools.partial(
    jax.jit,
    static_argnames=(
        "level", "wavelet_name", "threshold", "keep", "final_pca",
        "reference_kernels",
    ),
)
def denoise(
    x: jax.Array,
    level: int = 5,
    wavelet_name: str = "db4",
    threshold: bool = False,
    keep: int | str = 30,
    final_pca: bool = False,
    reference_kernels: bool = False,
) -> jax.Array:
    """MSPCA-denoise X (N, P) -> (N, P).

    Defaults (fixed ``keep``, no hard threshold, no final full-scale pass)
    are the *classification-stable* variant selected by the ablation in
    EXPERIMENTS.md: Bakshi's original Kaiser rule + universal threshold
    (``threshold=True, keep="kaiser", final_pca=True``) denoises more
    aggressively but makes the reconstruction content-dependent, which
    hurts downstream train/test feature consistency.

    ``reference_kernels=True`` runs the pre-megabatch scoring math end
    to end: gather + matmul wavelet analysis, scatter-add synthesis
    (``wavelet.synthesis_step_reference``), and the full-width masked
    PCA reconstruction. The default pad + static-slice polyphase
    kernels and sliced reconstruction are equal up to float32 summation
    order; the serving bench's serial-replay leg pins the reference
    path so the megabatch before/after stays measurable.
    """
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=0)
    xc = x - mean

    # DWT along samples: transform each column. wavelet ops act on the last
    # axis, so work with (P, N).
    coeffs = wavelet.dwt(
        xc.T, level, wavelet_name, reference=reference_kernels
    )  # list of (P, N/2^j)

    # Noise scale from the finest detail (median absolute deviation).
    d1 = coeffs[0]
    sigma = jnp.median(jnp.abs(d1)) / 0.6745

    new_coeffs = []
    for j, c in enumerate(coeffs):  # c is (P, n_j), variable-major
        if reference_kernels:
            # Historical per-scale shape: transpose to (n_j, P), fit
            # sample-major, full-width masked reconstruct, transpose back.
            rec = _pca_reconstruct(c.T, keep, reference=True).T
        else:
            rec = _pca_reconstruct_T(c, keep)
        if threshold and j < len(coeffs) - 1:  # details only, not A_L
            rec = _hard_threshold(rec, sigma, n=c.shape[1])
        new_coeffs.append(rec)

    xd = wavelet.idwt(
        new_coeffs, wavelet_name, reference=reference_kernels
    ).T  # (N, P)
    if final_pca:  # Bakshi step 4
        xd = _pca_reconstruct(xd, keep, reference=reference_kernels)
    return xd + mean


@functools.partial(
    jax.jit, static_argnames=("level", "wavelet_name", "reference_kernels")
)
def denoise_windows(
    windows: jax.Array,
    level: int = 5,
    wavelet_name: str = "db4",
    halo: jax.Array | None = None,
    reference_kernels: bool = False,
) -> jax.Array:
    """(W, C, N) raw windows -> (W, C, N) denoised: one 8-minute matrix.

    The paper's chunk-shaped entry point (Sec. 2.6): the W*C
    channel-windows become the columns of an N x (W*C) data matrix
    (2048 x 180 when W == 60, C == 3), ``denoise`` runs on that layout,
    and the result is folded back to windows. This is the SINGLE
    implementation both scoring paths share -- ``signal.frontend``'s
    streaming transition and (through it) the batch
    ``pipeline.process_windows`` -- so the matrix layout cannot drift
    between them.

    ``halo``: optional (H, C, N) raw windows that immediately PRECEDE
    this chunk in the stream (the carried ``FrontendState.boundary``).
    They are prepended as extra columns -- the matrix becomes
    N x ((H+W)*C) -- so the per-scale PCA bases are estimated with
    cross-seam context, then the halo columns are discarded: only the
    chunk's own W windows come back. ``halo=None`` (or H == 0) is
    byte-for-byte the historical independent-chunk path.
    """
    w, c, n = windows.shape
    if halo is not None and halo.shape[0] == 0:
        halo = None
    if halo is None:
        mat = windows.transpose(2, 0, 1).reshape(n, w * c)
        den = denoise(
            mat, level=level, wavelet_name=wavelet_name,
            reference_kernels=reference_kernels,
        )
        return den.reshape(n, w, c).transpose(1, 2, 0)
    h = halo.shape[0]
    ext = jnp.concatenate([halo.astype(windows.dtype), windows])
    mat = ext.transpose(2, 0, 1).reshape(n, (h + w) * c)
    den = denoise(
        mat, level=level, wavelet_name=wavelet_name,
        reference_kernels=reference_kernels,
    )
    return den.reshape(n, h + w, c).transpose(1, 2, 0)[h:]


def denoise_stream_chunked(
    stream: jax.Array,
    overlap: int,
    per: int = 60,
    level: int = 5,
    wavelet_name: str = "db4",
) -> jax.Array:
    """Reference chunked denoise of a chunk-aligned (K*per, C, N) stream:
    one ``denoise_windows`` call per chunk, carrying the previous chunk's
    last ``overlap`` RAW windows as the next chunk's halo (zeros before
    the first chunk). This is the longhand formulation of what
    ``frontend.frontend_step`` computes per step -- the seam-oracle
    harness of ``tests/test_overlap_mspca.py`` and the CI-gated
    ``bench_mspca_denoise`` seam ablation both measure THIS function, so
    the gate and the test oracle cannot drift apart."""
    k, rem = divmod(stream.shape[0], per)
    if rem:
        raise ValueError(
            f"stream of {stream.shape[0]} windows is not {per}-aligned"
        )
    chunks = stream.reshape(k, per, *stream.shape[1:])
    outs = []
    # Zero halo for the first chunk, hoisted out of the loop (one device
    # constant for the whole stream, not one per chunk).
    halo = (
        jnp.zeros((overlap, *stream.shape[1:]), jnp.float32)
        if overlap else None
    )
    for i in range(k):
        c = chunks[i]
        if overlap:
            outs.append(denoise_windows(
                c, level=level, wavelet_name=wavelet_name, halo=halo
            ))
            halo = c[per - overlap :].astype(jnp.float32)
        else:
            outs.append(denoise_windows(
                c, level=level, wavelet_name=wavelet_name
            ))
    return jnp.concatenate(outs)


def worst_seam_snr_db(
    reference: jax.Array,
    denoised: jax.Array,
    per: int = 60,
    seam_windows: int = 8,
) -> float:
    """Worst per-seam ``snr_db`` of chunked ``denoised`` output against
    the full-recording ``reference`` (the stream denoised as ONE
    matrix). Each seam is scored over its head region -- the
    ``seam_windows`` windows AFTER a chunk boundary, the windows whose
    preceding context the chunking cut. Higher is better; the
    stream-start chunk has no seam and is excluded."""
    n_chunks = reference.shape[0] // per
    return min(
        float(snr_db(
            reference[k * per : k * per + seam_windows],
            denoised[k * per : k * per + seam_windows],
        ))
        for k in range(1, n_chunks)
    )


def snr_db(clean: jax.Array, noisy: jax.Array) -> jax.Array:
    """SNR of ``noisy`` against ``clean`` in dB (the seam-error metric of
    ``tests/test_overlap_mspca.py`` / ``benchmarks/bench_mspca_denoise``).
    Both powers are floored so a zero-power ``clean`` input yields a
    finite 0 dB instead of ``log10(0) = -inf``."""
    err = noisy - clean
    return 10.0 * jnp.log10(
        jnp.maximum(jnp.sum(clean**2), 1e-12)
        / jnp.maximum(jnp.sum(err**2), 1e-12)
    )
