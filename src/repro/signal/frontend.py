"""Streaming signal front-end: the scoring path's map phase as a scan.

The paper's deployment (Sec. 2.6) is a *continuous* EEG monitor, but the
original ``pipeline.process_windows`` was a stateless batch function --
every chunk re-derived its denoise context and a backlogged stream had to
re-enter the pipeline once per chunk. This module restructures that stage
into an explicit streaming transition:

  * ``FrontendState``  -- the carried per-stream context: the previous
    chunk's boundary windows (the cross-chunk denoise halo) and the
    running chunk phase.
  * ``frontend_step``  -- the pure transition
    ``(state, chunk_windows) -> (state, features)``: MSPCA-denoise one
    8-minute matrix (``mspca.denoise_windows``, the single chunk-shaped
    entry point) and extract WPD feature rows (``features.wpd_features``).
    With ``cfg.overlap > 0`` the carried boundary windows are prepended
    to the denoise matrix as halo columns (and discarded after), so the
    per-scale PCA bases see cross-seam context instead of a hard edge
    at every chunk boundary.
  * ``scan_stream``    -- ``lax.scan`` of ``frontend_step`` over a
    chunk-aligned stream. ``pipeline.process_windows`` is this scan.
  * ``megabatch_step`` -- the de-serialized batch transition: D backlog
    chunks per stream featurized in ONE flattened (B*D) heavy pass,
    halos assembled from the backlog itself (chunk d's halo is chunk
    d-1's raw tail; only chunk 0 consumes the carried boundary). The
    serving engine's jitted step runs this instead of scanning
    ``frontend_step`` (``serving.api``).
  * ``StreamingFrontend`` -- host-side incremental wrapper: feed raw
    windows in arbitrary split sizes, get feature rows back per
    completed chunk, bit-identical to the one-shot batch path.

The transition stays exact under overlap: the halo is RAW windows (the
previous chunk's tail, carried in ``FrontendState``), never denoised
output, so each step still depends on its predecessor only through that
small payload -- scanning ``frontend_step`` over any chunk-aligned split
of a recording reproduces the one-shot batch features bit-for-bit
(pinned by ``tests/test_frontend.py`` / ``tests/test_overlap_mspca.py``),
and the map phase stays embarrassingly parallel given the halos. With
``cfg.overlap == 0`` the features are byte-identical to the historical
independent-chunk path.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.signal import eeg_data, features, mspca


class FrontendState(NamedTuple):
    """Carried per-stream signal context (one stream; vmap for batches).

    boundary : (H, C, N) float32 -- the last ``H = max(1, overlap)`` raw
               windows of the previous chunk (zeros before the first
               chunk). With ``cfg.overlap > 0`` these are the denoise
               halo the next chunk consumes; with ``overlap == 0`` the
               single boundary window is carried but not consumed (the
               pre-overlap contract, kept so state layout migrations
               stay explicit).
    phase    : () int32 -- chunks processed so far (the running chunk
               phase; the engine's per-slot copy survives slot eviction).
    """

    boundary: jax.Array
    phase: jax.Array


def boundary_width(overlap: int) -> int:
    """Carried boundary windows for an overlap setting (always >= 1)."""
    return max(1, overlap)


def init_state(
    n_channels: int = eeg_data.N_CHANNELS,
    window: int = eeg_data.WINDOW,
    overlap: int = 0,
) -> FrontendState:
    """Zero context: a stream that has not produced a chunk yet."""
    return FrontendState(
        boundary=jnp.zeros(
            (boundary_width(overlap), n_channels, window), jnp.float32
        ),
        phase=jnp.zeros((), jnp.int32),
    )


def init_batch(
    batch: int,
    n_channels: int = eeg_data.N_CHANNELS,
    window: int = eeg_data.WINDOW,
    overlap: int = 0,
) -> FrontendState:
    """(B,)-leading zero states: one per engine slot."""
    return FrontendState(
        boundary=jnp.zeros(
            (batch, boundary_width(overlap), n_channels, window), jnp.float32
        ),
        phase=jnp.zeros((batch,), jnp.int32),
    )


def state_to_arrays(state: FrontendState) -> dict[str, np.ndarray]:
    """One stream's (or a (B,)-leading batch's) carried context as a flat
    numpy dict -- the ``checkpoint.store``-ready serialization every
    frontend persister shares (``StreamingFrontend.state_dict`` and the
    engine snapshot's per-slot/per-session leaves). Pure host reads
    (explicit ``jax.device_get``): serializing never perturbs the
    stream."""
    boundary, phase = jax.device_get((state.boundary, state.phase))
    return {
        "boundary": np.asarray(boundary, np.float32),
        "phase": np.asarray(phase, np.int32),
    }


def state_from_arrays(
    arrays: dict, *, width: int | None = None
) -> FrontendState:
    """Inverse of ``state_to_arrays``; validates the layout up front so a
    checkpoint from a different overlap setting fails loudly instead of
    resuming with a silently wrong halo.

    ``width`` (when given) pins the expected boundary depth --
    ``boundary_width(cfg.overlap)`` of the consuming stream."""
    boundary = np.asarray(arrays["boundary"], np.float32)
    phase = np.asarray(arrays["phase"], np.int32)
    if boundary.ndim not in (3, 4) or phase.ndim != boundary.ndim - 3:
        raise ValueError(
            f"frontend state layout mismatch: boundary ndim "
            f"{boundary.ndim} / phase ndim {phase.ndim} is neither a "
            "single stream ((H, C, N) + ()) nor a batch "
            "((B, H, C, N) + (B,))"
        )
    got_width = boundary.shape[-3]
    if width is not None and got_width != width:
        raise ValueError(
            f"frontend boundary width {got_width} != expected {width} "
            "(= max(1, overlap)): the saved state comes from a different "
            "overlap setting"
        )
    return FrontendState(
        boundary=jax.device_put(boundary), phase=jax.device_put(phase)
    )


def chunk_features(
    chunk_windows: jax.Array, cfg, halo: jax.Array | None = None
) -> jax.Array:
    """(W, C, N) chunk -> (W, F) feature rows: the stateless core of one
    frontend step (denoise the chunk's 8-minute matrices, WPD-featurize
    each window). Both scoring paths -- the scanned stream and the
    engine's stateless ``score_chunks`` -- run THIS function, so they
    cannot drift. ``cfg`` is a static ``pipeline.PipelineConfig``.

    W is usually exactly ``WINDOWS_PER_MATRIX`` (one denoise matrix,
    no padding). Other chunk sizes keep the historical
    ``process_windows`` semantics: the chunk is wrap-padded by cyclic
    tiling to whole ``WINDOWS_PER_MATRIX``-window matrices, so an engine
    configured with a nonstandard ``chunk_windows`` denoises the same
    2048 x 180 matrix shape the training statistics were computed from
    (train/serve consistency) and scores bit-identically to the
    pre-scan engine.

    With ``cfg.overlap > 0``, ``halo`` is the (overlap, C, N) raw
    windows that precede this chunk in the stream (``None`` means a
    stream start: a zero halo, exactly what a fresh session's first
    chunk sees). The halo is prepended to the FIRST denoise matrix as
    extra columns; when the (wrap-padded) chunk spans several matrices,
    each inner matrix takes the raw tail of its predecessor in padded
    order -- the halo is always raw windows, so every matrix's halo is
    known upfront and the denoises stay vmappable. The wrap-pad is
    applied first: the halo touches only the matrix HEAD, never the
    cyclic padding at the tail (pinned by
    ``tests/test_overlap_mspca.py``).
    """
    if cfg.denoise:
        w, c, n = chunk_windows.shape
        per = eeg_data.WINDOWS_PER_MATRIX
        h = cfg.overlap
        if h > per:
            raise ValueError(
                f"overlap={h} exceeds WINDOWS_PER_MATRIX={per}: the halo "
                "must come from the immediately preceding denoise matrix"
            )
        n_mat = max(1, -(-w // per))
        pad = n_mat * per - w
        padded = (
            jnp.resize(chunk_windows, (n_mat * per, c, n)) if pad
            else chunk_windows
        )
        mats = padded.reshape(n_mat, per, c, n)
        if h:
            if halo is None:
                halo = jnp.zeros((h, c, n), jnp.float32)
            if halo.shape != (h, c, n):
                raise ValueError(
                    f"halo shape {halo.shape} != ({h}, {c}, {n}) "
                    f"for overlap={h}"
                )
            halos = jnp.concatenate(
                [halo[None].astype(jnp.float32), mats[:-1, per - h:]]
            )
            with jax.named_scope("mspca"):
                den = jax.vmap(
                    lambda m, hl: mspca.denoise_windows(
                        m, level=cfg.mspca_level, wavelet_name=cfg.wavelet,
                        halo=hl,
                        reference_kernels=cfg.reference_kernels,
                    )
                )(mats, halos)
        else:
            with jax.named_scope("mspca"):
                den = jax.vmap(
                    lambda m: mspca.denoise_windows(
                        m, level=cfg.mspca_level, wavelet_name=cfg.wavelet,
                        reference_kernels=cfg.reference_kernels,
                    )
                )(mats)
        chunk_windows = den.reshape(n_mat * per, c, n)[:w]
    return features.wpd_features(
        chunk_windows, level=cfg.wpd_level, wavelet_name=cfg.wavelet,
        use_kernel=cfg.use_kernel,
        reference_kernels=cfg.reference_kernels,
    )


def frontend_step(
    state: FrontendState, chunk_windows: jax.Array, cfg
) -> tuple[FrontendState, jax.Array]:
    """The pure streaming transition: consume one (W, C, N) chunk.

    Returns the advanced state (boundary windows, phase + 1) and the
    chunk's (W, F) feature rows. With ``cfg.overlap == 0`` each chunk's
    denoise is independent (paper Sec. 2.6); with ``overlap > 0`` the
    carried boundary is consumed as the denoise halo. Either way the
    step depends on its predecessor only through ``state``, so scanning
    it over a chunk-aligned stream is bit-identical to the one-shot
    batch featurization.
    """
    feats = chunk_features(
        chunk_windows, cfg, halo=state.boundary if cfg.overlap else None
    )
    bw = state.boundary.shape[0]
    new_state = FrontendState(
        # Last bw RAW windows of the stream so far: the chunk tail when
        # the chunk is at least bw windows deep, topped up from the old
        # boundary otherwise (tiny nonstandard chunk_windows).
        boundary=jnp.concatenate(
            [state.boundary, chunk_windows.astype(jnp.float32)]
        )[-bw:],
        phase=state.phase + 1,
    )
    return new_state, feats


def megabatch_step(
    state: FrontendState, chunks: jax.Array, active: jax.Array, cfg
) -> tuple[FrontendState, jax.Array]:
    """Batched multi-chunk transition: D backlog chunks per stream at once.

    The de-serialized form of scanning ``frontend_step`` D times: because
    the denoise halo is RAW input (the previous chunk's tail), every
    chunk's halo is already present in the backlog itself -- chunk d's
    halo is the tail of chunk d-1, and only chunk 0 needs the carried
    ``state.boundary``. So the heavy stage (denoise + WPD) runs ONCE over
    the flattened (B*D) chunk batch with halos gathered from the
    concatenated per-stream window sequence, no sequential dependency.

    state  : (B,)-leading ``FrontendState`` (one per stream/slot).
    chunks : (B, D, W, C, N) raw backlog windows, slot-major.
    active : (B, D) int32/bool PREFIX masks -- active[b] must be
             ``[1]*take + [0]*(D-take)``: real backlog chunks first,
             then padding. (That is the only shape the engine's backlog
             pop produces; the closed-form boundary/phase advance below
             relies on it.)
    Returns the advanced state -- boundary = the last ``bw`` raw windows
    after consuming each stream's ``take = sum(active[b])`` chunks,
    phase += take, exactly what ``take`` masked ``frontend_step``s leave
    behind -- and (B, D, W, F) feature rows. Feature rows of ACTIVE
    chunks are bit-identical to the serial scan (the halos are the same
    float32 windows either way); rows of padding chunks are computed
    with whatever stale halo precedes them in the buffer and must be
    masked by the caller, where the serial scan would have reused the
    post-``take`` state instead.
    """
    b, d, w, c, n = chunks.shape
    bw = state.boundary.shape[1]
    active = active.astype(jnp.int32)
    # Per-stream raw window sequence: carried boundary, then the backlog
    # in order. Chunk d starts at offset bw + d*w, so the bw windows
    # before it -- its halo -- sit at [d*w, d*w + bw).
    stream = jnp.concatenate(
        [state.boundary, chunks.astype(jnp.float32).reshape(b, d * w, c, n)],
        axis=1,
    )  # (B, bw + D*W, C, N)
    flat = chunks.reshape(b * d, w, c, n)
    if cfg.overlap:
        halo_idx = (
            jnp.arange(d, dtype=jnp.int32)[:, None] * w
            + jnp.arange(bw, dtype=jnp.int32)[None, :]
        )  # (D, bw)
        halos = stream[:, halo_idx].reshape(b * d, bw, c, n)
        feats = jax.vmap(
            lambda ch, hl: chunk_features(ch, cfg, halo=hl)
        )(flat, halos)
    else:
        feats = jax.vmap(lambda ch: chunk_features(ch, cfg))(flat)
    take = jnp.sum(active, axis=1)  # (B,)
    # Last bw raw windows of (boundary ++ chunks[:take]) -- the window
    # range [take*w, take*w + bw) of the concatenated stream. take == 0
    # slices at offset 0: the old boundary, untouched.
    new_boundary = jax.vmap(
        lambda s, t: jax.lax.dynamic_slice(
            s, (t * w, jnp.int32(0), jnp.int32(0)), (bw, c, n)
        )
    )(stream, take)
    new_state = FrontendState(
        boundary=new_boundary, phase=state.phase + take
    )
    return new_state, feats.reshape(b, d, w, -1)


@functools.partial(jax.jit, static_argnames=("cfg",))
def scan_stream(
    state: FrontendState, chunks: jax.Array, cfg
) -> tuple[FrontendState, jax.Array]:
    """Scan ``frontend_step`` over a (n_chunks, W, C, N) stream.

    Returns the final state and (n_chunks, W, F) feature rows. This is
    the implementation of ``pipeline.process_windows`` (which flattens
    the chunk axis back out) and the single-slot view of the serving
    engine's backlog-replay scan.
    """
    return jax.lax.scan(
        lambda s, ch: frontend_step(s, ch, cfg), state, chunks
    )


class StreamingFrontend:
    """Host-side incremental featurizer (the continuous-monitor shape).

    Feed raw windows in ANY split sizes; each completed
    ``chunk_windows``-window chunk is featurized through one
    ``frontend_step`` with the carried state, so the concatenated output
    over a session equals the one-shot ``pipeline.process_windows`` of
    the same stream bit-for-bit. Partial chunks stay buffered (use
    ``pending_windows`` to inspect).
    """

    def __init__(self, cfg, chunk_windows: int = eeg_data.WINDOWS_PER_MATRIX):
        self.cfg = cfg
        self.chunk_windows = chunk_windows
        self.state = init_state(overlap=cfg.overlap)
        self._buf = np.zeros(
            (0, eeg_data.N_CHANNELS, eeg_data.WINDOW), np.float32
        )

    @property
    def pending_windows(self) -> int:
        return int(self._buf.shape[0])

    @property
    def chunks_seen(self) -> int:
        return int(self.state.phase)

    def feed(self, windows) -> np.ndarray:
        """Buffer raw (W, C, N) windows; featurize every completed chunk.

        Returns (k * chunk_windows, F) feature rows for the k chunks this
        call completed (k may be 0: shape (0, F))."""
        windows = np.asarray(windows, np.float32)
        if windows.ndim == 2:
            windows = windows[None]
        self._buf = (
            np.concatenate([self._buf, windows]) if self._buf.size
            else windows.copy()
        )
        per = self.chunk_windows
        n_ready = self._buf.shape[0] // per
        if n_ready == 0:
            return np.zeros(
                (0, features.feature_dim(eeg_data.N_CHANNELS, self.cfg.wpd_level)),
                np.float32,
            )
        ready = self._buf[: n_ready * per].reshape(
            n_ready, per, *self._buf.shape[1:]
        )
        self._buf = self._buf[n_ready * per :]
        # Explicit transfers both ways (device_put in, device_get out):
        # the streaming suites run feed() under
        # jax.transfer_guard("disallow"), so any implicit crossing on
        # this path is a test failure, not a silent host sync.
        self.state, feats = scan_stream(
            self.state, jax.device_put(ready), self.cfg
        )
        return np.asarray(jax.device_get(feats)).reshape(n_ready * per, -1)

    def state_dict(self) -> dict[str, np.ndarray]:
        """The complete resumable state (carried context + the buffered
        partial chunk) as a flat numpy dict, ready for
        ``checkpoint.store.save``."""
        arrays = state_to_arrays(self.state)
        arrays["buf"] = np.asarray(self._buf, np.float32)
        return arrays

    def load_state_dict(self, arrays: dict) -> None:
        """Resume from a ``state_dict``: subsequent ``feed`` output is
        byte-identical to the uninterrupted stream's. Rejects state from
        a different overlap setting (boundary width mismatch)."""
        self.state = state_from_arrays(
            arrays, width=boundary_width(self.cfg.overlap)
        )
        self._buf = np.asarray(arrays["buf"], np.float32)
