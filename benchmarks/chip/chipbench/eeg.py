"""Seeded Freiburg-like EEG for the chip benchmark (the benchmark's own copy).

A copy of the surrogate generator the program ships (interictal,
preictal and ictal regimes with patient-keyed rhythms), kept here so
that the benchmark's traffic cannot move when the program's generator
changes. The recording format is a configuration's ``signal`` block
(``Signal``: the paper's is 3 channels at 256 Hz, 2048-sample windows,
60-window chunks); every channel is made by the same rule from its own
phases and noise key, so a montage of more channels keeps the first
ones' rhythms. Everything is built on the device in a few jitted calls
from one key.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

PREICTAL_S = 48 * 60        # the preictal record before an onset

INTERICTAL, PREICTAL, ICTAL = 0, 1, 2


class Signal(NamedTuple):
    """A recording format: a configuration's ``signal`` block."""

    channels: int
    window_samples: int
    chunk_windows: int          # windows per chunk, the unit of a vote
    sample_rate_hz: int

    @classmethod
    def of(cls, cfg: dict) -> "Signal":
        return cls(**cfg["signal"])

    @property
    def preictal_chunks(self) -> int:
        """Whole chunks of the 48-minute preictal record."""
        return (PREICTAL_S * self.sample_rate_hz
                // (self.window_samples * self.chunk_windows))

    def as_windows(self, x):
        """(..., channels, window_samples) -> (n, channels, window_samples)."""
        return x.reshape(-1, self.channels, self.window_samples)

    def as_chunks(self, x):
        """Windows in chunk order -> (n, chunk_windows, channels,
        window_samples)."""
        return x.reshape(-1, self.chunk_windows, self.channels,
                         self.window_samples)


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key that keeps all bits of a seed wider than 32 bits
    (``PRNGKey`` alone drops the high word)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF
    )


def _patient_params(patient_id: int) -> dict:
    ks = jax.random.split(jax.random.PRNGKey(1000 + patient_id), 8)
    u = lambda k, lo, hi: jax.random.uniform(k, (), minval=lo, maxval=hi)
    return dict(
        alpha_amp=u(ks[0], 8.0, 15.0), beta_amp=u(ks[1], 2.0, 5.0),
        theta_amp=u(ks[2], 3.0, 7.0), alpha_freq=u(ks[3], 8.5, 11.5),
        spike_freq=u(ks[4], 3.0, 5.0), noise=u(ks[5], 2.0, 6.0),
        ramp=u(ks[6], 0.5, 2.0), synchrony=u(ks[7], 0.6, 0.95),
    )


def _pink_noise(key: jax.Array, shape: tuple[int, ...], fs: int) -> jax.Array:
    white = jax.random.normal(key, shape)
    spec = jnp.fft.rfft(white, axis=-1)
    freqs = jnp.fft.rfftfreq(shape[-1], d=1.0 / fs)
    pink = jnp.fft.irfft(
        spec / jnp.sqrt(jnp.maximum(freqs, 1.0)), n=shape[-1], axis=-1
    ).astype(jnp.float32)
    return pink / (jnp.std(pink, axis=-1, keepdims=True) + 1e-8)


def windows(key, patient_id, state: int, n_windows: int, sig: Signal
            ) -> jax.Array:
    """(n_windows, channels, window_samples) float32 EEG in microvolts;
    ``state`` and ``sig`` static. Preictal windows drift toward the onset
    over the batch."""
    a, b = _patient_params(0), _patient_params(1)
    even = patient_id % 2 == 0
    mix = (patient_id % 5).astype(jnp.float32) / 4.0
    pp = {k: jnp.where(even, a[k], b[k]) * (0.8 + 0.4 * mix) for k in a}
    key = jax.random.fold_in(key, patient_id)
    fs, n_samples = sig.sample_rate_hz, sig.window_samples
    t = (jnp.arange(n_windows * n_samples, dtype=jnp.float32) / fs).reshape(
        n_windows, n_samples
    )
    k_noise, k_phase, _, _ = jax.random.split(key, 4)
    phases = jax.random.uniform(k_phase, (sig.channels, 4), maxval=2 * jnp.pi)
    drift = (jnp.arange(n_windows, dtype=jnp.float32)
             / max(n_windows - 1, 1))[:, None]
    noise_keys = jax.random.split(k_noise, sig.channels)

    def channel(c):
        ph = phases[c]
        alpha = pp["alpha_amp"] * jnp.sin(
            2 * jnp.pi * pp["alpha_freq"] * t + ph[0])
        beta = pp["beta_amp"] * jnp.sin(2 * jnp.pi * 21.0 * t + ph[1])
        theta = pp["theta_amp"] * jnp.sin(2 * jnp.pi * 6.0 * t + ph[2])
        noise = pp["noise"] * _pink_noise(noise_keys[c], t.shape, fs)
        if state == INTERICTAL:
            return alpha + beta + 0.3 * theta + noise
        carrier = jnp.sin(2 * jnp.pi * (
            6.0 if state == PREICTAL else pp["spike_freq"]) * t)
        sharp = jnp.sign(carrier) * jnp.abs(carrier) ** 0.3
        if state == PREICTAL:
            sync_theta = pp["theta_amp"] * jnp.sin(2 * jnp.pi * 6.0 * t)
            return (alpha * (1.0 - 0.3 * drift) + beta
                    + (1.0 + pp["ramp"] * drift)
                    * (0.5 * theta + pp["synchrony"] * sync_theta)
                    + pp["theta_amp"] * (0.5 + 1.2 * drift) * sharp
                    + noise * (1.0 + 0.5 * drift))
        return 4.0 * pp["alpha_amp"] * sharp + 0.5 * alpha + 0.5 * noise

    return jnp.stack([channel(c) for c in range(sig.channels)], axis=1).astype(
        jnp.float32
    )


def stratified_chunk_order(n_inter_chunks: int, n_pre_chunks: int) -> np.ndarray:
    """Chunk order that spreads each class at even fractional strides, so
    that contiguous chunk-aligned map shards all hold both classes
    (interictal chunks are ids [0, n_inter), preictal the rest)."""
    ids = [np.arange(n_inter_chunks),
           n_inter_chunks + np.arange(n_pre_chunks)]
    pos = np.concatenate([(np.arange(len(c)) + 0.5) / len(c) for c in ids])
    return np.concatenate(ids)[np.argsort(pos, kind="stable")]


@functools.partial(jax.jit, static_argnames=("n_inter", "n_pre", "sig"))
def training_set(key, patient_id, *, n_inter: int, n_pre: int, sig: Signal):
    """One patient's training set: ``n_inter`` interictal windows and the
    ``n_pre``-window preictal record, whole chunks stratified by class.
    Returns (windows (n, channels, window_samples), labels (n,) int32)."""
    k1, k2 = jax.random.split(key)
    wins = jnp.concatenate([
        windows(k1, patient_id, INTERICTAL, n_inter, sig),
        windows(k2, patient_id, PREICTAL, n_pre, sig),
    ])
    labels = jnp.concatenate([
        jnp.zeros((n_inter,), jnp.int32), jnp.ones((n_pre,), jnp.int32)
    ])
    chunk = sig.chunk_windows
    order = stratified_chunk_order(n_inter // chunk, n_pre // chunk)
    idx = (order[:, None] * chunk + np.arange(chunk)[None, :]).reshape(-1)
    return wins[idx], labels[idx]


@functools.partial(jax.jit, static_argnames=("n_inter_chunks", "sig"))
def timeline(key, patient_id, *, n_inter_chunks: int, sig: Signal):
    """A chronological stream in whole chunks: ``n_inter_chunks`` of
    interictal EEG, then the 48-minute preictal run-up (6 chunks at the
    paper's format). Returns (chunks (n, chunk_windows, channels,
    window_samples), chunk labels (n,))."""
    k1, k2 = jax.random.split(key)
    n_pre = sig.preictal_chunks
    wins = jnp.concatenate([
        windows(k1, patient_id, INTERICTAL,
                n_inter_chunks * sig.chunk_windows, sig),
        windows(k2, patient_id, PREICTAL, n_pre * sig.chunk_windows, sig),
    ])
    labels = jnp.concatenate([
        jnp.zeros((n_inter_chunks,), jnp.int32),
        jnp.ones((n_pre,), jnp.int32),
    ])
    return sig.as_chunks(wins), labels


def chunk_pool(key, n_timelines: int, n_inter_chunks: int, n_patients: int,
               sig: Signal):
    """(n_timelines * (n_inter_chunks + preictal chunks), chunk_windows,
    channels, window_samples) chunks on the device: seeded patient
    timelines, each ending in a preictal run-up, laid end to end;
    timeline j belongs to patient j mod ``n_patients``."""
    return _pool(key, jnp.arange(n_timelines) % n_patients,
                 n_inter_chunks=n_inter_chunks, sig=sig)


@functools.partial(jax.jit, static_argnames=("n_inter_chunks", "sig"))
def _pool(key, patients, *, n_inter_chunks: int, sig: Signal):
    keys = jax.random.split(key, patients.shape[0])
    chunks, _ = jax.lax.map(
        lambda kp: timeline(kp[0], kp[1], n_inter_chunks=n_inter_chunks,
                            sig=sig),
        (keys, patients),
    )
    return chunks.reshape((-1,) + chunks.shape[2:])
