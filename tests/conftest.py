"""Shared test infrastructure: hypothesis CI
profiles, and the seam-oracle fixtures every streaming-scoring suite
builds on (one synthetic stream + one trained program per test session
instead of each module rolling its own).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import rotation_forest as rf
from repro.serving import api
from repro.signal import eeg_data, mspca, pipeline

# ---------------------------------------------------------------------------
# Hypothesis profiles. The default "ci" profile keeps the PR gate fast and
# deterministic (derandomize: same examples every run); the "deep" profile
# is the scheduled fuzzing job (ci.yml `hypothesis-deep`): ~10x examples,
# derandomize OFF so every night draws fresh inputs. Select with
# REPRO_HYPOTHESIS_PROFILE=deep. Tests must NOT pass their own
# @settings -- that would override the profile and pin the deep job back
# to the shallow examples.
# ---------------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, settings

    _COMMON = dict(deadline=None, suppress_health_check=list(HealthCheck))
    settings.register_profile(
        "ci", max_examples=6, derandomize=True, **_COMMON
    )
    settings.register_profile(
        "deep", max_examples=60, derandomize=False, print_blob=True,
        **_COMMON,
    )
    settings.load_profile(os.environ.get("REPRO_HYPOTHESIS_PROFILE", "ci"))
except ImportError:  # CI installs hypothesis; local runs may lack it
    pass


# ---------------------------------------------------------------------------
# Seam-oracle stream: a multi-chunk synthetic EEG stream plus its
# full-recording MSPCA reference (the WHOLE stream denoised as ONE
# N x (W_total*C) matrix -- no chunk seams at all). The overlap-aware
# denoise is judged against this oracle: chunked scoring approximates it,
# and a cross-chunk halo must close part of the gap at the seams
# (tests/test_overlap_mspca.py). test_frontend.py reuses the same stream
# for its split/one-shot contracts.
# ---------------------------------------------------------------------------

PER = eeg_data.WINDOWS_PER_MATRIX
N_SEAM_CHUNKS = 3


@pytest.fixture(scope="session")
def seam_stream():
    """(3*PER, C, N) raw multi-chunk stream (2 chunk seams; no labels --
    the frontend suites need no fitted forest)."""
    return np.asarray(eeg_data.generate_windows(
        jax.random.PRNGKey(5), jnp.asarray(3), eeg_data.INTERICTAL,
        N_SEAM_CHUNKS * PER,
    ))


@pytest.fixture(scope="session")
def seam_reference(seam_stream):
    """Full-recording MSPCA oracle: ``seam_stream`` denoised as ONE data
    matrix, so every PCA basis is estimated with global context."""
    return np.asarray(mspca.denoise_windows(jnp.asarray(seam_stream)))


@pytest.fixture(scope="session")
def signal_cfg():
    """Default signal-stage config (no forest needed)."""
    return pipeline.PipelineConfig()


# ---------------------------------------------------------------------------
# Trained scoring artifacts shared by the engine suites
# (test_seizure_engine.py, test_frontend.py, test_engine_properties.py,
# test_overlap_mspca.py). One fit per test session.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="session")
def small_cfg():
    return pipeline.PipelineConfig(
        forest=rf.RotationForestConfig(
            n_trees=6, n_subsets=3, depth=5, n_classes=2, n_bins=16
        )
    )


@pytest.fixture(scope="session")
def fitted(small_cfg):
    rec = eeg_data.make_training_set(
        jax.random.PRNGKey(42), 3, n_interictal_windows=60, n_preictal_windows=60
    )
    return pipeline.fit(jax.random.PRNGKey(1), rec, small_cfg)


@pytest.fixture(scope="session")
def program(fitted, small_cfg):
    return api.ScoringProgram.from_fitted(fitted, small_cfg)


@pytest.fixture(scope="session")
def overlap_cfg(small_cfg):
    """The overlap-aware twin of ``small_cfg`` (2-window denoise halo)."""
    return small_cfg._replace(overlap=2)


@pytest.fixture(scope="session")
def overlap_program(fitted, overlap_cfg):
    """Same forest, overlap-aware scoring config: the packed forest is
    cached on params identity so this shares ``program``'s packing."""
    return api.ScoringProgram.from_fitted(fitted, overlap_cfg)


@pytest.fixture(scope="session")
def timeline():
    return eeg_data.make_test_timeline(
        jax.random.PRNGKey(7), 3, hours_interictal=1, minutes_preictal=48
    )


@pytest.fixture(scope="session")
def chunk_pool(timeline):
    """(quiet, preictal) chunks: vote 0 and vote 1 under the fitted forest."""
    wins = np.asarray(timeline.windows)
    n = wins.shape[0] // PER
    chunks = wins[: n * PER].reshape(n, PER, *wins.shape[1:])
    return chunks[0], chunks[-1]


# ---------------------------------------------------------------------------
# Device-transfer sanitizer (repro.analysis.sanitizers). For the
# streaming suites, every hot serving/frontend method runs under
# jax.transfer_guard("disallow"): the explicit jax.device_put /
# device_get calls those paths make are the ONLY legal host<->device
# crossings, so an accidental np.asarray coercion or implicit transfer
# creeping back into the loop fails the suite instead of silently
# syncing per step. Applied autouse to exactly the modules that exercise
# the hot loop -- other suites legitimately move test data across the
# boundary and are left unguarded.
# ---------------------------------------------------------------------------

_TRANSFER_GUARDED_SUITES = {
    "tests.test_seizure_engine",
    "tests.test_engine_properties",
    "tests.test_frontend",
    "tests.test_overlap_mspca",
    "tests.test_engine_checkpoint",
    "test_seizure_engine",
    "test_engine_properties",
    "test_frontend",
    "test_overlap_mspca",
    "test_engine_checkpoint",
}


@pytest.fixture(autouse=True)
def device_transfer_sanitizer(request):
    if request.module.__name__ not in _TRANSFER_GUARDED_SUITES:
        yield
        return
    from repro.analysis.sanitizers import guard_methods
    from repro.signal import frontend

    with guard_methods(
        api.SeizureEngine,
        "_step_once", "_admit", "_evict", "_sync_frontend", "score_chunks",
    ), guard_methods(frontend.StreamingFrontend, "feed"):
        yield


@pytest.fixture(scope="session")
def recompile_budgets():
    """The pinned compile-count budgets (repro/analysis/budgets.json)."""
    from repro.analysis import load_budgets

    return load_budgets()
