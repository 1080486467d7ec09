"""Compile the seizure path's kernels and engine step for a TPU v5e chip.

The TPU compiler is installed with jaxlib, and it compiles for a chip
that is described but not attached, so these tests catch what interpret
mode cannot -- block shapes the (8, 128) tiling refuses, in-kernel shape
casts Mosaic cannot lower, VMEM overflows, a step that does not fit the
chip's 16 GB -- without one. Nothing here runs on a device; results and
times need the chip (``chip_smoke.py``).

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library, and every
test worker imports this file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.eeg_paper import CONFIG, WINDOWS_PER_CHUNK
from repro.kernels.forest import kernel as forest_kernel
from repro.kernels.forest.ops import PackedForest
from repro.kernels.gram import kernel as gram_kernel
from repro.kernels.histogram import kernel as hist_kernel
from repro.kernels.wpd import kernel as wpd_kernel
from repro.serving import api
from repro.signal import eeg_data, features, wavelet

V5E_HBM_BYTES = 16 * 1000**3

# The paper's widths (configs/eeg_paper.py): 3 channels x 16 WPD nodes x 6
# statistics = 288 features, depth-6 trees (64 heap slots), 32 bins.
N_FEAT = features.feature_dim(eeg_data.N_CHANNELS, CONFIG.wpd_level)
N_LEAVES = 2 ** CONFIG.forest.depth
N_TREES = CONFIG.forest.n_trees
N_CLASSES = CONFIG.forest.n_classes
# A fleet engine step: 64 sessions x replay depth 4.
FLEET_B, FLEET_D = 64, 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compile cache off: an
    entry written for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_forest_kernel_compiles(one_chip):
    rows = FLEET_B * FLEET_D * WINDOWS_PER_CHUNK
    _compile_kernel(
        forest_kernel.forest_traverse,
        _sds(one_chip, (rows, N_FEAT)),
        _sds(one_chip, (N_TREES, N_FEAT, N_LEAVES)),
        _sds(one_chip, (N_TREES, N_LEAVES)),
        _sds(one_chip, (N_TREES, N_LEAVES, N_CLASSES)),
    )


def test_histogram_kernel_compiles(one_chip):
    # The grower's deepest level: 2**(depth-1) nodes x 32 bins buckets,
    # over one map shard of the 7200-window training set.
    n_buckets = 2 ** (CONFIG.forest.depth - 1) * CONFIG.forest.n_bins

    def hist(codes, wy):
        return hist_kernel.class_histogram(codes, wy, n_buckets=n_buckets)

    _compile_kernel(
        hist,
        _sds(one_chip, (N_TREES, 1800, N_FEAT), jnp.int32),
        _sds(one_chip, (N_TREES, 1800, N_CLASSES)),
    )


def test_wpd_kernel_compiles(one_chip):
    h, _ = wavelet.filters(CONFIG.wavelet)
    taps = int(h.shape[0])

    def level(x, h, g):
        return wpd_kernel.wpd_level(x, h, g, taps=taps)

    _compile_kernel(
        level,
        _sds(one_chip, (WINDOWS_PER_CHUNK * eeg_data.N_CHANNELS * 64,
                        eeg_data.WINDOW)),
        _sds(one_chip, (taps,)),
        _sds(one_chip, (taps,)),
    )


def test_gram_kernel_compiles(one_chip):
    # One MSPCA data matrix: 2048 samples x (60 windows x 3 channels).
    _compile_kernel(
        gram_kernel.gram,
        _sds(one_chip, (eeg_data.WINDOW,
                        WINDOWS_PER_CHUNK * eeg_data.N_CHANNELS)),
    )


def test_engine_step_compiles_and_fits(one_chip):
    fe_width = max(1, CONFIG.overlap)
    state = api.EngineState(
        rings=_sds(one_chip, (FLEET_B, CONFIG.alarm_m), jnp.int32),
        ring_pos=_sds(one_chip, (FLEET_B,), jnp.int32),
        alarm=_sds(one_chip, (FLEET_B,), jnp.int32),
        fe_boundary=_sds(one_chip, (FLEET_B, fe_width, eeg_data.N_CHANNELS,
                                    eeg_data.WINDOW)),
        fe_phase=_sds(one_chip, (FLEET_B,), jnp.int32),
    )
    chunks = _sds(one_chip, (FLEET_B, FLEET_D, WINDOWS_PER_CHUNK,
                             eeg_data.N_CHANNELS, eeg_data.WINDOW))
    packed = PackedForest(
        proj=_sds(one_chip, (N_TREES, N_FEAT, N_LEAVES)),
        thr=_sds(one_chip, (N_TREES, N_LEAVES)),
        leaf_probs=_sds(one_chip, (N_TREES, N_LEAVES, N_CLASSES)),
    )
    compiled = api._jit_engine_step_megabatch.lower(
        state, chunks, _sds(one_chip, (FLEET_B, FLEET_D), jnp.int32),
        packed, _sds(one_chip, (N_FEAT,)), _sds(one_chip, (N_FEAT,)),
        cfg=CONFIG, use_pallas=False,
    ).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
