"""Registry of hot entry points the contract checker traces.

Every function on the serving or training hot path is registered here at
PINNED abstract shapes (``jax.ShapeDtypeStruct`` -- tracing is symbolic,
nothing executes), together with its declared invariants:

  * which arguments its shipped jit wrapper donates, and which of those
    MUST survive lowering as real input/output aliases;
  * which argument is carried state whose output avals must match the
    input avals exactly (shape, dtype, weak type) -- the condition for a
    scan/engine step to stay recompile-free in steady state.

Adding a hot path to the repo means adding an ``EntrySpec`` here; the
``analysis`` CI job then enforces the contracts in ``contracts.RULES``
on it forever. See README "static guarantees" for the catalogue.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class EntrySpec:
    """One registered hot entry point at pinned abstract shapes.

    name          : stable id used in reports and suppressions.
    fn            : the SHIPPED callable (jitted wrappers preferred --
                    then donation checks see the real declaration).
    args          : positional arguments as ShapeDtypeStruct pytrees.
    static_kwargs : static keyword arguments (configs, flags).
    donate_argnums: argnums the shipped wrapper donates (used when ``fn``
                    is not already jitted; jitted fns carry their own).
    must_alias    : argnums whose donation MUST survive lowering.
    carry         : (argnum, out_index) of carried state that must be
                    aval-stable; out_index None means the whole output.
    description   : one line for the report.
    """

    name: str
    fn: Callable
    args: tuple
    static_kwargs: dict = dataclasses.field(default_factory=dict)
    donate_argnums: tuple = ()
    must_alias: tuple = ()
    carry: tuple | None = None
    description: str = ""

    @property
    def is_jitted(self) -> bool:
        return hasattr(self.fn, "lower")


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# Pinned shapes. Small batch/tree counts (tracing cost), REAL window/
# channel geometry (rules like the narrow-output-tile check depend on
# the true trailing dims the kernels see in production).
# ---------------------------------------------------------------------------

B = 2          # engine slots
D = 2          # replay depth
N_SHARDS = 2   # MapReduce shards


def _pinned_cfg(overlap: int = 0):
    from repro.core import rotation_forest as rf
    from repro.signal import pipeline

    return pipeline.PipelineConfig(
        forest=rf.RotationForestConfig(
            n_trees=4, n_subsets=3, depth=4, n_classes=2, n_bins=8
        ),
        overlap=overlap,
    )


def _geometry(cfg):
    from repro.signal import eeg_data, features

    c, n, w = eeg_data.N_CHANNELS, eeg_data.WINDOW, eeg_data.WINDOWS_PER_MATRIX
    f_raw = features.feature_dim(c, cfg.wpd_level)
    k = cfg.forest.n_subsets
    f_pad = f_raw + (-f_raw % k)
    n_leaves = 2 ** cfg.forest.depth
    return c, n, w, f_raw, f_pad, n_leaves


def _packed_avals(cfg):
    from repro.kernels.forest import ops as forest_ops

    _, _, _, _, f_pad, n_leaves = _geometry(cfg)
    t, nc = cfg.forest.n_trees, cfg.forest.n_classes
    return forest_ops.PackedForest(
        proj=_sds((t, f_pad, n_leaves)),
        thr=_sds((t, n_leaves)),
        leaf_probs=_sds((t, n_leaves, nc)),
    )


def _engine_state_avals(cfg):
    from repro.serving import api
    from repro.signal import eeg_data, frontend

    c, n = eeg_data.N_CHANNELS, eeg_data.WINDOW
    bw = frontend.boundary_width(cfg.overlap)
    return api.EngineState(
        rings=_sds((B, cfg.alarm_m), jnp.int32),
        ring_pos=_sds((B,), jnp.int32),
        alarm=_sds((B,), jnp.int32),
        fe_boundary=_sds((B, bw, c, n)),
        fe_phase=_sds((B,), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Entry builders (deferred imports: building the registry traces nothing
# and importing this module stays cheap).
# ---------------------------------------------------------------------------

def _serving_entries():
    from repro.serving import api
    from repro.signal import eeg_data

    cfg = _pinned_cfg()
    c, n, w, f_raw, _, _ = _geometry(cfg)
    state = _engine_state_avals(cfg)
    packed = _packed_avals(cfg)
    mean, std = _sds((f_raw,)), _sds((f_raw,))
    statics = dict(cfg=cfg, use_pallas=False)
    yield EntrySpec(
        name="serving.engine_step",
        fn=api._jit_engine_step,
        args=(state, _sds((B, D, w, c, n)), _sds((B, D), jnp.int32),
              packed, mean, std),
        static_kwargs=statics,
        donate_argnums=(0,),
        must_alias=(0,),
        carry=(0, 0),
        description="engine backlog-replay step: frontend scan + forest "
                    "vote + alarm rings, one jitted program",
    )
    yield EntrySpec(
        name="serving.engine_step_megabatch",
        fn=api._jit_engine_step_megabatch,
        args=(state, _sds((B, D, w, c, n)), _sds((B, D), jnp.int32),
              packed, mean, std),
        static_kwargs=statics,
        donate_argnums=(0,),
        must_alias=(0,),
        carry=(0, 0),
        description="megabatch engine step (engine default): (B*D) "
                    "batched denoise+WPD+vote, thin alarm-ring scan",
    )
    yield EntrySpec(
        name="serving.score_chunks",
        fn=api._jit_score_chunks,
        args=(_sds((B, w, c, n)), packed, mean, std),
        static_kwargs=statics,
        description="stateless fused chunk scoring (denoise+WPD+vote)",
    )
    yield EntrySpec(
        name="serving.splice_state",
        fn=api._splice_state,
        args=(state, _sds((), jnp.int32), _sds((cfg.alarm_m,), jnp.int32),
              _sds((), jnp.int32), _sds((), jnp.int32),
              _sds((state.fe_boundary.shape[1], c, n)), _sds((), jnp.int32)),
        donate_argnums=(0,),
        must_alias=(0,),
        carry=(0, None),
        description="session admit: splice saved stream state into a slot",
    )
    yield EntrySpec(
        name="serving.init_state",
        fn=api.init_state,
        args=(),
        static_kwargs=dict(max_batch=B, alarm_m=cfg.alarm_m),
        description="on-device zero engine state (no host zeros transfer)",
    )
    yield EntrySpec(
        name="serving.engine_restore",
        fn=api._install_state,
        args=(state,),
        carry=(0, None),
        description="snapshot-restore state install: canonicalize restored "
                    "leaves so the first post-restore step is a cache hit",
    )
    yield EntrySpec(
        name="serving.engine_swap_program",
        fn=api._install_program_arrays,
        args=(packed, mean, std),
        carry=(0, 0),
        description="live program hot-swap install: same-shape program "
                    "arrays stay step inputs (drain-free, 0 recompiles)",
    )


def _signal_entries():
    from repro.signal import eeg_data, frontend

    c, n = eeg_data.N_CHANNELS, eeg_data.WINDOW
    w = eeg_data.WINDOWS_PER_MATRIX
    for overlap in (0, 2):
        cfg = _pinned_cfg(overlap=overlap)
        bw = frontend.boundary_width(overlap)
        st = frontend.FrontendState(
            boundary=_sds((bw, c, n)), phase=_sds((), jnp.int32)
        )
        suffix = f"_overlap{overlap}" if overlap else ""
        yield EntrySpec(
            name=f"signal.frontend_step{suffix}",
            fn=frontend.frontend_step,
            args=(st, _sds((w, c, n))),
            static_kwargs=dict(cfg=cfg),
            carry=(0, 0),
            description="streaming front-end transition (denoise + WPD)",
        )
    cfg = _pinned_cfg()
    st = frontend.FrontendState(
        boundary=_sds((1, c, n)), phase=_sds((), jnp.int32)
    )
    yield EntrySpec(
        name="signal.process_windows_scan",
        fn=frontend.scan_stream,
        args=(st, _sds((3, w, c, n))),
        static_kwargs=dict(cfg=cfg),
        carry=(0, 0),
        description="chunk-aligned stream scan of frontend_step",
    )


def _training_entries():
    from repro.core import decision_tree, forest_trainer

    cfg = _pinned_cfg()
    t, n_rows, f = cfg.forest.n_trees, 64, 9
    yield EntrySpec(
        name="core.fit_forest_binned",
        fn=decision_tree.fit_forest_binned,
        args=(_sds((t, n_rows, f), jnp.int32), _sds((n_rows,), jnp.int32),
              _sds((t, n_rows))),
        static_kwargs=dict(
            depth=cfg.forest.depth, n_classes=cfg.forest.n_classes,
            n_bins=cfg.forest.n_bins,
        ),
        description="level-synchronous fused forest grower",
    )
    yield EntrySpec(
        name="core.fit_mapreduce_map",
        fn=functools.partial(
            forest_trainer.fit_mapreduce, n_shards=N_SHARDS
        ),
        args=(_sds((2,), jnp.uint32), _sds((n_rows, f)),
              _sds((n_rows,), jnp.int32)),
        static_kwargs=dict(cfg=cfg.forest),
        description="MapReduce shard fit (psum'd moments + union reduce), "
                    "vmap-emulated mesh",
    )


def _kernel_entries():
    from repro.kernels.flash_attention import ops as flash_ops
    from repro.kernels.forest import ops as forest_ops
    from repro.kernels.gram import ops as gram_ops
    from repro.kernels.histogram import ops as hist_ops
    from repro.kernels.ssd import ops as ssd_ops
    from repro.kernels.wpd import ops as wpd_ops
    from repro.signal import eeg_data

    cfg = _pinned_cfg()
    _, _, _, f_raw, _, _ = _geometry(cfg)
    packed = _packed_avals(cfg)
    yield EntrySpec(
        name="kernels.forest.forest_predict_proba",
        fn=forest_ops.forest_predict_proba,
        args=(packed, _sds((16, f_raw))),
        static_kwargs=dict(use_pallas=True, block_b=128, interpret=True),
        description="packed-forest Pallas traversal (one (B, T) pass)",
    )
    t, n_rows, f, nc = 2, 64, 9, cfg.forest.n_classes
    yield EntrySpec(
        name="kernels.histogram.class_histogram",
        fn=hist_ops.class_histogram,
        args=(_sds((t, n_rows, f), jnp.int32), _sds((t, n_rows, nc))),
        static_kwargs=dict(
            n_buckets=16, use_pallas=True, block_n=32, interpret=True
        ),
        description="grower histogram as one-hot MXU matmul",
    )
    yield EntrySpec(
        name="kernels.gram.gram",
        fn=gram_ops.gram,
        args=(_sds((256, 128)),),
        static_kwargs=dict(use_pallas=True),
        description="tiled X^T X (MSPCA covariance stage)",
    )
    yield EntrySpec(
        name="kernels.wpd.wpd_level",
        fn=wpd_ops.wpd_level,
        args=(_sds((16, eeg_data.WINDOW)),),
        static_kwargs=dict(use_pallas=True, block_b=8),
        description="one WPD analysis level (feature extraction stage)",
    )
    yield EntrySpec(
        name="kernels.ssd.ssd_scan",
        fn=ssd_ops.ssd_scan,
        args=(_sds((2, 32, 128)), _sds((2, 32, 128)), _sds((2, 32, 128)),
              _sds((2, 32))),
        static_kwargs=dict(chunk=16, use_pallas=True),
        description="SSD chunked scan (models stack)",
    )
    yield EntrySpec(
        name="kernels.flash_attention.flash_attention",
        fn=flash_ops.flash_attention,
        args=(_sds((1, 32, 2, 128)), _sds((1, 32, 1, 128)),
              _sds((1, 32, 1, 128))),
        static_kwargs=dict(block_q=16, block_k=16, use_pallas=True),
        description="flash attention (models stack)",
    )


def build_registry() -> list[EntrySpec]:
    """All registered hot entry points (deterministic order)."""
    entries: list[EntrySpec] = []
    for gen in (_serving_entries, _signal_entries, _training_entries,
                _kernel_entries):
        entries.extend(gen())
    names = [e.name for e in entries]
    assert len(names) == len(set(names)), "duplicate entry names"
    return entries


def get_entry(name: str) -> EntrySpec:
    for e in build_registry():
        if e.name == name:
            return e
    raise KeyError(name)


Registry = Any  # alias for typing in callers
