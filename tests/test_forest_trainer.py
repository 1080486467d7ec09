"""Distributed MapReduce forest training: the run/run_local equivalence,
the union-reduce algebra, and the signal-level mesh-aware fit path."""

from __future__ import annotations

import pathlib
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import forest_trainer as ft
from repro.core import rotation_forest as rf
from repro.launch.mesh import make_data_mesh
from repro.signal import eeg_data, pipeline


@pytest.fixture(scope="module")
def blobs():
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    x0 = jax.random.normal(k1, (200, 12)) + 2.0
    x1 = jax.random.normal(k2, (200, 12)) - 2.0
    x = jnp.concatenate([x0, x1])
    y = jnp.concatenate([jnp.zeros(200, jnp.int32), jnp.ones(200, jnp.int32)])
    perm = jax.random.permutation(k3, 400)
    return x[perm], y[perm]


CFG = rf.RotationForestConfig(
    n_trees=8, n_subsets=3, depth=4, n_classes=2, n_bins=16
)


class TestFitMapreduce:
    def test_mesh_equals_local_single_shard(self, blobs):
        x, y = blobs
        mesh = make_data_mesh(1)
        on_mesh = ft.fit_mapreduce(jax.random.PRNGKey(5), x, y, CFG, mesh=mesh)
        local = ft.fit_mapreduce(jax.random.PRNGKey(5), x, y, CFG, n_shards=1)
        for a, b in zip(jax.tree.leaves(on_mesh), jax.tree.leaves(local)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_mesh_equals_local_two_shards_subprocess(self, blobs):
        """run_local(S) must be BIT-IDENTICAL to run on an S-device mesh.

        The host device count is locked at first jax init, so the
        S=2 SPMD half runs in a subprocess with forced host devices; it
        prints the result leaves, which must match the in-process
        emulation exactly."""
        x, y = blobs
        small = CFG._replace(n_trees=4, depth=3, n_bins=8)
        local = ft.fit_mapreduce(
            jax.random.PRNGKey(5), x[:64], y[:64], small, n_shards=2
        )
        script = textwrap.dedent(
            """
            import os
            os.environ["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count=2"
            )
            import jax, jax.numpy as jnp, numpy as np
            from repro.core import forest_trainer as ft
            from repro.core import rotation_forest as rf
            key = jax.random.PRNGKey(0)
            k1, k2, k3 = jax.random.split(key, 3)
            x0 = jax.random.normal(k1, (200, 12)) + 2.0
            x1 = jax.random.normal(k2, (200, 12)) - 2.0
            x = jnp.concatenate([x0, x1])
            y = jnp.concatenate(
                [jnp.zeros(200, jnp.int32), jnp.ones(200, jnp.int32)]
            )
            perm = jax.random.permutation(k3, 400)
            x, y = x[perm][:64], y[perm][:64]
            cfg = rf.RotationForestConfig(
                n_trees=4, n_subsets=3, depth=3, n_classes=2, n_bins=8
            )
            from repro.launch.mesh import make_data_mesh
            mesh = make_data_mesh(2)
            res = ft.fit_mapreduce(jax.random.PRNGKey(5), x, y, cfg, mesh=mesh)
            for leaf in jax.tree.leaves(res):
                print("LEAF:" + np.asarray(leaf).tobytes().hex())
            """
        )
        proc = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            timeout=300,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = [
            ln[len("LEAF:"):] for ln in proc.stdout.splitlines()
            if ln.startswith("LEAF:")
        ]
        leaves = jax.tree.leaves(local)
        assert len(lines) == len(leaves)
        for payload, leaf in zip(lines, leaves):
            arr = np.asarray(leaf)
            got = np.frombuffer(
                bytes.fromhex(payload), dtype=arr.dtype
            ).reshape(arr.shape)
            np.testing.assert_array_equal(got, arr)

    def test_two_shard_union_accuracy(self, blobs):
        x, y = blobs
        single = ft.fit_mapreduce(jax.random.PRNGKey(5), x, y, CFG, n_shards=1)
        union = ft.fit_mapreduce(jax.random.PRNGKey(5), x, y, CFG, n_shards=2)
        # 2 shards x ceil(8/2)=4 trees: same ensemble size as single-device.
        assert union.forest.rotation.shape[0] == CFG.n_trees

        def acc(res):
            normed = (x - res.feat_mean) / res.feat_std
            return float(rf.accuracy(res.forest, normed, y))

        assert acc(union) > acc(single) - 0.05

    def test_global_stats_agree_across_shardings(self, blobs):
        # psum'd moments must not depend on the shard count (up to f32).
        x, y = blobs
        r1 = ft.fit_mapreduce(jax.random.PRNGKey(0), x, y, CFG, n_shards=1)
        r4 = ft.fit_mapreduce(jax.random.PRNGKey(0), x, y, CFG, n_shards=4)
        np.testing.assert_allclose(
            np.asarray(r1.feat_mean), np.asarray(r4.feat_mean),
            rtol=1e-4, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(r1.feat_std), np.asarray(r4.feat_std),
            rtol=1e-4, atol=1e-5,
        )

    def test_trees_per_shard_override(self, blobs):
        x, y = blobs
        res = ft.fit_mapreduce(
            jax.random.PRNGKey(0), x, y, CFG, n_shards=2, trees_per_shard=3
        )
        assert res.forest.rotation.shape[0] == 6
        with pytest.raises(ValueError, match="trees_per_shard"):
            ft.fit_mapreduce(
                jax.random.PRNGKey(0), x, y, CFG, n_shards=2,
                trees_per_shard=0,
            )

    def test_mode_selection_is_exclusive(self, blobs):
        x, y = blobs
        mesh = make_data_mesh(1)
        with pytest.raises(ValueError, match="exactly one"):
            ft.fit_mapreduce(jax.random.PRNGKey(0), x, y, CFG)
        with pytest.raises(ValueError, match="exactly one"):
            ft.fit_mapreduce(
                jax.random.PRNGKey(0), x, y, CFG, mesh=mesh, n_shards=1
            )

    def test_ragged_rows_rejected(self, blobs):
        x, y = blobs
        with pytest.raises(ValueError, match="shard evenly"):
            ft.fit_mapreduce(jax.random.PRNGKey(0), x, y, CFG, n_shards=7)


    def test_fit_lowering_carries_the_stage_scopes(self, small_cfg):
        """The mesh-free fit names its stages for a profiler trace
        (``jax.named_scope``): the per-shard featurization with the MSPCA,
        eigh and WPD inside it, the global moments, the rotations and the
        grower. Lowered only, not compiled."""
        sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                               / "benchmarks" / "chip"))
        from chipbench import program_trace

        per = eeg_data.WINDOWS_PER_MATRIX
        text = jax.jit(lambda k, x, y: ft.fit_mapreduce(
            k, x, y, small_cfg.forest, n_shards=2,
            feature_fn=lambda w: pipeline.process_windows(w, small_cfg),
        )).lower(
            jax.random.PRNGKey(0),
            jax.ShapeDtypeStruct((4 * per, eeg_data.N_CHANNELS,
                                  eeg_data.WINDOW), jnp.float32),
            jax.ShapeDtypeStruct((4 * per,), jnp.int32),
        ).as_text(debug_info=True)
        found = set().union(*map(program_trace.segments,
                                 re.findall(r'loc\("([^"]+)"', text)))
        assert {"featurize", "mspca", "eigh", "wpd", "moments", "rotate",
                "grow"} <= found


class TestMergeAlgebra:
    def test_merge_is_associative(self, blobs):
        x, y = blobs
        cfg = CFG._replace(n_trees=2, depth=3)
        a, b, c = (
            rf.fit(jax.random.PRNGKey(s), x, y, cfg) for s in (0, 1, 2)
        )
        left = rf.merge(rf.merge(a, b), c)
        right = rf.merge(a, rf.merge(b, c))
        for u, v in zip(jax.tree.leaves(left), jax.tree.leaves(right)):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))

    def test_union_reduce_equals_pairwise_merge(self, blobs):
        """reduce_concat (the shard reduce) == iterated ``rf.merge``: the
        union forest is exactly each shard's sub-forest, in shard order."""
        x, y = blobs
        cfg = CFG._replace(n_trees=4, depth=3)
        res = ft.fit_mapreduce(jax.random.PRNGKey(5), x, y, cfg, n_shards=2)
        shard_cfg = cfg._replace(n_trees=2)
        normed = (x.astype(jnp.float32) - res.feat_mean) / res.feat_std
        subs = [
            rf.fit(
                jax.random.fold_in(jax.random.PRNGKey(5), s), normed, y,
                shard_cfg,
            )
            for s in range(2)
        ]
        # NOTE: each oracle shard here fits on the FULL normalized data;
        # the mapreduce shards fit on half each, so only the structure
        # (tree count + member order of the merge monoid) is compared
        # against merge, plus merge's exact leaf layout.
        merged = rf.merge(subs[0], subs[1])
        assert merged.rotation.shape[0] == res.forest.rotation.shape[0] == 4
        np.testing.assert_array_equal(
            np.asarray(merged.rotation[:2]), np.asarray(subs[0].rotation)
        )
        np.testing.assert_array_equal(
            np.asarray(merged.rotation[2:]), np.asarray(subs[1].rotation)
        )


class TestPipelineMeshPath:
    @pytest.fixture(scope="class")
    def small_cfg(self):
        return pipeline.PipelineConfig(
            forest=rf.RotationForestConfig(
                n_trees=8, n_subsets=3, depth=5, n_classes=2, n_bins=16
            )
        )

    def test_sharded_fit_serves_alarms(self, small_cfg):
        # 4 chunks stratified to [i, p, i, p]: each of the 2 shards gets
        # one chunk of each class (2 chunks would leave shards pure).
        rec = eeg_data.stratify_chunks(
            eeg_data.make_training_set(
                jax.random.PRNGKey(42), 3,
                n_interictal_windows=120, n_preictal_windows=120,
            )
        )
        fitted = pipeline.fit(
            jax.random.PRNGKey(1), rec, small_cfg, n_shards=2
        )
        assert fitted.forest.rotation.shape[0] == small_cfg.forest.n_trees
        timeline = eeg_data.make_test_timeline(
            jax.random.PRNGKey(7), 3, hours_interictal=1,
        )
        res = pipeline.evaluate_timeline(fitted, timeline, small_cfg)
        assert float(res.lead_time_minutes) > 0  # predicts the seizure
        assert int(res.alarms[-1]) == 1

    def test_misaligned_denoise_shards_rejected(self, small_cfg):
        # 240 windows / 3 shards = 80 windows per shard = 1.33 denoise
        # matrices: the wrap-tiled partial chunk must be a loud error.
        rec = eeg_data.make_training_set(
            jax.random.PRNGKey(0), 1,
            n_interictal_windows=120, n_preictal_windows=120,
        )
        with pytest.raises(ValueError, match="WINDOWS_PER_MATRIX"):
            pipeline.fit(jax.random.PRNGKey(1), rec, small_cfg, n_shards=3)
        # denoise=False has no cross-window context: any even split is fine
        fitted = pipeline.fit(
            jax.random.PRNGKey(1), rec, small_cfg._replace(denoise=False),
            n_shards=3,
        )
        assert fitted.forest.rotation.shape[0] >= small_cfg.forest.n_trees

    def test_stratify_chunks_balances_shards(self):
        rec = eeg_data.make_training_set(
            jax.random.PRNGKey(0), 1,
            n_interictal_windows=120, n_preictal_windows=120,
        )
        strat = eeg_data.stratify_chunks(rec)
        per = eeg_data.WINDOWS_PER_MATRIX
        labels = np.asarray(strat.labels).reshape(-1, per)
        # alternating chunk classes: every adjacent pair is mixed
        chunk_class = labels.mean(axis=1) > 0.5
        assert chunk_class.tolist() == [False, True] * 2
        # same multiset of windows
        np.testing.assert_allclose(
            np.asarray(strat.windows).sum(), np.asarray(rec.windows).sum(),
            rtol=1e-6,
        )

    def test_stratify_spreads_imbalanced_classes(self):
        # 6 interictal + 2 preictal chunks: a plain round-robin would
        # leave the trailing half all-interictal; the strided placement
        # must put one preictal chunk in each 4-chunk shard.
        per = eeg_data.WINDOWS_PER_MATRIX
        rec = eeg_data.make_training_set(
            jax.random.PRNGKey(0), 1,
            n_interictal_windows=6 * per, n_preictal_windows=2 * per,
        )
        strat = eeg_data.stratify_chunks(rec)
        chunk_class = (
            np.asarray(strat.labels).reshape(-1, per).mean(axis=1) > 0.5
        )
        halves = chunk_class.reshape(2, 4)
        assert halves.sum(axis=1).tolist() == [1, 1]

    def test_stratify_keeps_short_recordings(self):
        rec = eeg_data.make_training_set(
            jax.random.PRNGKey(0), 1,
            n_interictal_windows=20, n_preictal_windows=20,
        )
        strat = eeg_data.stratify_chunks(rec)  # < 2 chunks: unchanged
        np.testing.assert_array_equal(
            np.asarray(strat.windows), np.asarray(rec.windows)
        )
