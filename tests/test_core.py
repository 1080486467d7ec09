"""Unit tests for repro.core: PCA, decision trees, rotation forest,
mapreduce, distributed ensemble."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import decision_tree as dt
from repro.core import ensemble, mapreduce as mr, pca
from repro.core import rotation_forest as rf
from repro.launch.mesh import make_data_mesh


@pytest.fixture(scope="module")
def blobs():
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    x0 = jax.random.normal(k1, (200, 12)) + 2.0
    x1 = jax.random.normal(k2, (200, 12)) - 2.0
    x = jnp.concatenate([x0, x1])
    y = jnp.concatenate(
        [jnp.zeros(200, jnp.int32), jnp.ones(200, jnp.int32)]
    )
    perm = jax.random.permutation(k3, 400)
    return x[perm], y[perm]


# ---------------------------------------------------------------- PCA ----

class TestPCA:
    def test_components_orthonormal(self, blobs):
        x, _ = blobs
        st = pca.fit(x)
        eye = st.components @ st.components.T
        np.testing.assert_allclose(np.asarray(eye), np.eye(12), atol=1e-5)

    def test_variances_sorted_nonnegative(self, blobs):
        x, _ = blobs
        st = pca.fit(x)
        v = np.asarray(st.variances)
        assert (v >= 0).all()
        assert (np.diff(v) <= 1e-5).all()

    def test_full_reconstruction_exact(self, blobs):
        x, _ = blobs
        st = pca.fit(x)
        xr = pca.inverse_transform(st, pca.transform(st, x))
        np.testing.assert_allclose(np.asarray(xr), np.asarray(x), atol=1e-4)

    def test_reconstruct_masks_components(self, blobs):
        x, _ = blobs
        st = pca.fit(x)
        r1 = pca.reconstruct(st, x, 1)
        rall = pca.reconstruct(st, x, 12)
        err1 = float(jnp.mean((r1 - x) ** 2))
        errall = float(jnp.mean((rall - x) ** 2))
        assert errall < 1e-6
        assert err1 > errall

    def test_variance_rules(self, blobs):
        x, _ = blobs
        st = pca.fit(x)
        k95 = int(pca.n_components_for_variance(st, 0.95))
        assert 1 <= k95 <= 12
        kk = int(pca.kaiser_rule(st))
        assert 1 <= kk <= 12
        # blobs have one dominant direction (the class separation)
        assert kk <= 3


# ------------------------------------------------------- decision tree ----

class TestDecisionTree:
    def test_fits_separable(self, blobs):
        x, y = blobs
        tree = dt.fit(x, y, depth=4, n_classes=2, n_bins=16)
        acc = float(jnp.mean(dt.predict(tree, x) == y))
        assert acc > 0.98

    def test_probs_normalized(self, blobs):
        x, y = blobs
        tree = dt.fit(x, y, depth=4, n_classes=2, n_bins=16)
        p = dt.predict_proba(tree, x)
        np.testing.assert_allclose(np.asarray(p.sum(-1)), 1.0, atol=1e-4)
        assert float(p.min()) >= 0.0

    def test_weights_mask_samples(self, blobs):
        x, y = blobs
        # Flip half the labels but zero their weight: the tree must ignore them.
        n = x.shape[0]
        y_bad = y.at[: n // 2].set(1 - y[: n // 2])
        w = jnp.ones((n,)).at[: n // 2].set(0.0)
        tree = dt.fit(x, y_bad, w, depth=4, n_classes=2, n_bins=16)
        acc = float(jnp.mean(dt.predict(tree, x)[n // 2 :] == y[n // 2 :]))
        assert acc > 0.95

    def test_pure_node_stops(self):
        x = jnp.ones((32, 3))
        y = jnp.zeros((32,), jnp.int32)
        tree = dt.fit(x, y, depth=3, n_classes=2, n_bins=8)
        # Root is pure: no split anywhere.
        assert int(tree.split_feature[1]) == -1
        p = dt.predict_proba(tree, x)
        assert float(p[:, 0].min()) > 0.9

    def test_depth_one_is_stump(self, blobs):
        x, y = blobs
        tree = dt.fit(x, y, depth=1, n_classes=2, n_bins=16)
        assert tree.leaf_probs.shape == (2, 2)
        acc = float(jnp.mean(dt.predict(tree, x) == y))
        assert acc > 0.9  # blobs are linearly separable on any axis


# ------------------------------------------------------ rotation forest ----

class TestRotationForest:
    def test_fit_predict(self, blobs):
        x, y = blobs
        cfg = rf.RotationForestConfig(
            n_trees=8, n_subsets=3, depth=4, n_classes=2, n_bins=16
        )
        params = rf.fit(jax.random.PRNGKey(0), x, y, cfg)
        assert float(rf.accuracy(params, x, y)) > 0.97

    def test_rotation_is_orthogonal(self, blobs):
        x, y = blobs
        cfg = rf.RotationForestConfig(
            n_trees=4, n_subsets=3, depth=3, n_classes=2, n_bins=16
        )
        params = rf.fit(jax.random.PRNGKey(0), x, y, cfg)
        for t in range(4):
            r = np.asarray(params.rotation[t])
            np.testing.assert_allclose(r @ r.T, np.eye(r.shape[0]), atol=1e-4)

    def test_feature_padding(self):
        # 10 features, 3 subsets -> pads to 12 internally.
        key = jax.random.PRNGKey(1)
        x = jax.random.normal(key, (100, 10))
        y = (x[:, 0] > 0).astype(jnp.int32)
        cfg = rf.RotationForestConfig(
            n_trees=4, n_subsets=3, depth=3, n_classes=2, n_bins=16
        )
        params = rf.fit(key, x, y, cfg)
        assert params.rotation.shape == (4, 12, 12)
        assert float(rf.accuracy(params, x, y)) > 0.9

    def test_merge_unions_forests(self, blobs):
        x, y = blobs
        cfg = rf.RotationForestConfig(
            n_trees=3, n_subsets=3, depth=3, n_classes=2, n_bins=16
        )
        a = rf.fit(jax.random.PRNGKey(0), x, y, cfg)
        b = rf.fit(jax.random.PRNGKey(1), x, y, cfg)
        m = rf.merge(a, b)
        assert m.rotation.shape[0] == 6
        assert float(rf.accuracy(m, x, y)) > 0.95

    def test_pack_is_cached_on_params_identity(self, blobs):
        x, y = blobs
        cfg = rf.RotationForestConfig(
            n_trees=3, n_subsets=3, depth=3, n_classes=2, n_bins=16
        )
        params = rf.fit(jax.random.PRNGKey(0), x, y, cfg)
        assert rf.pack(params) is rf.pack(params)
        # a distinct (even identical-valued) params pytree packs anew
        clone = jax.tree.map(lambda t: t + 0, params)
        assert rf.pack(clone) is not rf.pack(params)

    def test_pack_cache_keys_on_every_leaf(self, blobs):
        # Params sharing a rotation array but carrying DIFFERENT trees
        # must not collide in the cache (regression: id(rotation) alone).
        x, y = blobs
        cfg = rf.RotationForestConfig(
            n_trees=3, n_subsets=3, depth=3, n_classes=2, n_bins=16
        )
        a = rf.fit(jax.random.PRNGKey(8), x, y, cfg)
        b = rf.fit(jax.random.PRNGKey(9), x, y, cfg)
        rf.predict_proba(a, x)
        mixed = rf.RotationForestParams(rotation=a.rotation, trees=b.trees)
        got = rf.predict_proba(mixed, x)
        want = rf.forest_ops.forest_predict_proba(
            rf.forest_ops.pack_forest(mixed), x.astype(jnp.float32)
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_predict_proba_packs_once(self, blobs, monkeypatch):
        x, y = blobs
        cfg = rf.RotationForestConfig(
            n_trees=3, n_subsets=3, depth=3, n_classes=2, n_bins=16
        )
        params = rf.fit(jax.random.PRNGKey(4), x, y, cfg)
        calls = []
        real = rf.forest_ops.pack_forest
        monkeypatch.setattr(
            rf.forest_ops, "pack_forest",
            lambda p: (calls.append(1), real(p))[1],
        )
        p1 = rf.predict_proba(params, x)
        p2 = rf.predict_proba(params, x)
        assert len(calls) == 1  # second call hit the cache
        np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))

    def test_predict_proba_accepts_prepacked(self, blobs):
        x, y = blobs
        cfg = rf.RotationForestConfig(
            n_trees=3, n_subsets=3, depth=3, n_classes=2, n_bins=16
        )
        params = rf.fit(jax.random.PRNGKey(5), x, y, cfg)
        packed = rf.pack(params)
        np.testing.assert_array_equal(
            np.asarray(rf.predict_proba(params, x, packed=packed)),
            np.asarray(rf.predict_proba(params, x)),
        )

    def test_pack_bypasses_cache_under_tracing(self, blobs):
        # core.ensemble vmaps predict_proba over member params (tracers);
        # the identity cache must not capture or serve tracers.
        x, y = blobs
        cfg = rf.RotationForestConfig(
            n_trees=2, n_subsets=3, depth=3, n_classes=2, n_bins=16
        )
        a = rf.fit(jax.random.PRNGKey(6), x, y, cfg)
        b = rf.fit(jax.random.PRNGKey(7), x, y, cfg)
        members = jax.tree.map(lambda u, v: jnp.stack([u, v]), a, b)
        before = dict(rf._PACK_CACHE)
        probs = jax.vmap(lambda p: rf.predict_proba(p, x))(members)
        assert probs.shape == (2, x.shape[0], 2)
        assert rf._PACK_CACHE == before  # no tracer entries leaked in

    def test_ensemble_beats_single_tree_on_noise(self):
        # Noisy labels: ensemble averaging should not be worse than a stump.
        key = jax.random.PRNGKey(3)
        k1, k2 = jax.random.split(key)
        x = jax.random.normal(k1, (300, 9))
        y = (x[:, :3].sum(-1) > 0).astype(jnp.int32)
        flip = jax.random.uniform(k2, (300,)) < 0.15
        y_noisy = jnp.where(flip, 1 - y, y)
        cfg = rf.RotationForestConfig(
            n_trees=16, n_subsets=3, depth=4, n_classes=2, n_bins=16
        )
        params = rf.fit(key, x, y_noisy, cfg)
        acc_clean = float(jnp.mean(rf.predict(params, x) == y))
        assert acc_clean > 0.85


# ------------------------------------------------------------ mapreduce ----

class TestMapReduce:
    def test_local_equals_mesh(self):
        x = jnp.arange(128.0).reshape(64, 2)
        job = mr.MapReduce(lambda s: jnp.sum(s, axis=0), mr.reduce_sum)
        local = job.run_local(4, x)
        mesh = make_data_mesh(1)
        on_mesh = job.run(mesh, x)
        np.testing.assert_allclose(np.asarray(local), np.asarray(on_mesh), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(local), np.asarray(x.sum(0)), rtol=1e-6)

    def test_reduce_concat_preserves_rows(self):
        x = jnp.arange(32.0).reshape(32, 1)
        job = mr.MapReduce(lambda s: s * 2, mr.reduce_concat)
        out = job.run_local(8, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 2)

    def test_reduce_mean_max(self):
        x = jnp.arange(16.0).reshape(16, 1)
        mean_job = mr.MapReduce(lambda s: jnp.mean(s), mr.reduce_mean)
        max_job = mr.MapReduce(lambda s: jnp.max(s), mr.reduce_max)
        assert float(mean_job.run_local(4, x)) == pytest.approx(7.5)
        assert float(max_job.run_local(4, x)) == pytest.approx(15.0)

    def test_replicated_inputs(self):
        x = jnp.ones((8, 2))
        scale = jnp.asarray(3.0)
        job = mr.MapReduce(lambda s, k: jnp.sum(s * k), mr.reduce_sum)
        out = job.run_local(2, x, replicated_inputs=(scale,))
        assert float(out) == pytest.approx(48.0)

    def test_run_local_supports_collectives(self):
        # run_local's vmap carries the axis name, so map fns may psum
        # (the distributed forest trainer's global feature moments).
        x = jnp.arange(8.0).reshape(8, 1)
        job = mr.MapReduce(
            lambda s: jax.lax.psum(jnp.sum(s), "data"), mr.reduce_max
        )
        assert float(job.run_local(4, x)) == pytest.approx(28.0)


# ---------------------------------------------------------- shuffle_by_key ----

class TestShuffleByKey:
    """Exercised under vmap-with-axis-name (all_to_all has a batching
    rule), the same emulation MapReduce.run_local uses."""

    def _shuffle(self, values, keys, n_shards):
        return jax.vmap(
            lambda v, k: mr.shuffle_by_key(v, k, "data", n_shards),
            axis_name="data",
        )(values, keys)

    def test_balanced_keys_route_exactly(self):
        # 2 shards x 4 rows, two rows per destination from each shard.
        values = jnp.arange(8.0).reshape(2, 4, 1)
        keys = jnp.asarray([[0, 1, 0, 1], [1, 0, 1, 0]])
        out = self._shuffle(values, keys, 2)
        # shard 0 receives both shards' dest-0 rows (local order kept).
        assert sorted(np.asarray(out[0, :, 0]).tolist()) == [0.0, 2.0, 5.0, 7.0]
        assert sorted(np.asarray(out[1, :, 0]).tolist()) == [1.0, 3.0, 4.0, 6.0]

    def test_overflow_drops_excess_and_pads_deficit(self):
        # Shard 0 keys THREE of its four rows to destination 0 (bucket
        # capacity 2): the third must be DROPPED -- not leak into shard
        # 1's bucket (the pre-guard misrouting) -- and the short dest-1
        # bucket is zero-padded.
        values = jnp.asarray([[1.0, 2.0, 3.0, 4.0],
                              [10.0, 20.0, 30.0, 40.0]])[..., None]
        keys = jnp.asarray([[0, 0, 0, 1], [0, 1, 0, 1]])
        out = self._shuffle(values, keys, 2)
        # dest 0: shard0 keeps rows 1,2 (drops 3), shard1 sends 10,30.
        assert np.asarray(out[0, :, 0]).tolist() == [1.0, 2.0, 10.0, 30.0]
        # dest 1: shard0 sends row 4 (+pad), shard1 sends 20,40.
        assert np.asarray(out[1, :, 0]).tolist() == [4.0, 0.0, 20.0, 40.0]
        # the overflow row 3.0 appears NOWHERE.
        assert 3.0 not in np.asarray(out).ravel().tolist()

    def test_ragged_rows_per_shard_rejected(self):
        with pytest.raises(ValueError, match="not divisible"):
            mr.shuffle_by_key(
                jnp.zeros((5, 1)), jnp.zeros((5,), jnp.int32), "data", 2
            )


# ------------------------------------------------------------- ensemble ----

class TestDistributedEnsemble:
    def test_bagged_forest_local(self, blobs):
        x, y = blobs
        cfg = rf.RotationForestConfig(
            n_trees=2, n_subsets=3, depth=3, n_classes=2, n_bins=16
        )
        ens = ensemble.DistributedEnsemble(
            fit_fn=lambda k, xs, ys: rf.fit(k, xs, ys, cfg),
            predict_fn=rf.predict_proba,
        )
        members = ens.fit_local(4, jax.random.PRNGKey(0), x, y)
        # 4 members x 2 trees each
        assert members.rotation.shape[0] == 4
        acc = float(jnp.mean(ens.predict(members, x) == y))
        assert acc > 0.95

    def test_vote_probabilities_normalized(self, blobs):
        x, y = blobs
        cfg = rf.RotationForestConfig(
            n_trees=2, n_subsets=3, depth=3, n_classes=2, n_bins=16
        )
        ens = ensemble.DistributedEnsemble(
            fit_fn=lambda k, xs, ys: rf.fit(k, xs, ys, cfg),
            predict_fn=rf.predict_proba,
        )
        members = ens.fit_local(4, jax.random.PRNGKey(0), x, y)
        p = ens.predict_proba(members, x)
        np.testing.assert_allclose(np.asarray(p.sum(-1)), 1.0, atol=1e-4)
