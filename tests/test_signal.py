"""Tests for the signal substrate: wavelets, MSPCA, features, EEG data,
and the end-to-end seizure pipeline."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import rotation_forest as rf
from repro.launch.mesh import make_data_mesh
from repro.signal import eeg_data, features, mspca, pipeline, wavelet


# ------------------------------------------------------------- wavelets ----

class TestWavelet:
    @pytest.mark.parametrize("name", ["db1", "db2", "db3", "db4"])
    def test_filter_orthonormality(self, name):
        h, g = wavelet.filters(name)
        L = h.shape[0]
        assert float(jnp.sum(h * h)) == pytest.approx(1.0, abs=1e-6)
        assert float(jnp.sum(g * g)) == pytest.approx(1.0, abs=1e-6)
        assert float(jnp.sum(h * g)) == pytest.approx(0.0, abs=1e-6)
        for m in range(1, L // 2):
            assert float(jnp.sum(h[: L - 2 * m] * h[2 * m :])) == pytest.approx(
                0.0, abs=1e-6
            ), (name, m)

    @pytest.mark.parametrize("name", ["db1", "db2", "db4"])
    def test_perfect_reconstruction_step(self, name):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 128))
        a, d = wavelet.analysis_step(x, name)
        assert a.shape == d.shape == (4, 64)
        xr = wavelet.synthesis_step(a, d, name)
        np.testing.assert_allclose(np.asarray(xr), np.asarray(x), atol=1e-5)

    @pytest.mark.parametrize("name", ["db1", "db2", "db3", "db4"])
    def test_polyphase_synthesis_matches_scatter_reference(self, name):
        # The polyphase gather form and the longhand scatter-add
        # transpose are the same linear operator; they may differ only
        # in float32 summation order (a few ulp on unit-scale input).
        key = jax.random.PRNGKey(3)
        a = jax.random.normal(key, (2, 5, 64))
        d = jax.random.normal(jax.random.PRNGKey(4), (2, 5, 64))
        fast = wavelet.synthesis_step(a, d, name)
        ref = wavelet.synthesis_step_reference(a, d, name)
        np.testing.assert_allclose(
            np.asarray(fast), np.asarray(ref), atol=1e-5, rtol=1e-5
        )

    def test_idwt_reference_flag_routes_scatter_path(self):
        x = jax.random.normal(jax.random.PRNGKey(5), (3, 256))
        coeffs = wavelet.dwt(x, 4, "db4")
        fast = wavelet.idwt(coeffs, "db4")
        ref = wavelet.idwt(coeffs, "db4", reference=True)
        # Both are (near-)perfect inverses; cross-difference stays at
        # summation-order noise.
        np.testing.assert_allclose(np.asarray(ref), np.asarray(x), atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(fast), np.asarray(ref), atol=1e-5, rtol=1e-5
        )

    def test_perfect_reconstruction_multilevel(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (3, 256))
        coeffs = wavelet.dwt(x, 5, "db4")
        assert len(coeffs) == 6
        assert coeffs[-1].shape == (3, 8)
        xr = wavelet.idwt(coeffs, "db4")
        np.testing.assert_allclose(np.asarray(xr), np.asarray(x), atol=1e-5)

    def test_wpd_shapes_and_reconstruction(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 256))
        nodes = wavelet.wpd(x, 3, "db4")
        assert nodes.shape == (2, 8, 32)
        xr = wavelet.wpd_reconstruct(nodes, "db4")
        np.testing.assert_allclose(np.asarray(xr), np.asarray(x), atol=1e-5)

    def test_wpd_energy_conservation(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 512))
        nodes = wavelet.wpd(x, 4, "db4")
        np.testing.assert_allclose(
            float(jnp.sum(nodes**2)), float(jnp.sum(x**2)), rtol=1e-4
        )

    def test_wpd_counts_match_paper(self):
        # Sec 2.2: k-level WPD -> 2**k coefficient sets; DWT -> k+1.
        x = jax.random.normal(jax.random.PRNGKey(4), (1, 256))
        for k in (1, 2, 3, 4):
            assert wavelet.wpd(x, k).shape[-2] == 2**k
            assert len(wavelet.dwt(x, k)) == k + 1

    def test_dwt_lowpass_captures_low_freq(self):
        t = jnp.arange(512) / 256.0
        slow = jnp.sin(2 * jnp.pi * 2.0 * t)[None]
        coeffs = wavelet.dwt(slow, 4, "db4")
        detail_energy = sum(float(jnp.sum(c**2)) for c in coeffs[:-1])
        approx_energy = float(jnp.sum(coeffs[-1] ** 2))
        assert approx_energy > 10 * detail_energy


# ---------------------------------------------------------------- MSPCA ----

class TestMSPCA:
    def _noisy_lowrank(self, key, n=256, p=12, noise=1.0):
        k1, k2, k3 = jax.random.split(key, 3)
        t = jnp.arange(n) / 256.0
        basis = jnp.stack(
            [jnp.sin(2 * jnp.pi * 10 * t), jnp.sin(2 * jnp.pi * 6 * t + 1.0)]
        )  # (2, N)
        mix = jax.random.normal(k1, (2, p))
        clean = (basis.T @ mix).astype(jnp.float32)
        noisy = clean + noise * jax.random.normal(k2, (n, p))
        return clean, noisy

    def test_denoise_improves_snr(self):
        clean, noisy = self._noisy_lowrank(jax.random.PRNGKey(0))
        # keep = true rank of the clean subspace
        den = mspca.denoise(noisy, level=4, keep=2)
        snr_before = float(mspca.snr_db(clean, noisy))
        snr_after = float(mspca.snr_db(clean, den))
        assert snr_after > snr_before + 3.0  # at least 3 dB win

    def test_denoise_preserves_shape_and_finite(self):
        _, noisy = self._noisy_lowrank(jax.random.PRNGKey(1))
        den = mspca.denoise(noisy)
        assert den.shape == noisy.shape
        assert bool(jnp.isfinite(den).all())

    def test_reference_kernels_path_is_equal_up_to_fp_order(self):
        _, noisy = self._noisy_lowrank(jax.random.PRNGKey(4))
        fast = mspca.denoise(noisy, level=4, keep=2)
        ref = mspca.denoise(noisy, level=4, keep=2, reference_kernels=True)
        np.testing.assert_allclose(
            np.asarray(fast), np.asarray(ref), atol=1e-5, rtol=1e-5
        )

    def test_kaiser_mode_runs(self):
        _, noisy = self._noisy_lowrank(jax.random.PRNGKey(2))
        den = mspca.denoise(noisy, keep="kaiser", threshold=True, final_pca=True)
        assert bool(jnp.isfinite(den).all())

    def test_keep_all_threshold_off_is_near_identity(self):
        _, noisy = self._noisy_lowrank(jax.random.PRNGKey(3))
        den = mspca.denoise(noisy, keep=12, threshold=False, final_pca=False)
        np.testing.assert_allclose(np.asarray(den), np.asarray(noisy), atol=1e-3)


# ------------------------------------------------------------- features ----

class TestFeatures:
    def test_shapes(self):
        wins = jax.random.normal(jax.random.PRNGKey(0), (10, 3, 512))
        f = features.wpd_features(wins, level=3)
        assert f.shape == (10, features.feature_dim(3, 3))

    def test_finite_on_constant_signal(self):
        wins = jnp.ones((4, 3, 256))
        f = features.wpd_features(wins, level=2)
        assert bool(jnp.isfinite(f).all())

    def test_normalize_roundtrip(self):
        feats = jax.random.normal(jax.random.PRNGKey(1), (50, 8)) * 5 + 3
        normed, mean, std = features.normalize(feats)
        np.testing.assert_allclose(np.asarray(normed.mean(0)), 0.0, atol=1e-4)
        np.testing.assert_allclose(np.asarray(normed.std(0)), 1.0, atol=1e-2)
        normed2, _, _ = features.normalize(feats, mean, std)
        np.testing.assert_allclose(np.asarray(normed2), np.asarray(normed))

    def test_discriminates_states(self):
        # Preictal windows must differ from interictal in feature space.
        ki, kp = jax.random.split(jax.random.PRNGKey(2))
        inter = eeg_data.generate_windows(ki, jnp.asarray(3), eeg_data.INTERICTAL, 16)
        pre = eeg_data.generate_windows(kp, jnp.asarray(3), eeg_data.PREICTAL, 16)
        fi = features.wpd_features(inter, level=4)
        fp = features.wpd_features(pre, level=4)
        gap = jnp.abs(fi.mean(0) - fp.mean(0)) / (fi.std(0) + fp.std(0) + 1e-6)
        assert float(gap.max()) > 1.0  # at least one strongly separating feature


# ------------------------------------------------------------- EEG data ----

class TestEEGData:
    def test_shapes_and_dtype(self):
        w = eeg_data.generate_windows(
            jax.random.PRNGKey(0), jnp.asarray(1), eeg_data.INTERICTAL, 8
        )
        assert w.shape == (8, eeg_data.N_CHANNELS, eeg_data.WINDOW)
        assert w.dtype == jnp.float32
        assert bool(jnp.isfinite(w).all())

    def test_patients_differ(self):
        k = jax.random.PRNGKey(0)
        w3 = eeg_data.generate_windows(k, jnp.asarray(3), eeg_data.INTERICTAL, 4)
        w10 = eeg_data.generate_windows(k, jnp.asarray(10), eeg_data.INTERICTAL, 4)
        assert float(jnp.abs(w3 - w10).max()) > 1.0

    def test_ictal_has_higher_amplitude(self):
        k = jax.random.PRNGKey(1)
        inter = eeg_data.generate_windows(k, jnp.asarray(3), eeg_data.INTERICTAL, 8)
        ict = eeg_data.generate_windows(k, jnp.asarray(3), eeg_data.ICTAL, 8)
        assert float(jnp.std(ict)) > 1.5 * float(jnp.std(inter))

    def test_training_set_balanced(self):
        rec = eeg_data.make_training_set(
            jax.random.PRNGKey(0), 3, n_interictal_windows=20, n_preictal_windows=20
        )
        assert rec.windows.shape[0] == 40
        assert int(rec.labels.sum()) == 20

    def test_timeline_ordering(self):
        rec = eeg_data.make_test_timeline(
            jax.random.PRNGKey(0), 3, hours_interictal=1, minutes_preictal=16
        )
        # interictal block first (labels 0), then preictal/ictal (labels 1)
        first_one = int(jnp.argmax(rec.labels))
        assert int(rec.labels[:first_one].sum()) == 0
        assert int(rec.labels[first_one:].prod()) == 1


# ------------------------------------------------------------- pipeline ----

@pytest.fixture(scope="module")
def small_cfg():
    return pipeline.PipelineConfig(
        forest=rf.RotationForestConfig(
            n_trees=6, n_subsets=3, depth=5, n_classes=2, n_bins=16
        )
    )


@pytest.fixture(scope="module")
def fitted_p3(small_cfg):
    rec = eeg_data.make_training_set(
        jax.random.PRNGKey(42), 3, n_interictal_windows=60, n_preictal_windows=60
    )
    return pipeline.fit(jax.random.PRNGKey(1), rec, small_cfg), rec


class TestPipeline:
    def test_training_accuracy_matches_paper_band(self, fitted_p3, small_cfg):
        # Paper Table 1: 89-99% training accuracy.
        fitted, rec = fitted_p3
        preds = pipeline.predict_windows(fitted, rec.windows, small_cfg)
        acc = float(jnp.mean(preds == rec.labels))
        assert acc > 0.89

    def test_generalizes_to_fresh_interictal(self, fitted_p3, small_cfg):
        fitted, _ = fitted_p3
        fresh = eeg_data.generate_windows(
            jax.random.PRNGKey(99), jnp.asarray(3), eeg_data.INTERICTAL, 60
        )
        fp = float(pipeline.predict_windows(fitted, fresh, small_cfg).mean())
        assert fp < 0.3

    def test_chunk_aggregation(self, small_cfg):
        wp = jnp.concatenate(
            [jnp.zeros((60,), jnp.int32), jnp.ones((60,), jnp.int32)]
        )
        chunks = pipeline.chunk_predictions(wp, small_cfg)
        assert chunks.shape == (2,)
        assert chunks.tolist() == [0, 1]

    def test_alarm_rule_3_of_5(self, small_cfg):
        chunks = jnp.asarray([0, 1, 0, 1, 1, 0, 0, 0, 0], jnp.int32)
        alarms = pipeline.alarm_state(chunks, small_cfg)
        # at index 4 the last five are [0,1,0,1,1] -> 3 hits -> alarm
        assert alarms[4] == 1
        # early positions lack 3 hits
        assert alarms[0] == 0 and alarms[1] == 0
        # alarm decays once hits leave the window
        assert alarms[8] == 0

    def test_alarm_state_matches_stacked_reference(self, small_cfg):
        # The rolling-sum (lagged cumsum) alarm_state must be
        # bit-identical to the historical stacked-shifted-copies
        # formulation for every stream length and (k, m).
        def stacked_oracle(chunk_preds, m, k):
            padded = jnp.concatenate(
                [jnp.zeros((m - 1,), jnp.int32), chunk_preds]
            )
            windows = jnp.stack(
                [padded[i : i + chunk_preds.shape[0]] for i in range(m)]
            )
            return (jnp.sum(windows, axis=0) >= k).astype(jnp.int32)

        rng = np.random.RandomState(0)
        for n in (1, 2, 4, 5, 9, 37):
            for m, k in ((5, 3), (3, 2), (1, 1), (7, 7)):
                cfg = small_cfg._replace(alarm_m=m, alarm_k=k)
                preds = jnp.asarray(rng.randint(0, 2, size=n), jnp.int32)
                np.testing.assert_array_equal(
                    np.asarray(pipeline.alarm_state(preds, cfg)),
                    np.asarray(stacked_oracle(preds, m, k)),
                )

    def test_timeline_alarm_before_seizure(self, fitted_p3, small_cfg):
        fitted, _ = fitted_p3
        test = eeg_data.make_test_timeline(
            jax.random.PRNGKey(7), 3, hours_interictal=1, minutes_preictal=48
        )
        res = pipeline.evaluate_timeline(fitted, test, small_cfg)
        assert float(res.lead_time_minutes) > 0  # alarm fired before onset
        # no alarm during the first interictal hour (7 full chunks)
        assert int(res.alarms[:6].sum()) == 0
        # onset chunk = start of the labeled preictal run-up (hour 1 of
        # interictal = 7.5 chunks -> first majority-preictal chunk is 8)
        assert int(res.onset_chunk) == 8
        # the reported lead equals the helper applied to the outputs
        want = pipeline.lead_time_from_alarms(
            res.alarms, pipeline.chunk_predictions(test.labels, small_cfg)
        )
        assert float(res.lead_time_minutes) == float(want)


    def test_process_windows_shorter_than_one_chunk(self, small_cfg):
        # Regression: recordings with w < WINDOWS_PER_MATRIX (pad > w)
        # used to crash the wrap-padding reshape in process_windows; the
        # cyclic tiling must fill a whole denoising matrix from any w.
        wins = eeg_data.generate_windows(
            jax.random.PRNGKey(11), jnp.asarray(3), eeg_data.INTERICTAL, 10
        )
        feats = pipeline.process_windows(wins, small_cfg)
        assert feats.shape[0] == 10
        assert bool(jnp.isfinite(feats).all())

    def test_short_recording_wrap_equals_concat_padding(self, small_cfg):
        # For pad <= w the tiling must reproduce the original
        # concatenate([windows, windows[:pad]]) wrap exactly.
        wins = eeg_data.generate_windows(
            jax.random.PRNGKey(12), jnp.asarray(3), eeg_data.INTERICTAL, 70
        )
        per = eeg_data.WINDOWS_PER_MATRIX
        w, c, n = wins.shape
        tiled = jnp.resize(wins, (2 * per, c, n))
        concat = jnp.concatenate([wins, wins[: 2 * per - w]], axis=0)
        np.testing.assert_array_equal(np.asarray(tiled), np.asarray(concat))

    def test_mapreduce_features_match_serial(self, small_cfg):
        wins = eeg_data.generate_windows(
            jax.random.PRNGKey(5), jnp.asarray(3), eeg_data.INTERICTAL, 8
        )
        serial = pipeline.process_windows(wins, small_cfg._replace(denoise=False))
        mesh = make_data_mesh(1)
        cfgn = small_cfg._replace(denoise=False)
        rec = eeg_data.Recording(windows=wins, labels=jnp.zeros((8,), jnp.int32))
        dist = pipeline.process_recording_mapreduce(mesh, rec, cfgn)
        np.testing.assert_allclose(
            np.asarray(dist), np.asarray(serial), rtol=1e-5, atol=1e-5
        )


class TestLeadTimeSemantics:
    """Pins the lead-time convention: the stream ends AT the seizure
    (end-of-stream = ictal onset, the paper's Figs. 3-10 protocol), and
    only alarms at/after the preictal onset chunk are predictions --
    earlier alarms are false positives and earn no credit. Regression
    for the dead-``onset_chunk`` bug, where lead time was measured from
    the first alarm EVER, crediting false alarms with up to the whole
    interictal span."""

    def test_alarm_at_onset_measured_to_stream_end(self):
        true = jnp.asarray([0] * 5 + [1] * 5, jnp.int32)
        alarms = jnp.asarray([0] * 5 + [1] * 5, jnp.int32)
        # onset chunk 5 of 10: 5 chunks x 8 min of warning
        assert float(pipeline.lead_time_from_alarms(alarms, true)) == 40.0

    def test_late_alarm_shrinks_lead(self):
        true = jnp.asarray([0] * 5 + [1] * 5, jnp.int32)
        alarms = jnp.asarray([0] * 8 + [1, 1], jnp.int32)
        assert float(pipeline.lead_time_from_alarms(alarms, true)) == 16.0

    def test_false_alarm_before_onset_not_credited(self):
        true = jnp.asarray([0] * 5 + [1] * 5, jnp.int32)
        alarms = jnp.asarray([1, 1, 0, 0, 0, 0, 0, 0, 0, 0], jnp.int32)
        # pre-fix semantics credited this with (10 - 0) * 8 = 80 minutes
        assert float(pipeline.lead_time_from_alarms(alarms, true)) == -1.0

    def test_persistent_alarm_counts_from_onset(self):
        true = jnp.asarray([0] * 5 + [1] * 5, jnp.int32)
        alarms = jnp.ones((10,), jnp.int32)  # alarming since chunk 0
        # credit starts at the onset chunk, not at the false-alarm start
        assert float(pipeline.lead_time_from_alarms(alarms, true)) == 40.0

    def test_no_alarms_is_negative(self):
        true = jnp.asarray([0] * 5 + [1] * 5, jnp.int32)
        alarms = jnp.zeros((10,), jnp.int32)
        assert float(pipeline.lead_time_from_alarms(alarms, true)) == -1.0

    def test_no_onset_is_negative(self):
        # all-interictal stream: nothing to predict, whatever alarmed
        true = jnp.zeros((10,), jnp.int32)
        alarms = jnp.ones((10,), jnp.int32)
        assert float(pipeline.lead_time_from_alarms(alarms, true)) == -1.0
