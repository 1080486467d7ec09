"""End-to-end driver: the PAPER's full pipeline, patient-by-patient.

Synthetic Freiburg-like EEG (the database is access-gated) -> MSPCA
denoising -> WPD features -> MapReduce-distributed Rotation Forest ->
8-minute chunk votes -> the 3-of-5 alarm rule -> lead-time report.

This is the paper's experiment reproduced on its own terms (Tables 1, 2,
Figs 3-10); EXPERIMENTS.md §Paper-validation records the outcomes.

  PYTHONPATH=src python examples/eeg_seizure_prediction.py --patient 3
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.eeg_paper import CONFIG
from repro.launch.mesh import make_data_mesh
from repro.signal import eeg_data, pipeline


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--patient", type=int, default=3)
    ap.add_argument("--hours-interictal", type=int, default=1)
    ap.add_argument("--train-windows", type=int, default=120)
    args = ap.parse_args()

    key = jax.random.PRNGKey(args.patient)
    k_train, k_fit, k_test = jax.random.split(key, 3)

    # --- training set (paper Sec 2.6: 15h interictal + preictal records) ---
    rec = eeg_data.make_training_set(
        k_train, args.patient,
        n_interictal_windows=args.train_windows,
        n_preictal_windows=args.train_windows)
    print(f"[eeg] patient {args.patient}: {rec.windows.shape[0]} train "
          f"windows of {rec.windows.shape[2]} samples x "
          f"{rec.windows.shape[1]} channels")

    # --- signal processing as a MapReduce job (the paper's map phase) ----
    t0 = time.time()
    mesh = make_data_mesh(1)
    feats = pipeline.process_recording_mapreduce(mesh, rec, CONFIG)
    print(f"[eeg] MapReduce signal processing: {feats.shape} features "
          f"in {time.time() - t0:.1f}s")

    # --- train rotation forest, report training accuracy (Table 1) -------
    fitted = pipeline.fit(k_fit, rec, CONFIG)
    preds = pipeline.predict_windows(fitted, rec.windows, CONFIG)
    acc = float(jnp.mean((preds == rec.labels).astype(jnp.float32)))
    print(f"[eeg] training accuracy: {acc * 100:.2f}% (paper: 89.85-99.87%)")

    # --- real-time test timeline (Figs 3-10) ------------------------------
    test = eeg_data.make_test_timeline(
        k_test, args.patient, hours_interictal=args.hours_interictal)
    result = pipeline.evaluate_timeline(fitted, test, CONFIG)
    chunks = result.chunk_preds.tolist()
    alarms = result.alarms.tolist()
    print("[eeg] chunk predictions (8 min each): " +
          "".join(str(c) for c in chunks))
    print("[eeg] alarm state              : " +
          "".join(str(a) for a in alarms))
    lead = float(result.lead_time_minutes)
    if lead >= 0:
        print(f"[eeg] ALARM {lead:.0f} minutes before seizure onset "
              "(paper: 30-70 min)")
    else:
        print("[eeg] no alarm raised (paper patient 14 case)")


if __name__ == "__main__":
    main()
