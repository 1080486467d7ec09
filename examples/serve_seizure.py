"""Streaming seizure-scoring demo: continuous multi-patient sessions.

Trains a rotation forest on synthetic Freiburg-like EEG, freezes it into
a ``ScoringProgram``, then streams raw windows from several patients
through ``serving.SeizureEngine`` sessions. Pushes are NOT chunk-aligned
(the session assembles the paper's 60-window chunks itself), slots are
refilled mid-flight as sessions drain, and the k-of-m alarm rule runs
on-device inside the fused scoring step; typed events
(ChunkScored / AlarmRaised / AlarmCleared) come back from ``poll``.

  PYTHONPATH=src python examples/serve_seizure.py --patients 2 --batch 4
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core import rotation_forest as rf
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import AlarmRaised, ChunkScored, ScoringProgram, SeizureEngine
from repro.signal import eeg_data, pipeline


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--patients", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--hours-interictal", type=int, default=1)
    ap.add_argument("--push-windows", type=int, default=25,
                    help="windows per push (deliberately chunk-unaligned)")
    ap.add_argument("--save-dir", default=None,
                    help="optionally round-trip the ScoringProgram "
                         "through the checkpoint store")
    ap.add_argument("--use-forest-kernel", action="store_true",
                    help="Pallas forest traversal (interpret mode off-TPU)")
    ap.add_argument("--replay-depth", type=int, default=4,
                    help="backlogged chunks one engine step replays per "
                         "slot (catch-up bursts score up to this many "
                         "chunks per jitted dispatch)")
    ap.add_argument("--latency-budget", type=float, default=None,
                    help="seconds before a partial batch is flushed "
                         "anyway under poll(drain=False)")
    ap.add_argument("--overlap", type=int, default=0,
                    help="cross-chunk MSPCA halo windows (0 = the "
                         "paper's fully independent chunk denoise)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = pipeline.PipelineConfig(
        forest=rf.RotationForestConfig(
            n_trees=8, n_subsets=3, depth=5, n_classes=2, n_bins=16
        ),
        overlap=args.overlap,
    )

    # One forest serves all patients here (the paper trains per patient;
    # swap in per-patient programs + one engine per program).
    rec = eeg_data.make_training_set(jax.random.PRNGKey(0), 0, 60, 60)
    fitted = pipeline.fit(jax.random.PRNGKey(1), rec, cfg)
    program = ScoringProgram.from_fitted(fitted, cfg)
    if args.save_dir:
        path = program.save(args.save_dir)
        program = ScoringProgram.load(args.save_dir)
        print(f"round-tripped ScoringProgram through {path}")

    engine = SeizureEngine(
        program, max_batch=args.batch,
        replay_depth=args.replay_depth,
        latency_budget_s=args.latency_budget,
        use_forest_kernel=args.use_forest_kernel,
    )

    streams = {}
    for pid in range(args.patients):
        tl = eeg_data.make_test_timeline(
            jax.random.PRNGKey(100 + pid), pid,
            hours_interictal=args.hours_interictal, minutes_preictal=48,
        )
        streams[pid] = np.asarray(tl.windows)
        engine.open_session(pid)

    n_windows = sum(s.shape[0] for s in streams.values())
    print(f"serving {args.patients} patients, {n_windows} total 8s windows "
          f"(batch {args.batch}, pushes of {args.push_windows} windows)")
    t0 = time.time()
    scored = 0

    def handle(events) -> None:
        nonlocal scored
        for event in events:
            if isinstance(event, AlarmRaised):
                print(f"  *** ALARM *** patient {event.patient_id} "
                      f"at chunk {event.chunk_index} "
                      f"(t={event.chunk_index * 8}min)")
            elif isinstance(event, ChunkScored):
                scored += 1
                if event.chunk_pred:
                    print(f"  t={event.chunk_index * 8:4d}min "
                          f"patient {event.patient_id}: "
                          f"preictal_frac={event.preictal_frac:.2f} "
                          f"vote={event.chunk_pred} alarm={event.alarm}")

    # With a latency budget, defer partial batches (the budget bounds how
    # long a lone chunk can wait); without one, drain every poll.
    drain_each = args.latency_budget is None
    offset = 0
    while any(offset < s.shape[0] for s in streams.values()):
        for pid, wins in streams.items():
            engine.session(pid).push(wins[offset:offset + args.push_windows])
        offset += args.push_windows
        handle(engine.poll(drain=drain_each))
    handle(engine.poll())  # final drain of any deferred partial batch
    dt = time.time() - t0
    windows = scored * eeg_data.WINDOWS_PER_MATRIX
    print(f"scored {scored} chunks ({windows} windows) in {dt:.1f}s "
          f"-> {windows / dt:.0f} windows/s "
          f"({engine.steps} engine steps, replay depth {args.replay_depth})")
    for pid in streams:
        print(f"patient {pid}: final alarm state = {engine.alarm_state(pid)}")


if __name__ == "__main__":
    main()
