"""Distributed train -> serve driver: the paper's full loop in one command.

Trains a rotation forest MapReduce-style on the synthetic Freiburg
stand-ins (each shard denoises + featurizes + fits a sub-forest; global
feature moments combined across shards; union reduce), freezes it into a
``ScoringProgram`` through the checkpoint store, loads it back, and
streams a held-out chronological timeline through a ``SeizureEngine``
session -- asserting the served alarms match the offline
``pipeline.evaluate_timeline`` oracle.

  PYTHONPATH=src python -m repro.launch.train_forest --patient 3 \
      --shards 2 --save-dir /tmp/seizure_ckpt [--devices 2] [--trees 8]

``--shards S`` uses the single-device emulation (bit-identical to an
S-device mesh); ``--devices N`` runs the REAL ``shard_map`` job on a data
mesh of the first N devices instead. On the CPU backend it forces N host
devices (must be the first jax touch of the process, so it is set before
any jax import); on a TPU host the mesh takes N chips.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--patient", type=int, default=3)
    ap.add_argument("--shards", type=int, default=2,
                    help="map tasks (one-device emulation unless --devices)")
    ap.add_argument("--devices", type=int, default=0,
                    help="run the real shard_map job on N devices, forced "
                         "host devices on the CPU backend (0 = emulate "
                         "--shards on one device)")
    ap.add_argument("--trees", type=int, default=8)
    ap.add_argument("--depth", type=int, default=5)
    ap.add_argument("--bins", type=int, default=16)
    ap.add_argument("--train-chunks", type=int, default=4,
                    help="8-minute training chunks (half interictal, "
                         "half preictal); must shard evenly")
    ap.add_argument("--hours-interictal", type=int, default=1,
                    help="held-out interictal hours before the run-up")
    ap.add_argument("--batch", type=int, default=4,
                    help="SeizureEngine slots for the serve phase")
    ap.add_argument("--replay-depth", type=int, default=4,
                    help="backlogged chunks one engine step replays per "
                         "slot (the in-step lax.scan depth; 1 = PR-3 "
                         "chunk-per-step schedule)")
    ap.add_argument("--latency-budget", type=float, default=None,
                    help="seconds before poll(drain=False) flushes a "
                         "partial batch (default: drain fully each poll)")
    ap.add_argument("--overlap", type=int, default=0,
                    help="cross-chunk MSPCA halo windows: each denoise "
                         "matrix is extended with this many raw windows "
                         "from the previous chunk (0 = the paper's fully "
                         "independent chunks)")
    ap.add_argument("--save-dir", default=None,
                    help="ScoringProgram checkpoint dir (default: tmp)")
    ap.add_argument("--use-hist-kernel", action="store_true",
                    help="Pallas histogram grower (interpret off-TPU)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.devices > 0:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", "")
        )

    import jax
    import numpy as np

    from repro.core import rotation_forest as rf
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_data_mesh
    from repro.serving import ChunkScored, ScoringProgram, SeizureEngine
    from repro.signal import eeg_data, pipeline

    enable_compile_cache()

    per = eeg_data.WINDOWS_PER_MATRIX
    cfg = pipeline.PipelineConfig(
        forest=rf.RotationForestConfig(
            n_trees=args.trees, n_subsets=3, depth=args.depth,
            n_classes=2, n_bins=args.bins,
            use_hist_kernel=args.use_hist_kernel,
        ),
        overlap=args.overlap,
    )

    # ---- map/reduce training on the synthetic Freiburg stand-ins --------
    half = args.train_chunks * per // 2
    rec = eeg_data.make_training_set(
        jax.random.PRNGKey(args.seed), args.patient,
        n_interictal_windows=half, n_preictal_windows=half,
    )
    # Interleave interictal/preictal chunks so every contiguous map
    # shard is class-balanced (a single-class shard grows constant trees).
    rec = eeg_data.stratify_chunks(rec)
    if args.devices > 0:
        mesh = make_data_mesh(args.devices)
        shards, fit_kwargs = args.devices, {"mesh": mesh}
    else:
        shards, fit_kwargs = args.shards, {"n_shards": args.shards}
    t0 = time.time()
    fitted = pipeline.fit(
        jax.random.PRNGKey(args.seed + 1), rec, cfg, **fit_kwargs
    )
    jax.block_until_ready(fitted)
    n_trees = fitted.forest.rotation.shape[0]
    print(f"[train] {rec.windows.shape[0]} windows over {shards} map "
          f"shards -> union forest of {n_trees} trees "
          f"in {time.time() - t0:.1f}s "
          f"({'shard_map mesh' if args.devices > 0 else 'emulation'})")

    # ---- freeze + round-trip through the checkpoint store ---------------
    save_dir = args.save_dir or tempfile.mkdtemp(prefix="seizure_ckpt_")
    path = ScoringProgram.from_fitted(fitted, cfg).save(save_dir)
    program = ScoringProgram.load(save_dir)
    print(f"[ckpt]  ScoringProgram saved + reloaded from {path}")

    # ---- serve a held-out stream through the engine ---------------------
    timeline = eeg_data.make_test_timeline(
        jax.random.PRNGKey(args.seed + 2), args.patient,
        hours_interictal=args.hours_interictal,
    )
    wins = np.asarray(timeline.windows)
    engine = SeizureEngine(
        program, max_batch=args.batch, replay_depth=args.replay_depth,
        latency_budget_s=args.latency_budget,
    )
    session = engine.open_session(args.patient)
    events, t0 = [], time.time()
    drain_each = args.latency_budget is None
    for i in range(0, wins.shape[0], 37):  # deliberately chunk-unaligned
        session.push(wins[i : i + 37])
        events += engine.poll(drain=drain_each)
    events += engine.poll()
    dt = time.time() - t0
    scored = [e for e in events if isinstance(e, ChunkScored)]
    for e in scored:
        flag = " *** ALARM ***" if e.alarm else ""
        print(f"[serve] chunk {e.chunk_index:3d}: pred={e.chunk_pred} "
              f"frac={e.preictal_frac:.2f}{flag}")
    print(f"[serve] {wins.shape[0]} windows in {dt:.1f}s "
          f"({wins.shape[0] / dt:.1f} windows/s, {engine.steps} engine "
          f"steps at replay depth {args.replay_depth}), "
          f"final alarm={engine.alarm_state(args.patient)}")

    # ---- the loaded program must reproduce the offline oracle -----------
    res = pipeline.evaluate_timeline(fitted, timeline, cfg)
    want_alarms = np.asarray(res.alarms).tolist()
    got_alarms = [e.alarm for e in scored]
    if got_alarms != want_alarms:
        print("[check] FAIL: served alarms diverge from pipeline oracle")
        sys.exit(1)
    print(f"[check] served alarms == pipeline oracle "
          f"({sum(got_alarms)} alarm chunks); "
          f"lead time {float(res.lead_time_minutes):.0f} min "
          f"(onset chunk {int(res.onset_chunk)})")

    # ---- retrain on fresh shards -> hot-swap into the LIVE engine -------
    # The paper's continuous-retraining loop closed: a new MapReduce fit
    # lands in the serving engine through swap_program -- no session
    # drain, no step recompile -- and the alarms it serves from that
    # point match the NEW program's pipeline oracle.
    from repro.analysis.sanitizers import CompileCounter

    rec2 = eeg_data.stratify_chunks(eeg_data.make_training_set(
        jax.random.PRNGKey(args.seed + 3), args.patient,
        n_interictal_windows=half, n_preictal_windows=half,
    ))
    t0 = time.time()
    fitted2 = pipeline.fit(
        jax.random.PRNGKey(args.seed + 4), rec2, cfg, **fit_kwargs
    )
    jax.block_until_ready(fitted2)
    ScoringProgram.from_fitted(fitted2, cfg).save(save_dir, step=1)
    program2 = ScoringProgram.load(save_dir)  # latest step = the retrain
    print(f"[retrain] fresh shards -> new forest in {time.time() - t0:.1f}s, "
          f"checkpointed as step 1")

    n_chunks = wins.shape[0] // per
    k_swap = max(1, n_chunks // 2)
    session2 = engine.open_session(args.patient + 1000)
    session2.push(wins[: k_swap * per])
    events2 = engine.poll()  # k_swap chunks under the OLD program
    t0 = time.time()
    with CompileCounter() as cc:
        version = engine.swap_program(program2)
        for i in range(k_swap * per, n_chunks * per, 37):
            session2.push(wins[i : i + min(37, n_chunks * per - i)])
            events2 += engine.poll()
        events2 += engine.poll()
    swap_ms = (time.time() - t0) * 1e3
    scored2 = [e for e in events2 if isinstance(e, ChunkScored)]
    versions = [e.program_version for e in scored2]
    if cc.total != 0:
        print(f"[swap] FAIL: swap + post-swap serving recompiled "
              f"{cc.total}x ({cc.by_name})")
        sys.exit(1)
    if versions != [0] * k_swap + [version] * (n_chunks - k_swap):
        print(f"[swap] FAIL: program_version stamps wrong: {versions}")
        sys.exit(1)

    # Composite oracle: chunk votes depend only on the serving program
    # (alarm state is downstream), so the expected alarm sequence is the
    # k-of-m rule over old-program votes up to the swap and new-program
    # votes after -- both taken from the per-program pipeline oracles.
    res2 = pipeline.evaluate_timeline(fitted2, timeline, cfg)
    combined = np.concatenate([
        np.asarray(res.chunk_preds)[:k_swap],
        np.asarray(res2.chunk_preds)[k_swap:n_chunks],
    ])
    want2 = np.asarray(
        pipeline.alarm_state(jax.numpy.asarray(combined), cfg)
    ).tolist()
    got2 = [e.alarm for e in scored2]
    if got2 != want2:
        print("[swap] FAIL: post-swap served alarms diverge from the "
              "composite old/new pipeline oracle")
        sys.exit(1)
    changed = int(np.sum(
        np.asarray(res.chunk_preds)[:n_chunks]
        != np.asarray(res2.chunk_preds)[:n_chunks]
    ))
    print(f"[swap] v{version} live after chunk {k_swap}/{n_chunks}: "
          f"0 recompiles, swap+serve tail in {swap_ms:.0f} ms, served "
          f"alarms == composite oracle ({changed} chunk votes differ "
          f"between programs)")


if __name__ == "__main__":
    main()
