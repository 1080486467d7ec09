#!/usr/bin/env python3
"""Bring-up check: train -> freeze -> serve the paper's seizure deployment
on a TPU, through the entry points a user calls, and compare what is
served with a plain reference computed on the same chip.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # the 4-chip MapReduce path only

One chip (the default):
  * train  -- ``pipeline.fit(..., n_shards=4)``, the one-chip form of the
    MapReduce fit, on the paper's training set (15 h interictal + 48 min
    preictal, rounded to 7200 windows so that 4 shards hold whole 60-window
    chunks), generated from ``--seed``;
  * freeze -- ``ScoringProgram.from_fitted(...).save`` then ``load``;
  * serve  -- a ``SeizureEngine`` with 64 slots and replay depth 4; 64
    sessions each push a held-out timeline in chunk-unaligned pieces and
    the engine is polled until the backlog is drained;
  * check  -- every session's chunk votes and alarms must equal the plain
    reference ``predict_windows -> chunk_predictions -> alarm_state``.

``--chips 4`` runs only what exists across chips: the mesh fit against
the ``n_shards=4`` fit on one chip (bit-identical forests), and a
mesh-sharded engine serving the fleet against the unsharded engine
(identical chunk votes, alarms and alarm events; window-level
disagreements are counted: the sharded step is a different XLA program,
and a window on a split threshold can flip, as between the engine and
the reference on one chip).

The last line of stdout is one JSON object naming the device; any failure
exits non-zero before it is printed. The script refuses to run where JAX
finds no TPU: a CPU fallback would say nothing about the chip.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time
from typing import NamedTuple

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

PATIENT = 3


class Sizes(NamedTuple):
    """Data and fleet sizes of one run (``FULL`` is the paper's)."""

    interictal_windows: int = 6840   # 15 h of 8-s windows, rounded to chunks
    preictal_windows: int = 360      # the 48-minute preictal record
    shards: int = 4
    sessions: int = 64
    max_batch: int = 64
    replay_depth: int = 4
    hours_interictal: int = 1        # per held-out session timeline


FULL = Sizes()


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def training_set(seed: int, sizes: Sizes):
    import jax

    from repro.signal import eeg_data

    rec = eeg_data.make_training_set(
        jax.random.PRNGKey(seed), PATIENT,
        n_interictal_windows=sizes.interictal_windows,
        n_preictal_windows=sizes.preictal_windows,
    )
    # Whole chunks interleaved by class, so that every contiguous map
    # shard holds preictal chunks too.
    return eeg_data.stratify_chunks(rec)


def fleet_timelines(seed: int, sizes: Sizes):
    """One held-out chronological timeline (interictal hours, the preictal
    run-up, the seizure) per session, as host arrays."""
    import jax
    import numpy as np

    from repro.signal import eeg_data

    key = jax.random.PRNGKey(seed + 2)
    return [
        np.asarray(eeg_data.make_test_timeline(
            jax.random.fold_in(key, i), PATIENT,
            hours_interictal=sizes.hours_interictal,
        ).windows)
        for i in range(sizes.sessions)
    ]


def fit(seed: int, rec, cfg, **mode):
    import jax

    from repro.signal import pipeline

    t0 = time.perf_counter()
    fitted = jax.block_until_ready(
        pipeline.fit(jax.random.PRNGKey(seed + 1), rec, cfg, **mode)
    )
    return fitted, time.perf_counter() - t0


def freeze(fitted, cfg, directory: pathlib.Path):
    from repro.serving import ScoringProgram

    if directory.exists():
        shutil.rmtree(directory)
    path = ScoringProgram.from_fitted(fitted, cfg).save(str(directory))
    return ScoringProgram.load(str(directory)), path


def serve(program, fleet, sizes: Sizes, *, mesh=None):
    """Push every session's timeline in chunk-unaligned pieces (a different
    piece size per session, so that backlogs of 1 to 4 chunks build up and
    the replay depth is used), polling after each round, until drained.
    Returns the engine and its events."""
    from repro.serving import SeizureEngine

    engine = SeizureEngine(
        program, max_batch=sizes.max_batch,
        replay_depth=sizes.replay_depth, mesh=mesh,
    )
    sessions = [engine.open_session(i) for i in range(len(fleet))]
    pieces = [37 + 30 * (i % 8) for i in range(len(fleet))]
    pos = [0] * len(fleet)
    events: list = []
    t0 = time.perf_counter()
    while any(p < len(w) for p, w in zip(pos, fleet)):
        for i, session in enumerate(sessions):
            if pos[i] < len(fleet[i]):
                session.push(fleet[i][pos[i]:pos[i] + pieces[i]])
                pos[i] += pieces[i]
        events += engine.poll()
    events += engine.poll()
    return engine, events, time.perf_counter() - t0


def reference(fitted, fleet, cfg):
    """The plain per-session reference: ``predict_windows`` over each
    whole timeline, then ``chunk_predictions`` (which drops the trailing
    partial chunk, as the engine leaves it unscored) and ``alarm_state``.
    It shares no code with the engine's step or scheduler."""
    import jax.numpy as jnp
    import numpy as np

    from repro.signal import pipeline

    out = []
    for wins in fleet:
        preds = pipeline.predict_windows(fitted, jnp.asarray(wins), cfg)
        chunks = pipeline.chunk_predictions(preds, cfg)
        alarms = pipeline.alarm_state(chunks, cfg)
        out.append(tuple(np.asarray(a) for a in (preds, chunks, alarms)))
    return out


def served_by_session(events, n_sessions: int):
    import numpy as np

    from repro.serving import ChunkScored

    by = {i: [] for i in range(n_sessions)}
    for e in events:
        if isinstance(e, ChunkScored):
            by[e.patient_id].append(e)
    out = []
    for i in range(n_sessions):
        scored = by[i]
        check(
            [e.chunk_index for e in scored] == list(range(len(scored))),
            f"session {i}: chunks scored out of order",
        )
        out.append((
            np.concatenate([e.window_preds for e in scored]) if scored
            else np.zeros((0,), np.int32),
            np.asarray([e.chunk_pred for e in scored], np.int32),
            np.asarray([e.alarm for e in scored], np.int32),
        ))
    return out


def compare_with_reference(served, ref) -> tuple[int, int, int]:
    """Chunk votes and alarms must be equal for every session. Returns
    (window-level disagreements, windows compared, sessions alarmed)."""
    import numpy as np

    disagree = total = alarmed = 0
    for i, ((s_win, s_chunk, s_alarm), (r_win, r_chunk, r_alarm)) in (
        enumerate(zip(served, ref))
    ):
        check(s_chunk.shape == r_chunk.shape,
              f"session {i}: {s_chunk.shape[0]} chunks served, "
              f"{r_chunk.shape[0]} in the reference")
        check(np.array_equal(s_chunk, r_chunk),
              f"session {i}: chunk votes differ from the reference "
              f"({s_chunk.tolist()} != {r_chunk.tolist()})")
        check(np.array_equal(s_alarm, r_alarm),
              f"session {i}: alarms differ from the reference "
              f"({s_alarm.tolist()} != {r_alarm.tolist()})")
        check(set(np.unique(s_win).tolist()) <= {0, 1},
              f"session {i}: window predictions outside {{0, 1}}")
        disagree += int(np.sum(s_win != r_win[: s_win.shape[0]]))
        total += int(s_win.shape[0])
        alarmed += int(s_alarm.max(initial=0) > 0)
    return disagree, total, alarmed


def check_kernels(program, fleet, cfg) -> str:
    """Run each Pallas kernel of the seizure path once on the chip, at the
    paper's widths, against its XLA reference. Returns a summary line."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.forest import ops as forest_ops
    from repro.kernels.gram import ops as gram_ops
    from repro.kernels.histogram import ops as hist_ops
    from repro.kernels.histogram import ref as hist_ref
    from repro.kernels.wpd import ops as wpd_ops
    from repro.signal import eeg_data, features, pipeline

    per = eeg_data.WINDOWS_PER_MATRIX
    wins = jnp.asarray(fleet[0][: fleet[0].shape[0] // per * per])
    normed, _, _ = features.normalize(
        pipeline.process_windows(wins, cfg), program.feat_mean,
        program.feat_std,
    )
    p_kernel, p_xla = (
        np.asarray(forest_ops.forest_predict_proba(
            program.packed, normed, use_pallas=use))
        for use in (True, False)
    )
    check(np.array_equal(p_kernel.argmax(-1), p_xla.argmax(-1)),
          "forest kernel and XLA traversal classify windows differently")
    forest_diff = float(np.abs(p_kernel - p_xla).max())

    key = jax.random.PRNGKey(0)
    n_buckets = 2 ** (cfg.forest.depth - 1) * cfg.forest.n_bins
    codes = jax.random.randint(key, (3, 1800, 288), 0, n_buckets)
    wy = jax.nn.one_hot(jax.random.randint(key, (3, 1800), 0, 2), 2)
    hist_kernel = hist_ops.class_histogram(
        codes, wy, n_buckets=n_buckets, use_pallas=True)
    check(np.array_equal(np.asarray(hist_kernel), np.asarray(
        hist_ref.class_histogram_scatter(codes, wy, n_buckets))),
        "histogram kernel and scatter-add differ")

    rows = wins.reshape(-1, eeg_data.WINDOW)
    (a_k, d_k), (a_x, d_x) = (
        wpd_ops.wpd_level(rows, wavelet=cfg.wavelet, use_pallas=use)
        for use in (True, False)
    )
    wpd_diff = float(max(jnp.abs(a_k - a_x).max(), jnp.abs(d_k - d_x).max())
                     / jnp.abs(rows).max())
    check(wpd_diff < 1e-5, f"wpd kernel off by {wpd_diff:.2e} (relative)")

    mat = rows[: per * eeg_data.N_CHANNELS].T  # one MSPCA data matrix
    g_k, g_x = (gram_ops.gram(mat, use_pallas=use) for use in (True, False))
    gram_diff = float(jnp.abs(g_k - g_x).max() / jnp.abs(g_x).max())
    check(gram_diff < 1e-5, f"gram kernel off by {gram_diff:.2e} (relative)")
    return (f"forest max |dp| {forest_diff:.1e} with equal classes on "
            f"{p_kernel.shape[0]} windows; histogram equal; wpd relative "
            f"error {wpd_diff:.1e}; gram relative error {gram_diff:.1e}")


def check_fitted(fitted, what: str) -> None:
    import jax
    import numpy as np

    for leaf in jax.tree.leaves(fitted):
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.floating):
            check(bool(np.isfinite(arr).all()),
                  f"{what}: non-finite values in the fitted pipeline")


def peak_bytes(devices) -> str:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(str(stats.get("peak_bytes_in_use", "n/a")))
    return ", ".join(peaks)


# ---------------------------------------------------------------------------
# The two runs
# ---------------------------------------------------------------------------

def smoke_one_chip(seed: int, sizes: Sizes, out_dir: pathlib.Path) -> None:
    from repro.configs.eeg_paper import CONFIG as cfg

    rec = training_set(seed, sizes)
    fitted, t_fit = fit(seed, rec, cfg, n_shards=sizes.shards)
    check_fitted(fitted, "train")
    log(f"train: {rec.windows.shape[0]} windows over {sizes.shards} map "
        f"shards -> union of {fitted.forest.rotation.shape[0]} trees; "
        f"{t_fit:.1f} s including compilation (set-up, not speed)")

    program, path = freeze(fitted, cfg, out_dir / "program")
    log(f"freeze: ScoringProgram saved and loaded back from {path}")

    fleet = fleet_timelines(seed, sizes)
    engine, events, t_serve = serve(program, fleet, sizes)
    served = served_by_session(events, len(fleet))
    n_chunks = sum(s[1].shape[0] for s in served)
    log(f"serve: {len(fleet)} sessions, "
        f"{sum(w.shape[0] for w in fleet)} windows pushed, {n_chunks} "
        f"chunks scored in {engine.steps} engine steps (B="
        f"{sizes.max_batch}, D={sizes.replay_depth}); {t_serve:.1f} s "
        "including compilation (set-up, not speed)")

    ref = reference(fitted, fleet, cfg)
    disagree, total, alarmed = compare_with_reference(served, ref)
    log(f"check: window-level disagreements with the reference: "
        f"{disagree} of {total}")
    check(alarmed > 0, "no session raised an alarm: the served program "
          "predicts nothing")
    log(f"check: chunk votes and alarms equal the plain reference for "
        f"{len(fleet)} of {len(fleet)} sessions ({alarmed} sessions "
        "raised an alarm)")
    log(f"kernels on the chip vs XLA: {check_kernels(program, fleet, cfg)}")


def smoke_four_chips(seed: int, sizes: Sizes, devices) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.eeg_paper import CONFIG as cfg
    from repro.launch.mesh import make_data_mesh
    from repro.serving import ScoringProgram
    from repro.signal import eeg_data

    mesh = make_data_mesh(len(devices))
    rec = training_set(seed, sizes)
    # The emulation runs on one chip; the mesh fit gets the rows sharded
    # across every chip of the mesh.
    rec_one = jax.device_put(rec, devices[0])
    rec_mesh = eeg_data.Recording(
        windows=jax.device_put(rec.windows, NamedSharding(mesh, P("data"))),
        labels=jax.device_put(rec.labels, NamedSharding(mesh, P("data"))),
    )
    check(len(rec_mesh.windows.sharding.device_set) == len(devices),
          "training rows are not spread over the mesh")

    on_mesh, t_mesh = fit(seed, rec_mesh, cfg, mesh=mesh)
    emulated, t_one = fit(seed, rec_one, cfg, n_shards=len(devices))
    check_fitted(on_mesh, "mesh fit")
    check(len(on_mesh.forest.rotation.sharding.device_set) == len(devices),
          "the mesh fit did not run on every chip")
    check(emulated.forest.rotation.sharding.device_set == {devices[0]},
          "the one-chip fit left chip 0")
    leaves_mesh = jax.tree.leaves(on_mesh)
    leaves_one = jax.tree.leaves(emulated)
    differ = [
        int(np.sum(np.asarray(a) != np.asarray(b)))
        for a, b in zip(leaves_mesh, leaves_one)
    ]
    log(f"train: mesh fit over {len(devices)} chips {t_mesh:.1f} s, "
        f"n_shards={len(devices)} fit on one chip {t_one:.1f} s "
        "(both including compilation; set-up, not speed)")
    check(sum(differ) == 0,
          f"mesh and one-chip forests differ: {differ} unequal entries "
          "per leaf")
    log(f"check: mesh forest == one-chip forest bit for bit "
        f"({sum(np.asarray(a).size for a in leaves_mesh)} entries)")

    program = ScoringProgram.from_fitted(on_mesh, cfg)
    fleet = fleet_timelines(seed, sizes)
    sharded, ev_mesh, t_sharded = serve(program, fleet, sizes, mesh=mesh)
    check(len(sharded._state.rings.sharding.device_set) == len(devices),
          "the mesh engine's slot state is not spread over the mesh")
    single, ev_one, t_single = serve(program, fleet, sizes)

    log(f"serve: {len(fleet)} sessions; mesh engine {sharded.steps} steps "
        f"in {t_sharded:.1f} s, unsharded engine {single.steps} steps in "
        f"{t_single:.1f} s (including compilation; set-up, not speed)")

    def decisions(events):
        """Every event without its window-level payload: chunk votes,
        alarms, alarm transitions, their sessions and order."""
        return [
            (type(e).__name__, e.patient_id, e.chunk_index)
            + ((e.chunk_pred, e.alarm, e.program_version)
               if hasattr(e, "window_preds") else ())
            for e in events
        ]

    check(decisions(ev_mesh) == decisions(ev_one),
          "mesh engine and unsharded engine differ in chunk votes, alarms "
          "or alarm events")
    mesh_wins = served_by_session(ev_mesh, len(fleet))
    one_wins = served_by_session(ev_one, len(fleet))
    win_diff = sum(int(np.sum(a[0] != b[0]))
                   for a, b in zip(mesh_wins, one_wins))
    n_wins = sum(a[0].shape[0] for a in one_wins)
    frac_diff = max(
        (abs(a.preictal_frac - b.preictal_frac)
         for a, b in zip(ev_mesh, ev_one) if hasattr(a, "preictal_frac")),
        default=0.0,
    )
    same_bytes = all(
        a.window_preds.tobytes() == b.window_preds.tobytes()
        and a.preictal_frac == b.preictal_frac
        for a, b in zip(ev_mesh, ev_one) if hasattr(a, "window_preds")
    )
    log(f"check: mesh engine == unsharded engine in every chunk vote, "
        f"alarm and alarm event ({len(ev_mesh)} events); window-level "
        f"disagreements {win_diff} of {n_wins}, max |preictal_frac "
        f"difference| {frac_diff:.4f}; event streams "
        f"{'byte-identical' if same_bytes else 'not byte-identical'}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the train -> freeze -> serve path on one chip; "
                         "4: only the mesh fit and mesh engine against "
                         "their one-chip counterparts")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="output directory (the frozen program goes here)")
    args = ap.parse_args()

    import jax

    visible = jax.devices()
    if visible[0].platform != "tpu":
        sys.exit(f"[smoke] FAIL: JAX finds no TPU (platform "
                 f"{visible[0].platform!r}); this check runs on the chip only")
    if len(visible) < args.chips:
        sys.exit(f"[smoke] FAIL: {args.chips} chips asked for, "
                 f"{len(visible)} visible")
    devices = visible[: args.chips]

    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{enable_compile_cache()}")
    try:
        if args.chips == 1:
            smoke_one_chip(args.seed, FULL, pathlib.Path(args.out))
        else:
            smoke_four_chips(args.seed, FULL, devices)
    except SmokeFailure as e:
        sys.exit(f"[smoke] FAIL: {e}")
    log(f"peak_bytes_in_use per chip: {peak_bytes(devices)}")
    print(json.dumps({"ok": True, "device": {
        "platform": visible[0].platform,
        "kind": visible[0].device_kind,
        "count": len(visible),
    }}))


if __name__ == "__main__":
    main()
