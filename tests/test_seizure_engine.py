"""Session-oriented serving API: ``ScoringProgram`` round-trips through
the checkpoint store, and ``SeizureEngine`` must (a) make bit-identical
alarm decisions to the ``signal.pipeline`` oracle, (b) admit new sessions
into freed slots mid-flight without draining the in-flight batch, and
(c) carry each session's on-device alarm ring across slot evictions."""

from __future__ import annotations

import importlib.util
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_data_mesh
from repro.serving import api
from repro.signal import eeg_data, pipeline

# Shared fixtures (small_cfg, fitted, program, timeline, chunk_pool, the
# overlap twins, and the seam-oracle stream) live in tests/conftest.py.

PER = eeg_data.WINDOWS_PER_MATRIX


def oracle_timeline(fitted, cfg, windows):
    """The reference path the engine must match bit-for-bit: per-window
    forest predictions -> chunk majority votes -> k-of-m alarm scan."""
    preds = pipeline.predict_windows(fitted, jnp.asarray(windows), cfg)
    chunks = pipeline.chunk_predictions(preds, cfg)
    alarms = pipeline.alarm_state(chunks, cfg)
    return np.asarray(chunks).tolist(), np.asarray(alarms).tolist()


def scored_events(events):
    return [e for e in events if isinstance(e, api.ChunkScored)]


def oracle_chunks(fitted, cfg, chunks):
    """Per-patient oracle over a list of (PER, C, N) chunks: window preds
    -> chunk majority votes -> k-of-m alarm scan, all via signal.pipeline.
    The chunks are featurized as ONE sequential stream (concatenated in
    push order) so the carried frontend context -- the denoise halo when
    ``cfg.overlap > 0`` -- flows across them exactly as a session's
    does; with ``overlap == 0`` this is bit-identical to featurizing
    each chunk independently (chunk independence, pinned elsewhere)."""
    preds = pipeline.predict_windows(
        fitted, jnp.asarray(np.concatenate(chunks)), cfg
    )
    votes = pipeline.chunk_predictions(preds, cfg)
    alarms = pipeline.alarm_state(votes, cfg)
    return np.asarray(votes).tolist(), np.asarray(alarms).tolist()


def run_interleaving(
    program, fitted, pool, *, max_batch, streams, open_order, seed,
    replay_depth=1,
):
    """Drive a ``SeizureEngine`` over randomly interleaved multi-patient
    streams (random push sizes, sporadic polls, optional unscored tail
    windows) and assert every vote and alarm matches the pipeline oracle
    bit-for-bit and in per-session order.

    streams    : {patient_id: (list of pool chunk indices, extra_windows)}
    open_order : session creation order (may differ from push order)
    replay_depth : engine's in-step backlog scan depth (>1 exercises the
                 bucketed replay path under the same oracle)
    """
    cfg = program.cfg
    rng = np.random.RandomState(seed)
    chunks = {pid: [pool[i] for i in idxs] for pid, (idxs, _) in streams.items()}
    full = {
        pid: np.concatenate(
            chunks[pid] + ([pool[0][:extra]] if extra else [])
        )
        for pid, (_, extra) in streams.items()
    }

    engine = api.SeizureEngine(
        program, max_batch=max_batch, replay_depth=replay_depth
    )
    sessions = {pid: engine.open_session(pid) for pid in open_order}

    # Split each stream into random-size pushes; interleave across
    # patients in random order (per-patient order preserved: the stream
    # is temporal).
    remaining = {pid: [] for pid in streams}
    for pid, wins in full.items():
        i = 0
        while i < wins.shape[0]:
            n = int(rng.randint(1, 100))
            remaining[pid].append(wins[i : i + n])
            i += n
    events = []
    while any(remaining.values()):
        pid = rng.choice([p for p, parts in remaining.items() if parts])
        sessions[pid].push(remaining[pid].pop(0))
        if rng.rand() < 0.3:  # sporadic polls mid-stream
            events += engine.poll(drain=bool(rng.rand() < 0.5))
    events += engine.poll()

    got = {pid: ([], []) for pid in streams}
    for e in scored_events(events):
        got[e.patient_id][0].append(e.chunk_pred)
        got[e.patient_id][1].append(e.alarm)
    for pid in streams:
        want_votes, want_alarms = oracle_chunks(fitted, cfg, chunks[pid])
        assert got[pid][0] == want_votes, f"votes diverge for patient {pid}"
        assert got[pid][1] == want_alarms, f"alarms diverge for patient {pid}"
        extra = streams[pid][1]
        assert sessions[pid].pending_windows == extra
    return engine


# ---------------------------------------------------------------------------
# ScoringProgram
# ---------------------------------------------------------------------------

class TestScoringProgram:
    def test_from_fitted_shapes(self, program, fitted, small_cfg):
        assert program.packed.n_trees == small_cfg.forest.n_trees
        assert program.feat_mean.shape == fitted.feat_mean.shape
        assert program.cfg == small_cfg

    def test_from_fitted_packs_once(self, fitted, small_cfg, program):
        # rotation_forest.pack caches on params identity, so building a
        # second program from the same fitted forest reuses the packing.
        again = api.ScoringProgram.from_fitted(fitted, small_cfg)
        assert again.packed is program.packed

    def test_save_load_roundtrip(self, program, tmp_path):
        path = program.save(str(tmp_path), step=3)
        assert "step_00000003" in path
        restored = api.ScoringProgram.load(str(tmp_path))  # latest step
        assert restored.cfg == program.cfg
        for a, b in zip(
            jax.tree.leaves(program._arrays()),
            jax.tree.leaves(restored._arrays()),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_loaded_program_scores_identically(
        self, program, chunk_pool, tmp_path
    ):
        program.save(str(tmp_path))
        restored = api.ScoringProgram.load(str(tmp_path))
        quiet, pre = chunk_pool
        batch = np.stack([quiet, pre])
        v1, f1, _ = api.SeizureEngine(program, max_batch=2).score_chunks(batch)
        v2, f2, _ = api.SeizureEngine(restored, max_batch=2).score_chunks(
            batch.copy()
        )
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
        np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))

    def test_load_missing_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            api.ScoringProgram.load(str(tmp_path))

    def test_union_forest_streams_to_engine_on_device(self, small_cfg):
        # ROADMAP follow-on: a fit_mapreduce union forest must lower into
        # a served ScoringProgram WITHOUT leaving the device -- packing
        # (rotation_forest.pack -> kernels.forest.pack_forest) is jitted
        # gathers, and the engine scores a device-resident batch without
        # any implicit host round-trip. jax.transfer_guard turns any
        # such transfer into an error.
        rec = eeg_data.make_training_set(
            jax.random.PRNGKey(3), 1,
            n_interictal_windows=PER, n_preictal_windows=PER,
        )
        rec = eeg_data.stratify_chunks(rec)
        fitted = pipeline.fit(
            jax.random.PRNGKey(4), rec, small_cfg, n_shards=2
        )
        jax.block_until_ready(fitted)
        batch = jax.device_put(
            jnp.asarray(np.asarray(rec.windows[:PER])[None])
        )
        jax.block_until_ready(batch)
        with jax.transfer_guard("disallow"):
            prog = api.ScoringProgram.from_fitted(fitted, small_cfg)
            engine = api.SeizureEngine(prog, max_batch=1)
            votes, frac, preds = engine.score_chunks(batch)
            jax.block_until_ready((prog.packed, votes, frac, preds))
        # Sanity: the guarded result matches an unguarded rerun.
        again, _, _ = api.SeizureEngine(prog, max_batch=1).score_chunks(
            np.asarray(rec.windows[:PER])[None]
        )
        np.testing.assert_array_equal(np.asarray(votes), np.asarray(again))


# ---------------------------------------------------------------------------
# Engine vs the pipeline oracle
# ---------------------------------------------------------------------------

class TestEngineOracle:
    def test_streamed_session_matches_oracle(
        self, program, fitted, small_cfg, timeline
    ):
        wins = np.asarray(timeline.windows)
        want_votes, want_alarms = oracle_timeline(fitted, small_cfg, wins)

        engine = api.SeizureEngine(program, max_batch=2)
        session = engine.open_session(3)
        # Non-chunk-aligned pushes: 37-window slices of an 818-window
        # stream, polling as we go.
        events = []
        for i in range(0, wins.shape[0], 37):
            session.push(wins[i : i + 37])
            events += engine.poll()
        events += engine.poll()
        scored = scored_events(events)
        assert [e.chunk_pred for e in scored] == want_votes
        assert [e.alarm for e in scored] == want_alarms
        assert [e.chunk_index for e in scored] == list(range(len(want_votes)))
        # 818 = 13 * 60 + 38: the partial tail stays buffered, unscored.
        assert session.pending_windows == wins.shape[0] % PER
        assert engine.alarm_state(3) == 1

    def test_alarm_raised_and_cleared_events(self, program, chunk_pool):
        quiet, pre = chunk_pool
        cfg = program.cfg
        engine = api.SeizureEngine(program, max_batch=1)
        session = engine.open_session(9)
        for _ in range(cfg.alarm_k):
            session.push(pre)
        for _ in range(cfg.alarm_m):
            session.push(quiet)
        events = engine.poll()
        raised = [e for e in events if isinstance(e, api.AlarmRaised)]
        cleared = [e for e in events if isinstance(e, api.AlarmCleared)]
        # k preictal chunks fire the alarm at chunk k-1; it clears once
        # enough quiet chunks age the hits out of the m-deep ring.
        assert [e.chunk_index for e in raised] == [cfg.alarm_k - 1]
        assert len(cleared) == 1 and cleared[0].chunk_index > cfg.alarm_k - 1
        assert engine.alarm_state(9) == 0

    def test_evaluate_timeline_routes_through_engine(
        self, fitted, small_cfg, timeline
    ):
        # Offline eval and serving share one code path now; the result
        # must still match the raw oracle decision-for-decision.
        want_votes, want_alarms = oracle_timeline(
            fitted, small_cfg, timeline.windows
        )
        res = pipeline.evaluate_timeline(fitted, timeline, small_cfg)
        assert np.asarray(res.chunk_preds).tolist() == want_votes
        assert np.asarray(res.alarms).tolist() == want_alarms
        assert res.window_preds.shape[0] == timeline.windows.shape[0]
        assert float(res.lead_time_minutes) > 0

    def test_fleet_matches_plain_reference(self, program, fitted, small_cfg):
        """``chip_smoke.py``'s check at test size: a fleet larger than the
        slot count pushes held-out timelines in chunk-unaligned pieces of
        different sizes (multi-chunk backlogs, eviction churn) and every
        session's window predictions, chunk votes and alarms equal
        ``predict_windows -> chunk_predictions -> alarm_state``. The
        engine shape (B=1, D=1) is one this module's other tests
        compile."""
        smoke = _chip_smoke()
        sizes = smoke.Sizes(sessions=2, max_batch=1, replay_depth=1)
        fleet = smoke.fleet_timelines(0, sizes)
        _, events, _ = smoke.serve(program, fleet, sizes)
        served = smoke.served_by_session(events, len(fleet))
        ref = smoke.reference(fitted, fleet, small_cfg)
        disagree, total, alarmed = smoke.compare_with_reference(served, ref)
        assert total == len(fleet) * (fleet[0].shape[0] // PER) * PER
        assert disagree == 0
        assert alarmed == len(fleet)


def _chip_smoke():
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ---------------------------------------------------------------------------
# Continuous-batching scheduling
# ---------------------------------------------------------------------------

class TestContinuousScheduling:
    def test_midflight_refill_no_drain_barrier(self, program, chunk_pool):
        """A freed slot is refilled from the queue while the other slot's
        session is still streaming: total steps hit the ceil(total/B)
        optimum, which is impossible with drain-and-flush batches."""
        quiet, _ = chunk_pool
        engine = api.SeizureEngine(program, max_batch=2)
        a = engine.open_session(1)   # 3 chunks
        c = engine.open_session(2)   # 1 chunk
        d = engine.open_session(3)   # 2 chunks (queued: no free slot yet)
        a.push(np.concatenate([quiet] * 3))
        c.push(quiet)
        d.push(np.concatenate([quiet] * 2))
        scored = scored_events(engine.poll())
        order = [(e.patient_id, e.chunk_index) for e in scored]
        # 6 chunks / 2 slots = 3 steps: d joins the moment c's slot frees.
        assert engine.steps == 3
        # d's first chunk is scored BEFORE a's last: admitted mid-flight.
        assert order.index((3, 0)) < order.index((1, 2))
        # Per-session order is FIFO regardless of interleaving.
        for pid, n in ((1, 3), (2, 1), (3, 2)):
            assert [i for p, i in order if p == pid] == list(range(n))

    def test_ring_persists_across_slot_eviction(self, program, chunk_pool):
        """With one slot and two alternating patients, every chunk evicts
        and readmits a session; the k-of-m memory must survive the trip
        through host ring storage bit-for-bit."""
        quiet, pre = chunk_pool
        cfg = program.cfg
        engine = api.SeizureEngine(program, max_batch=1)
        p = engine.open_session(10)
        q = engine.open_session(11)
        alarms_p, alarms_q = [], []
        for _ in range(cfg.alarm_m):
            p.push(pre)
            q.push(quiet)
            for e in scored_events(engine.poll()):
                (alarms_p if e.patient_id == 10 else alarms_q).append(e.alarm)
        k = cfg.alarm_k
        assert alarms_p == [0] * (k - 1) + [1] * (cfg.alarm_m - k + 1)
        assert alarms_q == [0] * cfg.alarm_m

    def test_poll_without_drain_defers_partial_batch(self, program, chunk_pool):
        quiet, _ = chunk_pool
        engine = api.SeizureEngine(program, max_batch=2)
        for pid in range(3):
            engine.open_session(pid).push(quiet)
        first = scored_events(engine.poll(drain=False))
        assert len(first) == 2 and engine.steps == 1  # full batch only
        rest = scored_events(engine.poll())
        assert len(rest) == 1  # drained (padded) tail

    def test_mesh_engine_matches_unsharded(self, program, chunk_pool):
        quiet, pre = chunk_pool
        mesh = make_data_mesh(1)
        results = []
        for kwargs in ({}, {"mesh": mesh}):
            engine = api.SeizureEngine(program, max_batch=2, **kwargs)
            s = engine.open_session(0)
            s.push(np.concatenate([quiet, pre, pre, pre]))
            results.append(
                [(e.chunk_pred, e.alarm) for e in scored_events(engine.poll())]
            )
        assert results[0] == results[1]

    def test_explicit_axes_mesh_is_served(self, program, chunk_pool):
        # jax.make_mesh's default Explicit axes: the engine serves them on
        # an Auto-axes view of the same devices.
        quiet, pre = chunk_pool
        mesh = jax.make_mesh((1,), ("data",))
        assert mesh.axis_types == (jax.sharding.AxisType.Explicit,)
        results = []
        for kwargs in ({}, {"mesh": mesh}):
            engine = api.SeizureEngine(program, max_batch=2, **kwargs)
            engine.open_session(0).push(np.concatenate([pre, pre, quiet]))
            results.append(
                [(e.chunk_pred, e.alarm) for e in scored_events(engine.poll())]
            )
        assert results[0] == results[1]


# ---------------------------------------------------------------------------
# Observability: counters, host spans in a profiler trace, device scopes
# ---------------------------------------------------------------------------

def _midflight_engine(program, quiet, clock):
    """``test_midflight_refill_no_drain_barrier``'s traffic: 2 slots,
    sessions of 3, 1 and 2 chunks, all pushed at t=0."""
    engine = api.SeizureEngine(program, max_batch=2, clock=clock)
    for pid, n in ((1, 3), (2, 1), (3, 2)):
        engine.open_session(pid).push(np.concatenate([quiet] * n))
    return engine


def _bytes_per(engine):
    """(step batch + mask, step read-back, admission, eviction) bytes."""
    b, d, m = engine.max_batch, engine.replay_depth, engine.alarm_m
    window = eeg_data.N_CHANNELS * eeg_data.WINDOW * 4  # one f32 window
    boundary = engine.fe_width * window
    slots, w = b * d, engine.chunk_windows
    return (slots * w * window + slots * 4,   # batch, mask
            slots * 3 * 4 + slots * w * 4,    # votes, frac, alarm; preds
            4 * 4 + m * 4 + boundary,         # slot, pos, alarm, phase; ring
            b * (m * 4 + 3 * 4 + boundary))   # every slot's state


def _program_trace():
    """The chip benchmark's reader of the program's trace marks."""
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                           / "benchmarks" / "chip"))
    from chipbench import program_trace

    return program_trace


class TestObservability:
    def test_counters_match_the_hand_count(self, program, chunk_pool):
        quiet, _ = chunk_pool
        now = [0.0]
        engine = _midflight_engine(program, quiet, lambda: now[0])
        now[0] = 10.0
        engine.poll()
        step, readback, admission, eviction = _bytes_per(engine)
        # Slot 1's one-chunk session drains after step 1 and yields to
        # the queued one; nothing is evicted once the queue is empty.
        assert (engine.steps, engine.chunks_scored, engine.slot_positions,
                engine.evictions, engine.admissions) == (3, 6, 6, 1, 3)
        assert engine.h2d_bytes == 3 * step + 3 * admission
        assert engine.d2h_bytes == 3 * readback + eviction
        assert engine.queue_wait_s == 6 * 10.0

    def test_poll_writes_program_spans_into_a_profiler_trace(
        self, program, chunk_pool, tmp_path
    ):
        program_trace = _program_trace()
        quiet, _ = chunk_pool
        engine = _midflight_engine(program, quiet, lambda: 0.0)
        step, readback, admission, eviction = _bytes_per(engine)
        with jax.profiler.trace(str(tmp_path)):
            with jax.profiler.TraceAnnotation("bench.poll"):
                engine.poll()
        host = sorted(program_trace.load(str(tmp_path))["host"],
                      key=lambda h: h[1])
        caller, spans = host[0], host[1:]
        assert caller[0] == "bench.poll"
        per_step = ["seizure.fill", "seizure.assemble", "seizure.put",
                    "seizure.dispatch", "seizure.readback", "seizure.events"]
        assert [h[0] for h in spans] == per_step * 3 + ["seizure.fill"]
        assert all(caller[1] <= s and s + d <= caller[1] + caller[2]
                   for _, s, d, _ in spans)
        ends = [s + d for _, s, d, _ in spans]
        assert all(e <= s for e, (_, s, _, _) in zip(ends, spans[1:]))
        args = [h[3] for h in spans]
        assert args[0] == {"evictions": 0, "admissions": 2, "d2h_bytes": 0,
                           "h2d_bytes": 2 * admission}
        assert args[6] == {"evictions": 1, "admissions": 1,
                           "d2h_bytes": eviction, "h2d_bytes": admission}
        assert [a["chunks"] for a in args[1::6]] == [2, 2, 2]
        assert [a["bytes"] for a in args[2::6]] == [step] * 3
        assert [a["bytes"] for a in args[4::6]] == [readback] * 3
        assert args[3] == args[5] == {}

    def test_step_lowering_carries_the_stage_scopes(self, program):
        program_trace = _program_trace()
        sd = jax.ShapeDtypeStruct
        chunks = sd((2, 1, PER, eeg_data.N_CHANNELS, eeg_data.WINDOW),
                    jnp.float32)
        text = api._jit_engine_step_megabatch.lower(
            api.init_state(2, program.cfg.alarm_m), chunks,
            sd((2, 1), jnp.int32), program.packed, program.feat_mean,
            program.feat_std, cfg=program.cfg, use_pallas=False,
        ).as_text(debug_info=True)
        found = set().union(*map(program_trace.segments,
                                 re.findall(r'loc\("([^"]+)"', text)))
        assert {"mspca", "eigh", "wpd", "vote", "ring"} <= found


# ---------------------------------------------------------------------------
# Interleaving oracle (seeded scenarios; the hypothesis variant in
# test_engine_properties.py drives the same checker with drawn inputs)
# ---------------------------------------------------------------------------

class TestInterleavingOracle:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_seeded_random_interleavings(
        self, program, fitted, chunk_pool, seed
    ):
        rng = np.random.RandomState(1000 + seed)
        n_pat = int(rng.randint(1, 4))
        streams = {
            pid: (
                [int(i) for i in rng.randint(0, 2, size=rng.randint(1, 4))],
                int(rng.choice([0, 30])),
            )
            for pid in range(n_pat)
        }
        open_order = [int(p) for p in rng.permutation(list(streams))]
        run_interleaving(
            program, fitted, chunk_pool,
            max_batch=int(rng.randint(1, 3)),
            streams=streams, open_order=open_order, seed=seed,
        )


# ---------------------------------------------------------------------------
# Pallas forest path + alarm reset (migrated from the deleted
# SeizureScoringService shim tests -- the engine now owns both behaviors)
# ---------------------------------------------------------------------------

class TestKernelPathAndReset:
    def _drive(self, engine, chunks):
        session = engine.open_session(1)
        out = []
        for chunk in chunks:
            session.push(chunk)
            out += [
                (e.chunk_pred, e.alarm)
                for e in scored_events(engine.poll())
            ]
        return out

    def test_pallas_forest_path_same_alarms(self, program, chunk_pool):
        quiet, pre = chunk_pool
        stream = [pre] * 4 + [quiet] * 2
        ref = self._drive(
            api.SeizureEngine(program, max_batch=2), stream
        )
        kernel = self._drive(
            api.SeizureEngine(program, max_batch=2, use_forest_kernel=True),
            stream,
        )
        assert ref == kernel

    def test_reset_alarm_clears_ring(self, program, chunk_pool):
        _, pre = chunk_pool
        cfg = program.cfg
        engine = api.SeizureEngine(program, max_batch=1)
        s = engine.open_session(5)
        for _ in range(cfg.alarm_m):
            s.push(pre)
        engine.poll()
        assert engine.alarm_state(5) == 1
        engine.reset_alarm(5)
        assert engine.alarm_state(5) == 0

    def test_reset_alarm_keeps_queued_chunks(self, program, chunk_pool):
        # Reset clears the alarm ring only; a chunk pushed before the
        # reset still gets scored (against the fresh ring).
        _, pre = chunk_pool
        engine = api.SeizureEngine(program, max_batch=1)
        s = engine.open_session(5)
        s.push(pre)
        engine.reset_alarm(5)
        results = scored_events(engine.poll())
        assert [e.patient_id for e in results] == [5]
        assert results[0].alarm == 0  # one vote cannot fire k-of-m


# ---------------------------------------------------------------------------
# Session lifecycle
# ---------------------------------------------------------------------------

class TestSessionLifecycle:
    def test_duplicate_open_raises(self, program):
        engine = api.SeizureEngine(program, max_batch=1)
        engine.open_session(1)
        with pytest.raises(ValueError, match="already open"):
            engine.open_session(1)

    def test_close_discards_state_and_frees_patient(self, program, chunk_pool):
        _, pre = chunk_pool
        cfg = program.cfg
        engine = api.SeizureEngine(program, max_batch=1)
        s = engine.open_session(5)
        for _ in range(cfg.alarm_m):
            s.push(pre)
        engine.poll()
        assert engine.alarm_state(5) == 1
        engine.close_session(5)
        assert engine.alarm_state(5) == 0
        with pytest.raises(RuntimeError, match="closed"):
            s.push(pre)
        engine.open_session(5)  # patient id is reusable after close

    def test_push_rejects_malformed_windows(self, program):
        engine = api.SeizureEngine(program, max_batch=1)
        s = engine.open_session(0)
        with pytest.raises(ValueError, match="windows shape"):
            s.push(np.zeros((4, 2, 128), np.float32))

    def test_push_does_not_alias_caller_buffer(self, program, chunk_pool):
        # A streaming caller may reuse its acquisition buffer between
        # push and poll; queued chunks must capture the pushed values.
        quiet, pre = chunk_pool
        engine = api.SeizureEngine(program, max_batch=1)
        ref = engine.open_session(0)
        ref.push(pre)
        want = scored_events(engine.poll())[0].chunk_pred
        buf = pre.copy()
        s = engine.open_session(1)
        s.push(buf)
        buf[:] = quiet  # caller reuses the buffer before poll
        got = scored_events(engine.poll())[0].chunk_pred
        assert got == want

    def test_partial_push_buffers_until_chunk_completes(
        self, program, chunk_pool
    ):
        quiet, _ = chunk_pool
        engine = api.SeizureEngine(program, max_batch=1)
        s = engine.open_session(0)
        s.push(quiet[:37])
        assert engine.poll() == []
        assert s.pending_windows == 37 and s.pending_chunks == 0
        s.push(quiet[37:])
        assert s.pending_chunks == 1
        assert len(scored_events(engine.poll())) == 1
        assert s.pending_windows == 0
