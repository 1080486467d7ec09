"""Serving cells: a fleet of ``StreamSession``s on one ``SeizureEngine``.

Set-up builds everything a window needs from the seed: a pool of EEG
chunks cut from seeded patient timelines (on the device, then held on
the host as clients would hold their uploads), the served forest
(``reference.served_forest``, grown on seeded training chunks of the
configuration's ``served_forest.patients``: one program for the fleet),
the engine with
the configuration's options (``engine``: ``SeizureEngine`` keyword
arguments; ``"mesh": true`` serves on a ``data`` mesh over the cell's
chips), the open fleet, and one warm-up pass through every program the
window runs.

One generator reads every mix (``traffic/<mix>.json``):

  arrivals  "closed": before every ``poll(drain=False)`` the generator
            tops the fleet up to ``outstanding`` sessions with unscored
            backlog. Each reconnect is a session that is not outstanding,
            picked by the seed, whose outage backlog of whole chunks is
            due at once. Every run of ``outstanding`` reconnects has the
            same backlog lengths, ``outstanding`` of them evenly spread
            over ``backlog_chunks`` [lo, hi], in one fixed order (the
            order decides how the last backlogs drain), so that every
            seed does the same work; the seed picks the sessions and the
            chunks.
            "open": ``rate`` uploads per second from a fleet of
            ``rate * period_s`` sessions, each uploading
            ``upload_chunks`` whole chunks (default 1) once per period at
            a phase drawn from the seed (jittered even spacing, so every
            seed sends as many); with ``burst`` {"on_s", "off_s"} the
            uploads fall in the on-spells only, at the same mean rate.
            A due upload is pushed as soon as the loop gets to it; the
            delay is reported as generator lateness.
  push      "whole" (default): an upload or backlog in one ``push``;
            "windows": one ``push`` per 8-second window.
  churn     the share of sessions (default 0) that close once their
            backlog is scored and are replaced by a fresh session under
            a new patient id.
  fleet     closed arrivals: the number of open sessions.
  pool      {"timelines", "interictal_chunks"}: the chunks uploads are
            cut from (contiguous runs, starts drawn from the seed).

A chunk's latency runs from the time it was due (the push that completed
it) to the return of the ``poll`` that handed back its ``ChunkScored``.
The window ends with the first ``poll`` that returns after ``seconds``,
so a rate covers all the work and all the time of the window.
"""

from __future__ import annotations

import collections
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import eeg, program, reference, spans


class Prepared(NamedTuple):
    program: object               # the program's ScoringProgram
    pool: np.ndarray              # (P, 60, 3, 2048) host chunks
    served: reference.Served


class Fleet(NamedTuple):
    engine: object
    sessions: list                # position -> StreamSession
    pool: np.ndarray
    served: reference.Served
    cfg: dict
    traffic: dict


def prepare(cfg: dict, traffic: dict, seed: int, log) -> Prepared:
    """The pool and the served program, from the seed."""
    key = eeg.key_from_seed(seed)
    k_pool, k_train, k_forest = jax.random.split(key, 3)
    pool_spec = traffic["pool"]
    pool = np.asarray(eeg.chunk_pool(
        k_pool, pool_spec["timelines"], pool_spec["interictal_chunks"],
        cfg["patients"]))
    log(f"pool: {pool.shape[0]} chunks, {pool.nbytes} bytes on the host")

    t = cfg["served_forest"]
    n = t["chunks_per_class"] * eeg.CHUNK
    wins, labels = jax.vmap(
        lambda k, p: eeg.training_set(k, p, n_inter=n, n_pre=n))(
        jax.random.split(k_train, t["patients"]), jnp.arange(t["patients"]))
    feats = reference.features_in_blocks(
        wins.reshape(-1, eeg.CHUNK, eeg.N_CHANNELS, eeg.WINDOW))
    labels = labels.reshape(-1)
    jax.block_until_ready(feats)
    log(f"served forest: {labels.shape[0]} training windows of "
        f"{t['patients']} patients featurized by the reference")
    fc = cfg["pipeline"]["forest"]
    served = reference.served_forest(
        k_forest, feats.reshape(-1, feats.shape[-1]), labels,
        reference.FitConfig(shards=1, trees_per_shard=fc["n_trees"],
                            subsets=fc["n_subsets"], depth=fc["depth"],
                            bins=fc["n_bins"], classes=fc["n_classes"]))
    prog = program.scoring_program(served, program.pipeline_config(cfg))
    jax.block_until_ready((prog.packed, served))
    log(f"program: {fc['n_trees']} trees of depth {fc['depth']}")
    return Prepared(prog, pool, served)


def fleet_size(traffic: dict) -> int:
    if traffic["arrivals"] == "closed":
        return traffic["fleet"]
    return int(round(traffic["rate"] * traffic["period_s"]))


def open_fleet(prep: Prepared, cfg: dict, traffic: dict, devices: list,
               log) -> Fleet:
    """A warmed-up engine with the configuration's options and the
    traffic's fleet of open sessions."""
    from repro.serving import SeizureEngine

    opts = dict(cfg["engine"])
    if opts.pop("mesh", False):
        from repro.launch.mesh import make_data_mesh

        opts["mesh"] = make_data_mesh(len(devices))

    def engine():
        return SeizureEngine(prep.program, **opts)

    _warm_up(engine(), prep.pool, opts)
    eng = engine()
    n = fleet_size(traffic)
    sessions = [eng.open_session(i) for i in range(n)]
    log(f"engine: {cfg['engine']}; {n} sessions open")
    return Fleet(eng, sessions, prep.pool, prep.served, cfg, traffic)


def setup(cfg: dict, traffic: dict, seed: int, devices: list, log) -> Fleet:
    return open_fleet(prepare(cfg, traffic, seed, log), cfg, traffic,
                      devices, log)


def _warm_up(engine, pool: np.ndarray, opts: dict) -> None:
    """Every program the window runs, once: a full step at the fixed
    (B, D), eviction and admission of a waiting session, and a partial
    step."""
    b, d = opts["max_batch"], opts["replay_depth"]
    for i in range(b + 1):
        engine.open_session(i).push(
            pool[i % len(pool)][None].repeat(d if i < b else 1, 0).reshape(
                -1, eeg.N_CHANNELS, eeg.WINDOW))
    engine.poll(drain=False)
    engine.poll()


class Window(NamedTuple):
    seconds: float                 # first push to the last in-window poll
    scored_in_window: int
    latencies_s: list              # every due chunk that was scored
    attempted: int                 # chunks due in the window
    scored: dict                   # session -> [(chunk_index, vote, frac,
                                   #              alarm, window_preds)]
    alarms: dict                   # session -> [("raised"|"cleared", i)]
    pushed: dict                   # session -> [pool chunk id per chunk]
    steps: int
    slots: int                     # steps x B x D: chunk slots stepped
    lateness_s: list               # open arrivals: push time - due time
    backlog: list                  # unscored chunks after each poll


class _Book:
    """What the generator pushed and what came back."""

    def __init__(self, push_mode: str):
        if push_mode not in ("whole", "windows"):
            raise ValueError(f"unknown push mode {push_mode!r}")
        self.by_window = push_mode == "windows"
        self.due: dict = collections.defaultdict(list)
        self.pushed: dict = collections.defaultdict(list)
        self.scored: dict = collections.defaultdict(list)
        self.alarms: dict = collections.defaultdict(list)
        self.latencies: list = []
        self.unscored = 0

    def push(self, session, pool, first: int, k: int, due: float | None,
             rec):
        """Push pool chunks [first, first + k) into the session. ``due``
        None: each chunk is due when the push that completes it starts."""
        sid = session.patient_id
        with rec.span("bench.push"):
            if not self.by_window:
                start = time.perf_counter()
                session.push(pool[first:first + k].reshape(
                    -1, eeg.N_CHANNELS, eeg.WINDOW))
                dues = [start if due is None else due] * k
            else:
                dues = []
                for c in range(first, first + k):
                    for w in range(eeg.CHUNK):
                        start = time.perf_counter()
                        session.push(pool[c, w])
                    dues.append(start if due is None else due)
        self.pushed[sid] += range(first, first + k)
        self.due[sid] += dues
        self.unscored += k
        rec.count("chunks_pushed", k)

    def take(self, events, now: float) -> list:
        """Book a poll's events; returns the sessions whose backlog the
        poll finished."""
        from repro.serving import AlarmCleared, AlarmRaised, ChunkScored

        done = []
        for ev in events:
            sid = ev.patient_id
            if isinstance(ev, ChunkScored):
                self.scored[sid].append((ev.chunk_index, ev.chunk_pred,
                                         ev.preictal_frac, ev.alarm,
                                         np.asarray(ev.window_preds)))
                if ev.chunk_index < len(self.due[sid]):
                    self.latencies.append(now - self.due[sid][ev.chunk_index])
                self.unscored -= 1
                if len(self.scored[sid]) == len(self.pushed[sid]):
                    done.append(sid)
            elif isinstance(ev, AlarmRaised):
                self.alarms[sid].append(("raised", ev.chunk_index))
            elif isinstance(ev, AlarmCleared):
                self.alarms[sid].append(("cleared", ev.chunk_index))
        return done


class _Closed:
    """Closed arrivals: reconnects that keep ``outstanding`` sessions
    with backlog."""

    def __init__(self, tr: dict, n_sessions: int, n_pool: int, rng):
        lo, hi = tr["backlog_chunks"]
        self.target = tr["outstanding"]
        self.lengths = np.random.default_rng(0).permutation(
            np.rint(np.linspace(lo, hi, self.target)).astype(int))
        self.order: list = []
        self.n_sessions, self.n_pool, self.rng = n_sessions, n_pool, rng
        self.outstanding: set = set()
        self.lateness: list = []

    def due(self, now: float, t0: float):
        """(position, first pool chunk, chunks, due time or None) to push
        now; ``t0`` is the window's start."""
        while len(self.outstanding) < self.target:
            if not self.order:
                self.order = list(self.lengths[::-1])
            k = int(self.order.pop())
            pos = int(self.rng.integers(self.n_sessions))
            while pos in self.outstanding:
                pos = int(self.rng.integers(self.n_sessions))
            self.outstanding.add(pos)
            yield pos, int(self.rng.integers(self.n_pool - k + 1)), k, None

    def done(self, pos: int) -> None:
        self.outstanding.discard(pos)

    def wait(self, elapsed: float) -> float:
        return 0.0


class _Open:
    """Open arrivals: one upload per session per period, at phases drawn
    from the seed."""

    def __init__(self, tr: dict, n_sessions: int, n_pool: int, rng,
                 seconds: float):
        period = float(tr["period_s"])
        burst = tr.get("burst")
        on, off = ((burst["on_s"], burst["off_s"]) if burst
                   else (period, 0.0))
        # Even spacing jittered within each slot over the on-time of a
        # period, then laid onto the clock's on-spells.
        u = (np.arange(n_sessions) + rng.uniform(size=n_sessions)) * (
            period * on / (on + off) / n_sessions)
        spell = np.floor(u / on)
        when = spell * (on + off) + (u - spell * on)
        self.positions = rng.permutation(n_sessions)
        self.when = when
        self.k = int(tr.get("upload_chunks", 1))
        self.n_pool, self.rng, self.seconds = n_pool, rng, seconds
        self.next = 0
        self.lateness: list = []

    def due(self, now: float, t0: float):
        while (self.next < len(self.when)
               and self.when[self.next] <= min(now - t0, self.seconds)):
            pos = int(self.positions[self.next])
            due = t0 + self.when[self.next]
            self.lateness.append(now - due)
            self.next += 1
            yield (pos, int(self.rng.integers(self.n_pool - self.k + 1)),
                   self.k, due)

    def done(self, pos: int) -> None:
        pass

    def wait(self, elapsed: float) -> float:
        """Seconds to idle before the next upload is due (at most 2 ms)."""
        if self.next >= len(self.when):
            return 0.0
        return min(0.002, max(0.0, self.when[self.next] - elapsed))


def run_window(fleet: Fleet, seconds: float, seed: int, rec: spans.Recorder
               ) -> Window:
    tr = fleet.traffic
    rng = np.random.default_rng([seed, 7])
    n, n_pool = len(fleet.sessions), fleet.pool.shape[0]
    if tr["arrivals"] == "closed":
        gen = _Closed(tr, n, n_pool, rng)
    elif tr["arrivals"] == "open":
        gen = _Open(tr, n, n_pool, rng, seconds)
    else:
        raise ValueError(f"unknown arrivals {tr['arrivals']!r}")
    churn = float(tr.get("churn", 0.0))
    book = _Book(tr.get("push", "whole"))
    engine, pool, sessions = fleet.engine, fleet.pool, fleet.sessions
    position = {s.patient_id: i for i, s in enumerate(sessions)}
    next_id = max(position) + 1
    backlog: list = []
    steps0 = engine.steps
    n_in = 0
    t0 = time.perf_counter()
    with rec.window():
        while True:
            with rec.span("bench.arrivals"):
                for pos, first, k, due in gen.due(time.perf_counter(), t0):
                    book.push(sessions[pos], pool, first, k, due, rec)
            with rec.span("bench.poll"):
                events = engine.poll(drain=False)
            now = time.perf_counter()
            before = len(book.latencies)
            for sid in book.take(events, now):
                pos = position.pop(sid)
                gen.done(pos)
                if churn and rng.uniform() < churn:
                    engine.close_session(sid)
                    sessions[pos] = engine.open_session(next_id)
                    rec.count("sessions_replaced")
                    next_id += 1
                position[sessions[pos].patient_id] = pos
            n_in += len(book.latencies) - before
            backlog.append(book.unscored)
            if now - t0 >= seconds:
                break
            if not events:
                time.sleep(gen.wait(time.perf_counter() - t0))
    steps = engine.steps - steps0
    e = fleet.cfg["engine"]
    slots = steps * e["max_batch"] * e["replay_depth"]
    rec.count("engine_steps", steps)
    rec.count("chunk_slots", slots)
    rec.count("chunks_scored", n_in)
    attempted = sum(len(v) for v in book.pushed.values())
    # Anything still queued is flushed and booked late, not dropped.
    book.take(engine.poll(), time.perf_counter())
    return Window(now - t0, n_in, book.latencies, attempted,
                  dict(book.scored), dict(book.alarms), dict(book.pushed),
                  steps, slots, gen.lateness, backlog)


# ---------------------------------------------------------------------------
# Metrics and the comparison with the reference
# ---------------------------------------------------------------------------

def end_to_end(w: Window) -> dict:
    lat = np.asarray(w.latencies_s) * 1e3
    return {
        "chunks_per_s": w.scored_in_window / w.seconds,
        "p99_chunk_ms": float(np.percentile(lat, 99)) if lat.size else float("inf"),
    }


def attempted_failed(w: Window) -> tuple[int, int]:
    return w.attempted, w.attempted - sum(len(v) for v in w.scored.values())


def check(fleet: Fleet, w: Window, seed: int, control: bool = False) -> dict:
    """Every session's events against the vote and the 3-of-5 rule; a
    sample of the scored chunks, drawn from the seed, against the
    reference's window labels. With ``control`` the sample's labels come
    from the reference in bfloat16 put in the program's place. Returns
    {name: value}."""
    unscored = vote_bad = alarm_bad = 0
    sample_pool: list = []
    for sid, chunks in w.pushed.items():
        got = w.scored.get(sid, [])
        if [c[0] for c in got] != list(range(len(chunks))):
            unscored += abs(len(chunks) - len(got)) or 1
        votes = []
        for idx, vote, frac, _, preds in got:
            ref_vote, ref_frac = reference.chunk_vote(preds)
            vote_bad += int(vote != ref_vote or abs(frac - ref_frac) > 1e-6)
            votes.append(vote)
        alarms = reference.alarm_sequence(votes)
        alarm_bad += sum(int(a != g[3]) for a, g in zip(alarms, got))
        alarm_bad += int(reference.alarm_events(alarms)
                         != w.alarms.get(sid, []))
        sample_pool += [(sid, c[0]) for c in got if c[0] < len(chunks)]
    rng = np.random.default_rng([seed, 13])
    sample_chunks = fleet.cfg["check"]["sample_chunks"]
    pick = rng.choice(len(sample_pool), min(sample_chunks, len(sample_pool)),
                      replace=False)
    picked = [sample_pool[i] for i in sorted(pick)]
    if not picked:
        raise RuntimeError("no chunk was scored: nothing to compare")
    ids = np.asarray([w.pushed[s][i] for s, i in picked])
    labels = _labels(fleet.served, fleet.pool[ids], reference.EXACT)
    if control:
        served = _labels(fleet.served, fleet.pool[ids], reference.CONTROL)
    else:
        served = np.stack([dict((c[0], c[4]) for c in w.scored[sid])[i]
                           for sid, i in picked])
    return {
        "window_disagree": float(np.mean(labels != served)),
        "vote_mismatch": vote_bad,
        "alarm_mismatch": alarm_bad,
        "unscored": unscored,
        "sample_windows": int(labels.size),
        "alarms_raised": sum(1 for v in w.alarms.values()
                             for kind, _ in v if kind == "raised"),
    }


def _labels(s: reference.Served, chunks, num) -> np.ndarray:
    """(K, 60) window labels of the reference at ``num``."""
    feats = reference.features_in_blocks(chunks, num)
    x = reference.normalize(feats.reshape(-1, feats.shape[-1]), s.mean, s.std)
    return np.asarray(reference.predict(s.forest, x)).reshape(len(chunks), -1)
