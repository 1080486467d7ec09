"""Unified streaming-session serving API for seizure scoring.

This is THE public serving surface (paper Sec. 2.6 deployed): one frozen,
checkpointable scoring artifact and one engine that watches many patients'
EEG streams at once, with the k-of-m alarm rule evaluated on-device.

  * ``ScoringProgram`` -- everything inference needs, packed once: the
    dense ``PackedForest`` traversal tensors, the training feature
    statistics, and the static ``PipelineConfig``. Built via
    ``ScoringProgram.from_fitted`` and round-tripped through
    ``checkpoint.store`` (arrays) + a JSON sidecar (config).
  * ``SeizureEngine`` -- a continuous-batching slot scheduler (the
    ``serving.continuous`` design, ported from LM decode to chunk
    scoring): a fixed ``max_batch`` of slots, each bound to one patient
    session, whose donated device state carries that slot's (m,)-deep
    alarm ring INSIDE the jitted step. Finished sessions free their slot
    and the queue refills it mid-flight -- no drain-and-flush barrier.
  * ``StreamSession`` -- per-patient handle: ``push`` arbitrary-length
    window streams (the session assembles the paper's 60-window chunks
    internally); results come back from ``engine.poll()`` as typed
    events: ``ChunkScored``, ``AlarmRaised``, ``AlarmCleared``.

Division of labor: the device step scores a (B, D, W, C, N) batch of up
to ``replay_depth`` backlogged chunks per slot in ONE jitted program,
as a two-stage MEGABATCH step: (1) the heavy map phase -- MSPCA
denoise -> WPD features (``signal.frontend.megabatch_step``, every
chunk's halo assembled from its predecessor in the backlog buffer
itself) and the packed forest vote -- runs batched over the flattened
(B*D) chunk axis; (2) only the O(m) k-of-m alarm-ring advance stays a
``lax.scan`` over the precomputed (B, D) votes. A single-patient
catch-up therefore costs one batched dispatch, not D sequential
denoise+WPD+forest passes (the serial scan survives as the oracle path
behind ``SeizureEngine(megabatch=False)``). The host schedules
sessions into slots, splices evicted/admitted rings + frontend
context, enforces the optional latency budget (deadline-based partial
flush), and turns the (B, D) readbacks into per-chunk events.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import json
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.checkpoint import store as ckpt_store
from repro.core import rotation_forest as rf
from repro.kernels.forest import ops as forest_ops
from repro.signal import eeg_data, features, frontend, pipeline


# ---------------------------------------------------------------------------
# Events
# ---------------------------------------------------------------------------

class ChunkScored(NamedTuple):
    """One 8-minute chunk of one patient was scored."""

    patient_id: int
    chunk_index: int       # per-session sequence number (0-based)
    chunk_pred: int        # 1 = chunk voted preictal
    preictal_frac: float   # fraction of the chunk's windows voted preictal
    alarm: int             # k-of-m alarm state AFTER this chunk
    window_preds: np.ndarray  # (chunk_windows,) int32 per-window labels
    # Which installed program scored this chunk: the engine's running
    # program version (0 at construction, bumped by each ``swap_program``)
    # so callers can attribute every score to a model version across
    # live hot-swaps.
    program_version: int = 0


class AlarmRaised(NamedTuple):
    """The k-of-m rule transitioned 0 -> 1 at this chunk."""

    patient_id: int
    chunk_index: int


class AlarmCleared(NamedTuple):
    """The k-of-m rule transitioned 1 -> 0 (hits aged out of the ring)."""

    patient_id: int
    chunk_index: int


# ---------------------------------------------------------------------------
# ScoringProgram: the frozen inference artifact
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ScoringProgram:
    """Pack once, serve forever: the complete inference-time artifact.

    packed    : dense forest traversal tensors (``kernels.forest``).
    feat_mean : (F,) training feature means (z-score statistics).
    feat_std  : (F,) training feature stds.
    cfg       : the static ``PipelineConfig`` the forest was trained with.
    """

    packed: forest_ops.PackedForest
    feat_mean: jax.Array
    feat_std: jax.Array
    cfg: pipeline.PipelineConfig

    @classmethod
    def from_fitted(
        cls, fitted: pipeline.FittedPipeline, cfg: pipeline.PipelineConfig
    ) -> "ScoringProgram":
        """Lower a trained ``FittedPipeline`` into the serving artifact.
        This is the one place forest packing happens on the serving path
        (``rotation_forest.pack`` caches, so repeated calls are free)."""
        return cls(
            packed=rf.pack(fitted.forest),
            feat_mean=fitted.feat_mean,
            feat_std=fitted.feat_std,
            cfg=cfg,
        )

    # -- persistence (checkpoint/store arrays + JSON config sidecar) --------

    def _arrays(self) -> dict[str, jax.Array]:
        return {
            "proj": self.packed.proj,
            "thr": self.packed.thr,
            "leaf_probs": self.packed.leaf_probs,
            "feat_mean": self.feat_mean,
            "feat_std": self.feat_std,
        }

    def _to_arrays(self) -> dict[str, np.ndarray]:
        """The complete artifact as one flat checkpoint-store tree: the
        array leaves plus the static config as a uint8 JSON leaf -- the
        same encoding both ``save`` and the engine snapshot embed."""
        cfg_json = self.cfg._asdict()
        cfg_json["forest"] = self.cfg.forest._asdict()
        arrays = dict(self._arrays())
        arrays["cfg_json"] = np.frombuffer(
            json.dumps(cfg_json).encode(), dtype=np.uint8
        )
        return arrays

    @classmethod
    def _from_arrays(cls, arrays: dict) -> "ScoringProgram":
        """Inverse of ``_to_arrays`` (shared by ``load`` and
        ``SeizureEngine.restore``)."""
        cfg_json = json.loads(
            np.asarray(arrays.pop("cfg_json")).tobytes().decode()
        )
        forest_cfg = rf.RotationForestConfig(**cfg_json.pop("forest"))
        cfg = pipeline.PipelineConfig(forest=forest_cfg, **cfg_json)
        return cls(
            packed=forest_ops.PackedForest(
                proj=arrays["proj"], thr=arrays["thr"],
                leaf_probs=arrays["leaf_probs"],
            ),
            feat_mean=arrays["feat_mean"],
            feat_std=arrays["feat_std"],
            cfg=cfg,
        )

    def save(self, directory: str, step: int = 0) -> str:
        """Write the program under ``directory/step_<step>`` (atomic).

        The static config rides INSIDE the checkpoint as a uint8 leaf
        (JSON bytes), so the store's temp-dir + rename atomicity covers
        the whole artifact -- a killed save never leaves arrays without
        their config."""
        return ckpt_store.save(directory, step, self._to_arrays())

    @classmethod
    def load(cls, directory: str, step: int | None = None) -> "ScoringProgram":
        """Restore a saved program (latest step when ``step`` is None)."""
        if step is None:
            step = ckpt_store.latest_step(directory)
            if step is None:
                raise FileNotFoundError(
                    f"no ScoringProgram checkpoints under {directory!r} "
                    "(empty or missing directory)"
                )
        like = ckpt_store.manifest_like(directory, step)
        return cls._from_arrays(ckpt_store.restore(directory, step, like))


# ---------------------------------------------------------------------------
# Device step
# ---------------------------------------------------------------------------

class EngineState(NamedTuple):
    """Per-slot device state (leading axis = slot, sharded along ``data``).

    The sequential stream context lives HERE, inside the jitted step:
    ``rings[b]`` holds slot b's last ``alarm_m`` chunk votes
    (zero-initialized, so a ring with fewer than m votes written behaves
    exactly like the reference deque), ``ring_pos[b]`` the next cyclic
    write index, ``alarm[b]`` the k-of-m state after the slot's latest
    chunk, and ``fe_boundary[b]`` / ``fe_phase[b]`` the slot's streaming
    front-end context (``signal.frontend.FrontendState``) -- carried
    across engine steps AND across the in-step backlog-replay scan.
    """

    rings: jax.Array        # (B, m) int32
    ring_pos: jax.Array     # (B,) int32
    alarm: jax.Array        # (B,) int32
    fe_boundary: jax.Array  # (B, max(1, overlap), C, N) float32
    fe_phase: jax.Array     # (B,) int32

    def frontend_state(self) -> frontend.FrontendState:
        """The (B,)-leading slot frontend contexts as a FrontendState."""
        return frontend.FrontendState(
            boundary=self.fe_boundary, phase=self.fe_phase
        )


@functools.partial(
    jax.jit,
    static_argnames=("max_batch", "alarm_m", "n_channels", "window", "overlap"),
)
def init_state(
    max_batch: int,
    alarm_m: int,
    n_channels: int = eeg_data.N_CHANNELS,
    window: int = eeg_data.WINDOW,
    overlap: int = 0,
) -> EngineState:
    # jitted (all-static) so the zero-fill happens ON device: engine
    # construction stays legal under jax.transfer_guard("disallow").
    fe = frontend.init_batch(max_batch, n_channels, window, overlap)
    return EngineState(
        rings=jnp.zeros((max_batch, alarm_m), jnp.int32),
        ring_pos=jnp.zeros((max_batch,), jnp.int32),
        alarm=jnp.zeros((max_batch,), jnp.int32),
        fe_boundary=fe.boundary,
        fe_phase=fe.phase,
    )


def _vote_chunks(feats, packed, feat_mean, feat_std, *, use_pallas):
    """(B, W, F) feature rows -> per-chunk vote/fraction/preds: z-score
    with the training statistics, run the packed forest, majority-vote
    each chunk (paper: "half of total value"). The single voting
    implementation both the stateless score path and the engine's
    replay-scan body share."""
    b, w, f = feats.shape
    with jax.named_scope("vote"):
        normed, _, _ = features.normalize(feats.reshape(b * w, f),
                                          feat_mean, feat_std)
        probs = forest_ops.forest_predict_proba(
            packed, normed, use_pallas=use_pallas
        )
        preds = jnp.argmax(probs, axis=-1).reshape(b, w).astype(jnp.int32)
        frac = jnp.mean(preds.astype(jnp.float32), axis=1)
        votes = (frac > 0.5).astype(jnp.int32)
    return votes, frac, preds


def _score_chunks(chunks, packed, feat_mean, feat_std, *, cfg, use_pallas):
    """(B, W, C, N) raw chunk windows -> per-chunk vote/fraction/preds.

    The fused map phase: denoise each chunk matrix (the shared
    ``frontend.chunk_features`` entry point), then the shared
    ``_vote_chunks`` voting block. One XLA program.
    """
    feats = jax.vmap(lambda m: frontend.chunk_features(m, cfg))(chunks)
    return _vote_chunks(
        feats, packed, feat_mean, feat_std, use_pallas=use_pallas
    )


def _engine_step(state, chunks, active, packed, feat_mean, feat_std,
                 *, cfg, use_pallas):
    """Scan each slot over its chunk backlog AND advance the on-device
    sequential state (alarm rings + frontend context) -- one jitted step.

    ``chunks`` is (B, D, W, C, N): up to D backlogged chunks per slot,
    valid-prefix order. ``active`` is a (B, D) 0/1 mask: masked entries
    (padding rows / slots with a shallower backlog) keep their
    ring/pos/alarm/frontend untouched. The backlog axis is a
    ``lax.scan`` (the alarm ring is a genuine sequential dependency);
    everything is per-slot independent across the batch axis, so the
    state advances shardable along ``data``. Returns per-chunk
    (B, D)-shaped votes/fracs/alarms and (B, D, W) window preds.

    This is the SERIAL ORACLE: the megabatch step
    (``_engine_step_megabatch``, the engine default) must emit
    byte-identical events; keep this scan as the reference the equality
    suite (tests/test_megabatch_replay.py) pins it against.
    """
    b, m = state.rings.shape
    rows = jnp.arange(b)  # loop-invariant: hoisted out of the scan body

    def body(st, inp):
        ch, act = inp  # (B, W, C, N), (B,)
        fe, feats = jax.vmap(
            lambda s, c_: frontend.frontend_step(s, c_, cfg)
        )(st.frontend_state(), ch)
        votes, frac, preds = _vote_chunks(
            feats, packed, feat_mean, feat_std, use_pallas=use_pallas
        )
        votes = votes * act
        written = st.rings.at[rows, st.ring_pos].set(votes)
        rings = jnp.where(act[:, None] > 0, written, st.rings)
        ring_pos = jnp.where(act > 0, (st.ring_pos + 1) % m, st.ring_pos)
        hits = jnp.sum(rings, axis=1)
        alarm = jnp.where(
            act > 0, (hits >= cfg.alarm_k).astype(jnp.int32), st.alarm
        )
        new = EngineState(
            rings=rings, ring_pos=ring_pos, alarm=alarm,
            fe_boundary=jnp.where(
                act[:, None, None, None] > 0, fe.boundary, st.fe_boundary
            ),
            fe_phase=jnp.where(act > 0, fe.phase, st.fe_phase),
        )
        return new, (votes, frac, alarm, preds)

    state, (votes, frac, alarm, preds) = jax.lax.scan(
        body, state,
        (jnp.swapaxes(chunks, 0, 1), jnp.swapaxes(active, 0, 1)),
    )
    # Scan stacks outputs (D, B, ...); hand the host (B, D, ...) views.
    return (
        state, votes.T, frac.T, alarm.T, jnp.swapaxes(preds, 0, 1)
    )


def _engine_step_megabatch(state, chunks, active, packed, feat_mean,
                           feat_std, *, cfg, use_pallas):
    """The de-serialized engine step: same contract as ``_engine_step``
    (byte-identical events), two stages instead of a D-deep heavy scan.

    Stage 1 (batched heavy): ``frontend.megabatch_step`` assembles every
    backlog chunk's denoise halo from its predecessor IN the (B, D)
    buffer (only chunk 0 consumes the carried ``fe_boundary``; the
    closed-form boundary/phase advance needs ``active`` to be prefix
    masks, which is the only shape ``_step_once`` produces), then ONE
    flattened (B*D) pass runs denoise + WPD + the forest vote -- the
    paper's embarrassingly parallel map phase, restored: a depth-D
    catch-up costs one batched dispatch, not D sequential passes.

    Stage 2 (thin sequential): the ``lax.scan`` survives only as the
    O(m)-per-step masked alarm-ring advance over the precomputed (B, D)
    votes -- the one genuine sequential dependency.

    Outputs for INACTIVE (padding) positions: votes are masked to 0 and
    the alarm sequence carries the slot's running alarm either way --
    both bit-identical to the serial scan. ``frac``/``preds`` of padding
    positions are computed from whatever stale windows sit in the buffer
    (the serial scan reuses the post-backlog state instead); the host
    never reads them (``_step_once`` walks only the popped prefix).
    """
    b, m = state.rings.shape
    d = chunks.shape[1]
    active = active.astype(jnp.int32)
    fe, feats = frontend.megabatch_step(
        state.frontend_state(), chunks, active, cfg
    )
    w = feats.shape[2]
    votes, frac, preds = _vote_chunks(
        feats.reshape(b * d, w, -1), packed, feat_mean, feat_std,
        use_pallas=use_pallas,
    )
    votes = votes.reshape(b, d) * active
    frac = frac.reshape(b, d)
    preds = preds.reshape(b, d, w)

    rows = jnp.arange(b)  # loop-invariant: hoisted out of the ring scan

    def ring_body(st, inp):
        rings_, pos_, alarm_ = st
        v, act = inp  # (B,), (B,)
        written = rings_.at[rows, pos_].set(v)
        rings = jnp.where(act[:, None] > 0, written, rings_)
        pos = jnp.where(act > 0, (pos_ + 1) % m, pos_)
        hits = jnp.sum(rings, axis=1)
        alarm = jnp.where(
            act > 0, (hits >= cfg.alarm_k).astype(jnp.int32), alarm_
        )
        return (rings, pos, alarm), alarm

    with jax.named_scope("ring"):
        (rings, ring_pos, alarm), alarm_seq = jax.lax.scan(
            ring_body, (state.rings, state.ring_pos, state.alarm),
            (votes.T, active.T),
        )
    new_state = EngineState(
        rings=rings, ring_pos=ring_pos, alarm=alarm,
        fe_boundary=fe.boundary, fe_phase=fe.phase,
    )
    return new_state, votes, frac, alarm_seq.T, preds


# One shared jit cache across engine instances (cfg/use_pallas static).
# Only the state (arg 0) is donated: every EngineState leaf aliases the
# matching output leaf 1:1, so the donation survives lowering (checked
# by repro.analysis `donation-surviving`). The chunk batch used to be
# donated too, but no output shares its shape/dtype, so XLA silently
# dropped that donation at lowering -- declaring it bought nothing.
_jit_engine_step = functools.partial(
    jax.jit, static_argnames=("cfg", "use_pallas"), donate_argnums=(0,)
)(_engine_step)

_jit_engine_step_megabatch = functools.partial(
    jax.jit, static_argnames=("cfg", "use_pallas"), donate_argnums=(0,)
)(_engine_step_megabatch)

_jit_score_chunks = functools.partial(
    jax.jit, static_argnames=("cfg", "use_pallas")
)(_score_chunks)


@functools.partial(jax.jit, donate_argnums=(0,))
def _splice_state(
    state: EngineState, slot, ring, pos, alarm, boundary, phase
) -> EngineState:
    """Write one session's saved (ring, pos, alarm, frontend context)
    into slot ``slot``.

    ``slot`` is a traced scalar (dynamic_update_slice), so one compiled
    program covers every slot index."""
    rings = jax.lax.dynamic_update_slice(
        state.rings, ring[None].astype(state.rings.dtype), (slot, 0)
    )
    fe_boundary = jax.lax.dynamic_update_slice(
        state.fe_boundary,
        boundary[None].astype(state.fe_boundary.dtype),
        (slot, 0, 0, 0),
    )
    return EngineState(
        rings=rings,
        ring_pos=state.ring_pos.at[slot].set(pos),
        alarm=state.alarm.at[slot].set(alarm),
        fe_boundary=fe_boundary,
        fe_phase=state.fe_phase.at[slot].set(phase),
    )


@jax.jit
def _install_state(state: EngineState) -> EngineState:
    """Restore-path state install: cast every snapshot leaf to the
    engine state's canonical avals (strong int32/float32).

    The first engine step after ``SeizureEngine.restore`` must be a jit
    CACHE HIT in a warm process -- any aval drift (a weak type or dtype
    picked up on the disk round-trip) would recompile the step per
    restore. Registered as ``serving.engine_restore``: the carry-stable
    contract rule pins output avals == input avals statically."""
    return EngineState(
        rings=state.rings.astype(jnp.int32),
        ring_pos=state.ring_pos.astype(jnp.int32),
        alarm=state.alarm.astype(jnp.int32),
        fe_boundary=state.fe_boundary.astype(jnp.float32),
        fe_phase=state.fe_phase.astype(jnp.int32),
    )


@jax.jit
def _install_program_arrays(packed, feat_mean, feat_std):
    """Program install: cast a (new) program's array leaves to the
    serving step's pinned avals (strong float32).

    Every program the engine serves -- the constructor's, a restored
    snapshot's, or a live ``swap_program`` push -- goes through this, so
    installing a same-shape program can NEVER change the step's input
    avals: the program arrays are step *inputs* (never baked into the
    compiled program), which is what makes the hot-swap drain-free with
    zero recompiles. Registered as ``serving.engine_swap_program``."""
    return (
        forest_ops.PackedForest(
            proj=packed.proj.astype(jnp.float32),
            thr=packed.thr.astype(jnp.float32),
            leaf_probs=packed.leaf_probs.astype(jnp.float32),
        ),
        feat_mean.astype(jnp.float32),
        feat_std.astype(jnp.float32),
    )


# ---------------------------------------------------------------------------
# Sessions
# ---------------------------------------------------------------------------

class StreamSession:
    """One patient's stream handle (created by ``SeizureEngine.open_session``).

    ``push`` accepts ANY number of raw 8-second windows -- (W, C, N) for
    W >= 0, or a single (C, N) window; the session buffers partial chunks
    and enqueues each completed ``chunk_windows``-window chunk for
    scoring. Per-session chunk order is FIFO; results arrive as events
    from ``engine.poll()``.
    """

    def __init__(self, engine: "SeizureEngine", patient_id: int):
        self._engine = engine
        self.patient_id = patient_id
        # Completed chunks awaiting scoring: (enqueue_time, windows)
        # pairs -- the timestamp drives the engine's latency budget.
        self.chunks: collections.deque[tuple[float, np.ndarray]] = (
            collections.deque()
        )
        self._buf = np.zeros(
            (0, eeg_data.N_CHANNELS, eeg_data.WINDOW), np.float32
        )
        # Host copies of the alarm ring and streaming-frontend context;
        # authoritative only while the session is NOT resident in a slot
        # (the device copy rules then).
        self.ring = np.zeros((engine.alarm_m,), np.int32)
        self.ring_pos = 0
        self.alarm = 0
        self.fe_boundary = np.zeros(
            (engine.fe_width, eeg_data.N_CHANNELS, eeg_data.WINDOW),
            np.float32,
        )
        self.fe_phase = 0
        self.chunk_seq = 0
        self.slot: int | None = None
        self.queued = False
        self.closed = False

    # -- public ------------------------------------------------------------

    def push(self, windows) -> int:
        """Buffer raw windows; returns the number of now-complete chunks
        waiting to be scored (engine-wide scheduling happens in ``poll``)."""
        if self.closed:
            raise RuntimeError(f"session {self.patient_id} is closed")
        windows = np.asarray(windows, np.float32)
        if windows.ndim == 2:
            windows = windows[None]
        expect = (eeg_data.N_CHANNELS, eeg_data.WINDOW)
        if windows.ndim != 3 or windows.shape[1:] != expect:
            raise ValueError(
                f"windows shape {windows.shape} != (W, {expect[0]}, {expect[1]})"
            )
        # Copy on adopt: np.asarray is a no-copy pass-through for float32
        # input, and queued chunks are sliced views of _buf -- without the
        # copy they would alias (and silently track) the caller's buffer.
        self._buf = (
            np.concatenate([self._buf, windows]) if self._buf.size
            else windows.copy()
        )
        per = self._engine.chunk_windows
        now = self._engine._clock()
        while self._buf.shape[0] >= per:
            self.chunks.append((now, self._buf[:per]))
            self._buf = self._buf[per:]
        if self.chunks:
            self._engine._mark_ready(self)
        return len(self.chunks)

    @property
    def pending_windows(self) -> int:
        """Windows buffered toward the next (incomplete) chunk."""
        return int(self._buf.shape[0])

    @property
    def pending_chunks(self) -> int:
        """Complete chunks waiting to be scored."""
        return len(self.chunks)

    def close(self) -> None:
        self._engine.close_session(self.patient_id)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class SeizureEngine:
    """Continuous-batching multi-patient seizure-scoring engine.

    program       : the frozen ``ScoringProgram`` to serve.
    max_batch     : number of device slots (one compiled program per
                    backlog depth, ever).
    chunk_windows : windows per chunk (the paper's 60).
    replay_depth  : backlogged chunks ONE engine step scores per slot
                    (the megabatch D axis). 1 reproduces the
                    chunk-per-step schedule exactly; deeper replay gives
                    a backlogged session (e.g. single-patient catch-up
                    after an uplink outage) up to ``replay_depth`` chunks
                    per dispatch with byte-identical events. Every step
                    pads to this FIXED depth, so steady-state and replay
                    traffic share one compiled program (engine recompile
                    budget == 1, enforced by ``repro.analysis``).
    megabatch     : True (default) runs ``_engine_step_megabatch`` --
                    denoise+WPD+forest batched over the whole (B, D)
                    backlog, only the alarm-ring advance sequential.
                    False keeps the serial per-chunk ``lax.scan``
                    (``_engine_step``): the oracle path the equality
                    suite and the serving bench's baseline leg run.
    latency_budget_s : deadline for ``poll(drain=False)``: a partial
                    batch is flushed anyway once the OLDEST queued chunk
                    has waited longer than this many seconds (None keeps
                    the pure dense-batching trade-off).
    mesh          : optional mesh; slots are sharded along ``data``.
    use_forest_kernel : route the forest stage through the Pallas kernel
                    (interpret mode off-TPU); default pure-JAX traversal.
    clock         : monotonic time source for the latency budget
                    (injectable for tests; default ``time.monotonic``).

    Scheduling: each slot is bound to at most one session; a session
    scores its chunks strictly in order (its alarm ring and streaming
    front-end context are carried in the slot's device state between
    steps and across the in-step replay scan). After every step, slots
    whose session has nothing ready are freed and refilled from the
    waiting queue -- new work joins mid-flight, in-flight sessions never
    stall.

    With ``program.cfg.overlap > 0`` each slot's carried frontend
    context is the (overlap, C, N) raw-window denoise halo: the MSPCA
    stage of every chunk sees the previous chunk's tail, and the halo
    payload rides the same evict/admit splice as the alarm ring, so
    eviction churn cannot perturb the numerics (property-tested in
    tests/test_engine_properties.py).

    Counters, plain ints (one float) that only grow, for operators:

      steps           jitted step invocations (restored from a snapshot).
      chunks_scored   chunks the steps took from session queues.
      slot_positions  chunk positions the steps computed, max_batch x
                      replay_depth each: chunks_scored / slot_positions
                      is the share of the step that was not padding.
      h2d_bytes       bytes copied host -> device: each step's batch and
                      mask, and each admission's saved stream state.
      d2h_bytes       bytes copied device -> host: each step's votes,
                      fractions, alarms and window predictions, and the
                      state that evictions and frontend syncs pull.
      evictions       drained sessions pulled out of a slot.
      admissions      session states spliced into a slot (``reset_alarm``
                      re-splices a resident one).
      queue_wait_s    sum over the chunks taken of (step start - the
                      chunk's enqueue time), on the engine's ``clock``.

    All but ``steps`` count from construction or restore. While a
    ``jax.profiler`` trace runs, ``poll`` also writes the host spans
    ``seizure.fill`` (``_fill_slots``) and, per step,
    ``seizure.assemble``, ``seizure.put``, ``seizure.dispatch``,
    ``seizure.readback`` and ``seizure.events``, with the counters' share
    of that span as its args (``repro.obs``).
    """

    def __init__(
        self,
        program: ScoringProgram,
        *,
        max_batch: int = 8,
        chunk_windows: int = eeg_data.WINDOWS_PER_MATRIX,
        replay_depth: int = 1,
        megabatch: bool = True,
        latency_budget_s: float | None = None,
        mesh: Mesh | None = None,
        use_forest_kernel: bool = False,
        clock=time.monotonic,
    ):
        if replay_depth < 1:
            raise ValueError(f"replay_depth={replay_depth} must be >= 1")
        self.program = program
        self.max_batch = max_batch
        self.chunk_windows = chunk_windows
        self.replay_depth = replay_depth
        self.megabatch = megabatch
        self.latency_budget_s = latency_budget_s
        self.mesh = mesh
        self.use_forest_kernel = use_forest_kernel
        self.alarm_m = program.cfg.alarm_m
        # Carried boundary windows per slot (the cross-chunk denoise halo
        # when cfg.overlap > 0; a single carried-but-unused window else).
        self.fe_width = frontend.boundary_width(program.cfg.overlap)
        self.steps = 0  # jitted step invocations (scheduling observability)
        self.chunks_scored = 0
        self.slot_positions = 0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.evictions = 0
        self.admissions = 0
        self.queue_wait_s = 0.0
        self.program_version = 0  # bumped by each swap_program
        self._clock = clock

        self._sessions: dict[int, StreamSession] = {}
        self._slots: list[StreamSession | None] = [None] * max_batch
        self._waiting: collections.deque[StreamSession] = collections.deque()
        self._state = init_state(
            max_batch, self.alarm_m, overlap=program.cfg.overlap
        )

        step_fn = _engine_step_megabatch if megabatch else _engine_step
        if mesh is None:
            self._step = (
                _jit_engine_step_megabatch if megabatch else _jit_engine_step
            )
            self._splice = _splice_state
            self._score = _jit_score_chunks
            self._state_sharding = None
            self._program_sharding = None
        else:
            # The step declares shardings only at its jit boundary, so it
            # runs on an Auto-axes view of the caller's devices (an
            # Explicit-axes mesh, ``jax.make_mesh``'s default, would type
            # every intermediate's sharding).
            mesh = Mesh(
                mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names),
            )
            self.mesh = mesh
            if max_batch % mesh.shape["data"] != 0:
                raise ValueError(
                    f"max_batch={max_batch} not divisible by mesh "
                    f"data axis {mesh.shape['data']}"
                )
            data = NamedSharding(mesh, P("data"))
            repl = NamedSharding(mesh, P())
            state_sh = EngineState(
                rings=data, ring_pos=data, alarm=data,
                fe_boundary=data, fe_phase=data,
            )
            self._state = jax.device_put(self._state, state_sh)
            self._state_sharding = state_sh
            self._program_sharding = (
                forest_ops.PackedForest(proj=repl, thr=repl, leaf_probs=repl),
                repl, repl,
            )
            # Bind the static config via partial: jit rejects kwargs once
            # in_shardings is given.
            statics = dict(cfg=program.cfg, use_pallas=use_forest_kernel)
            jit_step = jax.jit(
                functools.partial(step_fn, **statics),
                donate_argnums=(0,),
                in_shardings=(state_sh, data, data, repl, repl, repl),
                out_shardings=(state_sh, data, data, data, data),
            )
            jit_score = jax.jit(
                functools.partial(_score_chunks, **statics),
                in_shardings=(data, repl, repl, repl),
                out_shardings=(data, data, data),
            )
            # Same call signature as the shared jits (statics are baked in).
            self._step = lambda *a, cfg, use_pallas: jit_step(*a)
            self._score = lambda *a, cfg, use_pallas: jit_score(*a)
            self._splice = jax.jit(
                _splice_state,
                donate_argnums=(0,),
                in_shardings=(state_sh,) + (repl,) * 6,
                out_shardings=state_sh,
            )

        # Canonicalize the program leaves through the SAME install path a
        # later ``swap_program`` takes, so the construction-time program
        # and every hot-swapped successor present identical avals to the
        # step: the swap is then a guaranteed jit cache hit.
        self.program = self._install_program(program)

    # -- program install / hot-swap ------------------------------------------

    def _install_program(self, program: ScoringProgram) -> ScoringProgram:
        packed, mean, std = _install_program_arrays(
            program.packed, program.feat_mean, program.feat_std
        )
        if self._program_sharding is not None:
            packed, mean, std = jax.device_put(
                (packed, mean, std), self._program_sharding
            )
        return dataclasses.replace(
            program, packed=packed, feat_mean=mean, feat_std=std
        )

    def swap_program(
        self, new_program: ScoringProgram, *, version: int | None = None
    ) -> int:
        """Install a newly trained ``ScoringProgram`` into the RUNNING
        engine -- no session drain, no step recompile.

        The program arrays are step *inputs* (never constants baked into
        the compiled step), so as long as the new program's packed shapes
        match the old one's, the very next ``poll`` serves the new model:
        in-flight alarm rings and frontend context are untouched, and
        every subsequent ``ChunkScored`` carries the bumped
        ``program_version``. Shape or static-config drift is rejected
        up front with a ``ValueError`` (a differently shaped forest needs
        a new engine -- its step would have to recompile anyway).

        Returns the now-serving program version (``version`` if given,
        else the running version + 1).
        """
        if new_program.cfg != self.program.cfg:
            raise ValueError(
                "swap_program: new program's PipelineConfig differs from "
                f"the serving one ({new_program.cfg} != {self.program.cfg}); "
                "the static config is compiled into the step -- open a new "
                "engine instead"
            )
        old, new = self.program._arrays(), new_program._arrays()
        mismatched = [
            f"{k}: {tuple(new[k].shape)}/{new[k].dtype} != "
            f"{tuple(old[k].shape)}/{old[k].dtype}"
            for k in old
            if tuple(new[k].shape) != tuple(old[k].shape)
            or np.dtype(new[k].dtype) != np.dtype(old[k].dtype)
        ]
        if mismatched:
            raise ValueError(
                "swap_program: packed shapes must match the serving "
                "program (drain-free swap keeps the step's avals fixed); "
                "mismatched leaves: " + "; ".join(mismatched)
            )
        self.program = self._install_program(new_program)
        self.program_version = (
            self.program_version + 1 if version is None else int(version)
        )
        return self.program_version

    # -- sessions ------------------------------------------------------------

    def open_session(self, patient_id: int) -> StreamSession:
        patient_id = int(patient_id)
        if patient_id in self._sessions:
            raise ValueError(f"session for patient {patient_id} already open")
        session = StreamSession(self, patient_id)
        self._sessions[patient_id] = session
        return session

    def session(self, patient_id: int) -> StreamSession | None:
        return self._sessions.get(int(patient_id))

    def close_session(self, patient_id: int) -> None:
        """Drop a session and its alarm state (unscored chunks included)."""
        session = self._sessions.pop(int(patient_id), None)
        if session is None:
            return
        if session.slot is not None:
            self._slots[session.slot] = None
            session.slot = None
        if session.queued:
            self._waiting.remove(session)
            session.queued = False
        session.closed = True

    def alarm_state(self, patient_id: int) -> int:
        """Current k-of-m alarm state (0 if the patient is unknown)."""
        session = self._sessions.get(int(patient_id))
        return int(session.alarm) if session is not None else 0

    def reset_alarm(self, patient_id: int) -> None:
        """Zero a session's alarm ring WITHOUT touching its queued or
        buffered windows (e.g. after a confirmed false alarm)."""
        session = self._sessions.get(int(patient_id))
        if session is None:
            return
        if session.slot is not None:
            # The device copy of the frontend context is authoritative
            # while resident: pull it down so re-admitting the zeroed
            # ring does not also rewind the stream context.
            self._sync_frontend(session.slot, session)
        session.ring = np.zeros((self.alarm_m,), np.int32)
        session.ring_pos = 0
        session.alarm = 0
        if session.slot is not None:
            self._admit(session.slot, session)  # re-splice the zeroed ring

    def _mark_ready(self, session: StreamSession) -> None:
        if session.slot is None and not session.queued:
            self._waiting.append(session)
            session.queued = True

    # -- slot scheduling -----------------------------------------------------

    def _sync_frontend(self, slot: int, session: StreamSession) -> None:
        """Pull the slot's device frontend context into the session."""
        # device_get the whole leaves, then index on the host: slicing a
        # device array with a host int rides jax's cached-gather path,
        # which ships the index device-side as an implicit transfer (a
        # transfer_guard violation). Eviction/sync are rare lifecycle
        # events and the state is small, so the full pull is cheap.
        boundary, phase = jax.device_get((
            self._state.fe_boundary, self._state.fe_phase
        ))
        self.d2h_bytes += boundary.nbytes + phase.nbytes
        session.fe_boundary = np.asarray(boundary[slot])
        session.fe_phase = int(phase[slot])

    def _evict(self, slot: int) -> None:
        """Pull the slot's device stream state back into the session."""
        session = self._slots[slot]
        # One host sync of the full (small) state, indexed on the host --
        # see _sync_frontend for why device-side int indexing is out.
        ring, pos, alarm, boundary, phase = jax.device_get((
            self._state.rings,
            self._state.ring_pos,
            self._state.alarm,
            self._state.fe_boundary,
            self._state.fe_phase,
        ))
        self.d2h_bytes += sum(a.nbytes for a in (ring, pos, alarm, boundary,
                                                 phase))
        self.evictions += 1
        session.ring = np.asarray(ring[slot])
        session.ring_pos = int(pos[slot])
        session.alarm = int(alarm[slot])
        session.fe_boundary = np.asarray(boundary[slot])
        session.fe_phase = int(phase[slot])
        session.slot = None
        self._slots[slot] = None

    def _admit(self, slot: int, session: StreamSession) -> None:
        """Splice the session's saved stream state (alarm ring + frontend
        context) into the slot's device state."""
        # Explicit host->device handoff (jax.device_put, not jnp.asarray):
        # the engine/frontend suites run these paths under
        # jax.transfer_guard("disallow"), which turns any IMPLICIT
        # transfer into an error -- every intentional crossing is spelled
        # out (tests/conftest.py `device_transfer_sanitizer`).
        saved = (
            np.int32(slot),
            np.asarray(session.ring, np.int32),
            np.int32(session.ring_pos),
            np.int32(session.alarm),
            np.asarray(session.fe_boundary, np.float32),
            np.int32(session.fe_phase),
        )
        self._state = self._splice(
            self._state, *(jax.device_put(a) for a in saved)
        )
        self.h2d_bytes += sum(a.nbytes for a in saved)
        self.admissions += 1
        session.slot = slot
        session.queued = False
        self._slots[slot] = session

    def _fill_slots(self) -> None:
        before = (self.evictions, self.admissions, self.d2h_bytes,
                  self.h2d_bytes)
        with obs.span("fill") as span:
            for i in range(self.max_batch):
                occupant = self._slots[i]
                if (occupant is not None and not occupant.chunks
                        and self._waiting):
                    self._evict(i)  # refill mid-flight: drained session yields
                if self._slots[i] is None and self._waiting:
                    self._admit(i, self._waiting.popleft())
            span.set_metadata(
                evictions=self.evictions - before[0],
                admissions=self.admissions - before[1],
                d2h_bytes=self.d2h_bytes - before[2],
                h2d_bytes=self.h2d_bytes - before[3],
            )

    # -- serving -------------------------------------------------------------

    def _deadline_exceeded(self) -> bool:
        """True iff the latency budget is set and the OLDEST queued chunk
        (across every session, resident or waiting) has outlived it."""
        if self.latency_budget_s is None:
            return False
        oldest = min(
            (s.chunks[0][0] for s in self._sessions.values() if s.chunks),
            default=None,
        )
        return (
            oldest is not None
            and self._clock() - oldest >= self.latency_budget_s
        )

    def poll(self, *, drain: bool = True) -> list:
        """Score ready chunks and return the resulting events.

        drain=True (default) scores EVERYTHING ready, zero-padding a final
        partial batch. drain=False runs only full batches -- leftovers wait
        for future pushes to pack densely (throughput mode) UNLESS the
        engine's ``latency_budget_s`` is set and the oldest queued chunk
        has already waited past it, in which case the partial batch is
        flushed anyway (the deadline-based middle ground between
        per-chunk dispatch and unbounded tail latency). Call ``poll()``
        (or ``drain=True``) to flush the tail unconditionally.
        """
        events: list = []
        while True:
            self._fill_slots()
            active = [
                i for i, s in enumerate(self._slots)
                if s is not None and s.chunks
            ]
            if not active:
                break
            if (
                not drain
                and len(active) < self.max_batch
                and not self._deadline_exceeded()
            ):
                break
            events.extend(self._step_once(active))
        return events

    def _step_once(self, active: list[int]) -> list:
        start = self._clock()
        with obs.span("assemble") as span:
            # Fixed D: every step pads the backlog axis to ``replay_depth``,
            # so steady-state and replay traffic run ONE compiled program.
            depth = self.replay_depth
            batch = np.zeros(
                (self.max_batch, depth, self.chunk_windows,
                 eeg_data.N_CHANNELS, eeg_data.WINDOW),
                np.float32,
            )
            mask = np.zeros((self.max_batch, depth), np.int32)
            popped: dict[int, int] = {}
            wait = 0.0
            for i in active:
                session = self._slots[i]
                take = min(depth, len(session.chunks))
                for j in range(take):
                    enqueued, batch[i, j] = session.chunks.popleft()
                    wait += start - enqueued
                    mask[i, j] = 1
                popped[i] = take
            taken = sum(popped.values())
            span.set_metadata(chunks=taken, queue_wait_s=wait)
        self.chunks_scored += taken
        self.slot_positions += self.max_batch * depth
        self.queue_wait_s += wait
        program = self.program
        sent = batch.nbytes + mask.nbytes
        with obs.span("put", bytes=sent):
            # device_put, not jnp.asarray: the batch crossing is an
            # EXPLICIT transfer, legal under jax.transfer_guard("disallow").
            batch, mask = jax.device_put(batch), jax.device_put(mask)
        self.h2d_bytes += sent
        with obs.span("dispatch"):
            self._state, votes, frac, alarm, preds = self._step(
                self._state, batch, mask,
                program.packed, program.feat_mean, program.feat_std,
                cfg=program.cfg, use_pallas=self.use_forest_kernel,
            )
        self.steps += 1
        with obs.span("readback") as span:
            votes, frac, alarm, preds = jax.device_get(
                (votes, frac, alarm, preds)
            )
            got = votes.nbytes + frac.nbytes + alarm.nbytes + preds.nbytes
            span.set_metadata(bytes=got)
        self.d2h_bytes += got
        events: list = []
        with obs.span("events"):
            for i in active:
                session = self._slots[i]
                for j in range(popped[i]):
                    prev_alarm, session.alarm = session.alarm, int(alarm[i, j])
                    events.append(ChunkScored(
                        patient_id=session.patient_id,
                        chunk_index=session.chunk_seq,
                        chunk_pred=int(votes[i, j]),
                        preictal_frac=float(frac[i, j]),
                        alarm=session.alarm,
                        window_preds=np.asarray(preds[i, j]),
                        program_version=self.program_version,
                    ))
                    if session.alarm > prev_alarm:
                        events.append(
                            AlarmRaised(session.patient_id, session.chunk_seq)
                        )
                    elif session.alarm < prev_alarm:
                        events.append(
                            AlarmCleared(session.patient_id, session.chunk_seq)
                        )
                    session.chunk_seq += 1
        return events

    def score_chunks(self, chunks) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Stateless raw step: an already-assembled (B, W, C, N) batch ->
        (votes (B,), preictal_frac (B,), window_preds (B, W)) WITHOUT
        touching any session's alarm ring. (This is the PR-1
        ``score_batch`` contract.) A host batch crosses to the device via
        an explicit ``jax.device_put``; a device-resident batch passes
        through untouched, so the whole call is transfer-free under
        ``jax.transfer_guard("disallow")``."""
        program = self.program
        return self._score(
            jax.device_put(chunks), program.packed,
            program.feat_mean, program.feat_std,
            cfg=program.cfg, use_pallas=self.use_forest_kernel,
        )

    # -- persistence (snapshot / restore) ------------------------------------

    def snapshot(self, directory: str, step: int) -> str:
        """Persist the COMPLETE engine -- device state, every session's
        host bookkeeping, and the serving program -- as one atomic
        checkpoint (``checkpoint.store``'s temp-dir + rename writer, so a
        killed snapshot never leaves a half-written step).

        Snapshotting is non-mutating (pure ``jax.device_get`` reads): the
        running engine continues bit-exactly whether or not a snapshot
        was taken. Layout is one flat array tree:

          * ``state__<leaf>``     -- the (B,)-leading ``EngineState``.
          * ``program__<leaf>``   -- ``ScoringProgram._to_arrays()``.
          * ``sess<pid>__<leaf>`` -- per-session queued chunks (k, W, C,
            N), partial-chunk buffer, alarm ring, frontend halo.
          * ``host_json``         -- uint8 JSON bytes: engine kwargs,
            per-session scalars + queue ages, slot binding, and the
            waiting-queue order (everything scheduling depends on).

        Queued-chunk timestamps are stored as AGES (now - t) and rebased
        onto the restoring engine's clock, so the latency budget keeps
        meaning across a restart."""
        arrays: dict[str, np.ndarray] = {}
        for name, leaf in jax.device_get(self._state)._asdict().items():
            arrays[f"state__{name}"] = np.asarray(leaf)
        for name, leaf in self.program._to_arrays().items():
            arrays[f"program__{name}"] = np.asarray(jax.device_get(leaf))
        now = self._clock()
        sessions_meta = []
        for pid, s in self._sessions.items():
            tag = f"sess{pid:08d}"
            queued = [w for (_, w) in s.chunks]
            arrays[f"{tag}__chunks"] = (
                np.stack(queued).astype(np.float32) if queued
                else np.zeros(
                    (0, self.chunk_windows, eeg_data.N_CHANNELS,
                     eeg_data.WINDOW), np.float32,
                )
            )
            arrays[f"{tag}__buf"] = np.asarray(s._buf, np.float32)
            arrays[f"{tag}__ring"] = np.asarray(s.ring, np.int32)
            arrays[f"{tag}__fe_boundary"] = np.asarray(
                s.fe_boundary, np.float32
            )
            sessions_meta.append({
                "patient_id": pid,
                "ring_pos": int(s.ring_pos),
                "alarm": int(s.alarm),
                "fe_phase": int(s.fe_phase),
                "chunk_seq": int(s.chunk_seq),
                "slot": s.slot,
                "queued": bool(s.queued),
                "chunk_ages": [float(now - t) for (t, _) in s.chunks],
            })
        host = {
            "format": 1,
            "engine": {
                "max_batch": self.max_batch,
                "chunk_windows": self.chunk_windows,
                "replay_depth": self.replay_depth,
                "megabatch": self.megabatch,
                "latency_budget_s": self.latency_budget_s,
                "use_forest_kernel": self.use_forest_kernel,
                "steps": self.steps,
                "program_version": self.program_version,
            },
            "sessions": sessions_meta,
            "waiting": [s.patient_id for s in self._waiting],
        }
        arrays["host_json"] = np.frombuffer(
            json.dumps(host).encode(), dtype=np.uint8
        )
        return ckpt_store.save(directory, step, arrays)

    @classmethod
    def restore(
        cls,
        directory: str,
        step: int | None = None,
        *,
        megabatch: bool | None = None,
        mesh: Mesh | None = None,
        clock=time.monotonic,
    ) -> "SeizureEngine":
        """Rebuild a bit-identical engine from a ``snapshot`` (latest
        step when ``step`` is None): the event stream it emits from here
        on is byte-identical to the uninterrupted engine's (pinned by
        tests/test_engine_checkpoint.py).

        ``megabatch``/``mesh``/``clock`` may be overridden (the step
        implementations are event-equal by the megabatch equality suite,
        so switching them cannot perturb results); everything else comes
        from the snapshot. The restored state passes through the jitted
        ``_install_state`` canonicalizer, so in a warm process the first
        post-restore step is a jit cache hit (``serving.engine_restore``
        budget = 0 extra compiles)."""
        if step is None:
            step = ckpt_store.latest_step(directory)
            if step is None:
                raise FileNotFoundError(
                    f"no engine snapshots under {directory!r} "
                    "(empty or missing directory)"
                )
        like = ckpt_store.manifest_like(directory, step)
        arrays = ckpt_store.restore(directory, step, like)
        host = json.loads(
            np.asarray(jax.device_get(arrays["host_json"])).tobytes().decode()
        )
        if host.get("format") != 1:
            raise ValueError(
                f"unsupported engine snapshot format {host.get('format')!r} "
                f"in {directory!r} step {step}"
            )
        eng = host["engine"]
        program = ScoringProgram._from_arrays({
            k[len("program__"):]: v
            for k, v in arrays.items() if k.startswith("program__")
        })
        engine = cls(
            program,
            max_batch=eng["max_batch"],
            chunk_windows=eng["chunk_windows"],
            replay_depth=eng["replay_depth"],
            megabatch=eng["megabatch"] if megabatch is None else megabatch,
            latency_budget_s=eng["latency_budget_s"],
            mesh=mesh,
            use_forest_kernel=eng["use_forest_kernel"],
            clock=clock,
        )
        engine.steps = int(eng["steps"])
        engine.program_version = int(eng["program_version"])
        state = EngineState(
            *(arrays[f"state__{n}"] for n in EngineState._fields)
        )
        if engine._state_sharding is not None:
            state = jax.device_put(state, engine._state_sharding)
        engine._state = _install_state(state)
        now = engine._clock()
        for meta in host["sessions"]:
            pid = int(meta["patient_id"])
            tag = f"sess{pid:08d}"
            s = engine.open_session(pid)
            queued = np.asarray(
                jax.device_get(arrays[f"{tag}__chunks"]), np.float32
            )
            for age, w in zip(meta["chunk_ages"], queued):
                s.chunks.append((now - float(age), np.asarray(w)))
            s._buf = np.asarray(jax.device_get(arrays[f"{tag}__buf"]),
                                np.float32)
            s.ring = np.asarray(jax.device_get(arrays[f"{tag}__ring"]),
                                np.int32)
            s.ring_pos = int(meta["ring_pos"])
            s.alarm = int(meta["alarm"])
            s.fe_boundary = np.asarray(
                jax.device_get(arrays[f"{tag}__fe_boundary"]), np.float32
            )
            s.fe_phase = int(meta["fe_phase"])
            s.chunk_seq = int(meta["chunk_seq"])
            if meta["slot"] is not None:
                s.slot = int(meta["slot"])
                engine._slots[s.slot] = s
        for pid in host["waiting"]:
            s = engine._sessions[int(pid)]
            engine._waiting.append(s)
            s.queued = True
        return engine
