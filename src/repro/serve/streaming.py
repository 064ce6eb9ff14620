"""Real-time streaming basecalling with adaptive read ejection (ReadUntil).

Every other entry point in this repo wants the complete raw signal up
front; a sequencer gives you neither that nor the time to wait for it —
thousands of pores emit signal CHUNKS concurrently, each wanting
provisional bases and an eject/continue verdict within a few chunks
(the UNCALLED / ReadUntil scenario).  This module is that scenario as a
first-class serving subsystem:

  * :class:`StreamingSession` — one pore's incremental decode.  Chunks go
    in (``feed``); each overlap window decodes EXACTLY ONCE the moment its
    samples are complete (``pipeline.chunking.WindowBuffer``), through the
    same jitted quantized-DNN + hash-beam stage batch serving uses — the
    ``gru_seq`` persistent kernel threads hidden state across every
    timestep of the walk and ``beam_merge_multiframe`` keeps beam state
    resident across decode strips, so within a lane no sample is ever
    re-run.  ``finalize()`` is bitwise identical to
    ``BasecallPipeline.basecall`` on the concatenated signal: chunk
    boundaries never change the result.
  * an incremental stitcher — the batch path's ``align_offsets`` chaining
    is a scan, so it replays exactly one window at a time; bases whose
    overlap horizon has closed are emitted early as
    :class:`ProvisionalBases` patches (the final patch reconciles, so
    applying all patches reconstructs the exact final consensus).
  * :class:`EjectPolicy` — the ReadUntil verdict surface: after the first
    N chunks the policy sees a :class:`StreamProgress` (provisional read +
    per-base beam-score posteriors) and answers ``continue`` / ``accept``
    / ``eject``; an eject cancels the lane, reclaims its
    ``SlotScheduler`` slot, and resolves the request with status
    ``"ejected"``.
  * :class:`StreamingBasecallEngine` — an ``EngineProtocol``
    step-executor, so streams get the same admission queue, priorities,
    deadlines, dp-sharded batching, and ``Server.metrics()`` as batch
    serving: one (B, window, C) device batch per step over every lane's
    next ready window.

The model's own chunk-boundary state contract
(``models.basecaller.apply_basecaller(..., rnn_state=..., return_state=
True)``) is exact for forward-only stacks; the paper presets run
alternating-direction layers, whose reversed walks integrate FUTURE
samples — so the streaming quantum here is the overlap WINDOW (bitwise
parity with the batch path, by construction), not the sub-window sample.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core import voting as voting_lib
from repro.dist import sharding as shd
from repro.pipeline import chunking
from repro.pipeline.pipeline import BasecallPipeline, BasecallResult
from repro.serve.api import STATUS_EJECTED, STATUS_OK
from repro.serve.scheduler import SlotScheduler

#: eject-policy verdicts
CONTINUE = "continue"   # undecided: consult again next step
ACCEPT = "accept"       # keep the read; stop consulting the policy
EJECT = "eject"         # abandon the read, free the lane NOW

#: EjectPolicy: ``StreamProgress -> CONTINUE | ACCEPT | EJECT``
EjectPolicy = Callable[["StreamProgress"], str]


@functools.cache
def _pairwise_offset():
    """Jitted ``voting.pairwise_offset`` (integer DP — exact), shared by
    every session so the per-window alignment compiles once per shape."""
    return jax.jit(voting_lib.pairwise_offset)


# ---------------------------------------------------------------------------
# provisional output events
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ProvisionalBases:
    """One streamed consensus patch: ``read[start : start+len(bases)] =
    bases``.

    Mid-stream patches are append-only (``start`` == bases emitted so
    far); the finalize patch may rewind ``start`` to revise — after
    applying it the read ENDS at ``start + len(bases)``, so folding every
    patch of a stream reconstructs the exact final consensus
    (:func:`apply_patches`)."""
    start: int
    bases: np.ndarray            # (k,) int32 base ids

    def __len__(self) -> int:
        return len(self.bases)


def apply_patches(patches) -> np.ndarray:
    """Fold :class:`ProvisionalBases` patches into the read they spell.

    The consumer-side contract: after a session's final patch this equals
    ``result.read[:result.length]`` exactly."""
    buf = np.zeros((0,), np.int32)
    for p in patches:
        buf = np.concatenate([buf[: p.start],
                              np.asarray(p.bases, np.int32)])
    return buf


@dataclasses.dataclass(frozen=True)
class StreamProgress:
    """What an :data:`EjectPolicy` sees after each engine step.

    ``read`` is the provisional consensus emitted so far (bases whose
    overlap horizon closed); ``base_logprobs`` its per-base confidence —
    the mean top-beam log-probability per base of the windows that voted
    at each position (beam-score posteriors at window granularity)."""
    read: np.ndarray             # (length,) int32 provisional consensus
    length: int
    base_logprobs: np.ndarray    # (length,) float32
    window_scores: np.ndarray    # (n_windows,) top-beam score per window
    window_lengths: np.ndarray   # (n_windows,)
    n_windows: int               # windows decoded so far
    n_chunks: int                # raw chunks consumed so far
    n_samples: int               # raw samples consumed so far

    def score_per_base(self) -> float:
        """Pool-level confidence: summed window scores per decoded base."""
        total = int(self.window_lengths.sum())
        return float(self.window_scores.sum()) / max(total, 1)


class ScoreEjectPolicy:
    """Reference :data:`EjectPolicy`: eject low-confidence reads early.

    Ejects once the mean per-base top-beam log-probability over at least
    ``min_bases`` decoded bases falls below ``threshold``; accepts once it
    holds above.  Stays ``CONTINUE`` until enough evidence arrives.
    """

    def __init__(self, threshold: float, min_bases: int = 8):
        self.threshold = threshold
        self.min_bases = min_bases

    def __call__(self, progress: StreamProgress) -> str:
        if int(progress.window_lengths.sum()) < self.min_bases:
            return CONTINUE
        return (EJECT if progress.score_per_base() < self.threshold
                else ACCEPT)


# ---------------------------------------------------------------------------
# the incremental stitcher
# ---------------------------------------------------------------------------

class _IncrementalStitcher:
    """``core.voting`` replayed one window at a time.

    ``align_offsets`` is a scan whose carry is (previous read, previous
    offset) — so offsets are computed incrementally with the SAME integer
    DP (exact).  Votes accumulate on a growing host-side counts grid;
    once ``depth`` newer windows have opened past a grid position, its
    overlap horizon has closed and its majority base is emitted as a
    provisional patch.  Horizon closure is a heuristic (a pathological
    later window may still align backwards); the finalize patch
    reconciles against the authoritative batch vote, so the patch stream
    always folds to the exact final consensus.
    """

    def __init__(self, max_read_len: int, depth: int, n_symbols: int = 4):
        self.L = max_read_len
        self.depth = max(depth, 1)
        self.n_symbols = n_symbols
        self._counts = np.zeros((0, n_symbols), np.int64)
        self._qual = np.zeros((0,), np.float64)     # summed score/base votes
        self._offs: List[int] = []                  # last `depth` offsets
        self._prev: Optional[Tuple[np.ndarray, int, int]] = None
        self._cursor = 0                            # grid scan position
        self._emitted_vals = np.zeros((0,), np.int32)
        self._emitted_pos = np.zeros((0,), np.int64)

    def _grow(self, upto: int) -> None:
        if upto > self._counts.shape[0]:
            extra = upto - self._counts.shape[0]
            self._counts = np.concatenate(
                [self._counts, np.zeros((extra, self.n_symbols), np.int64)])
            self._qual = np.concatenate(
                [self._qual, np.zeros((extra,), np.float64)])

    def push(self, read: np.ndarray, length: int,
             score: float) -> List[ProvisionalBases]:
        """Vote one window read onto the grid; emit newly closed bases."""
        read = np.asarray(read, np.int32)
        length = int(length)
        if self._prev is None:
            off = 0
        else:
            p_read, p_len, p_off = self._prev
            rel, _ = _pairwise_offset()(p_read, p_len, read, length)
            off = max(p_off + int(rel), 0)
        self._prev = (read, length, off)
        self._offs.append(off)
        del self._offs[: -self.depth]
        if length > 0:
            self._grow(off + length)
            pos = off + np.arange(length)
            sym = np.clip(read[:length], 0, self.n_symbols - 1)
            np.add.at(self._counts, (pos, sym), 1)
            self._qual[pos] += float(score) / max(length, 1)
        frontier = max(self._cursor, min(self._offs))
        frontier = min(frontier, self._counts.shape[0])
        if frontier <= self._cursor:
            return []
        rows = self._counts[self._cursor: frontier]
        covered = rows.sum(axis=1) > 0
        vals = rows.argmax(axis=1).astype(np.int32)[covered]
        poss = np.arange(self._cursor, frontier)[covered]
        self._cursor = frontier
        if vals.size == 0:
            return []
        patch = ProvisionalBases(start=int(self._emitted_vals.size),
                                 bases=vals)
        self._emitted_vals = np.concatenate([self._emitted_vals, vals])
        self._emitted_pos = np.concatenate([self._emitted_pos, poss])
        return [patch]

    def emitted(self) -> Tuple[np.ndarray, np.ndarray]:
        """(provisional read, per-base mean vote score) emitted so far."""
        pos = self._emitted_pos
        if pos.size == 0:
            return self._emitted_vals, np.zeros((0,), np.float32)
        votes = self._counts[pos].sum(axis=1)
        lp = (self._qual[pos] / np.maximum(votes, 1)).astype(np.float32)
        return self._emitted_vals, lp

    def flush(self, final_read: np.ndarray,
              final_length: int) -> ProvisionalBases:
        """The reconciling terminal patch against the batch-voted read."""
        want = np.asarray(final_read[:final_length], np.int32)
        have = self._emitted_vals
        m = min(have.size, want.size)
        diff = np.nonzero(have[:m] != want[:m])[0]
        k = int(diff[0]) if diff.size else m
        if k == have.size == want.size:
            k = want.size            # clean append of nothing: a no-op tail
        return ProvisionalBases(start=k, bases=want[k:].copy())


# ---------------------------------------------------------------------------
# the per-pore session
# ---------------------------------------------------------------------------

class StreamingSession:
    """One pore's incremental basecall: chunks in, provisional bases out.

    Two driving modes share all geometry/stitching state:

      * **bound** (default, ``pipe.stream()``): ``feed`` decodes windows
        the moment they complete, through the pipeline's own jitted
        decode stage — batched ``chunk.batch_windows`` at a time and
        dp-sharded under the mesh ambient at session creation, exactly
        like ``basecall_iter``.
      * **engine-driven** (``auto=False``): ``StreamingBasecallEngine``
        pulls ready windows from many sessions into ONE device batch per
        step (``ready``/``next_window``/``push_decoded``) — the session
        never touches the device itself.

    Either way ``finalize()`` runs the batch path's own
    ``BasecallResult.from_window_reads`` over the identical window reads,
    so the result is bitwise what ``pipe.basecall`` returns for the
    concatenated signal.

    Args:
        pipeline: the :class:`~repro.pipeline.BasecallPipeline` whose
            chunk geometry and jitted decode stage this stream uses.
        params: optional checkpoint override (bound mode only).
        auto: decode on ``feed`` (bound mode) vs. engine-driven.

    Example::

        sess = pipe.stream()
        for chunk in pore_chunks:
            for patch in sess.feed(chunk):
                ...                      # provisional bases, early
        result = sess.finalize()         # == pipe.basecall(full_signal)
    """

    def __init__(self, pipeline: BasecallPipeline, params=None, *,
                 auto: bool = True):
        self.pipe = pipeline
        self.auto = auto
        self.buffer = chunking.WindowBuffer(pipeline.chunk)
        self.stitcher = _IncrementalStitcher(
            pipeline.max_read_len, chunking.overlap_depth(pipeline.chunk))
        #: every ProvisionalBases patch emitted, in order (monotone — the
        #: serving layer streams new entries as ServeEvents)
        self.events: List[ProvisionalBases] = []
        self.n_chunks = 0
        self._reads: List[np.ndarray] = []
        self._lengths: List[int] = []
        self._scores: List[float] = []
        self._result: Optional[BasecallResult] = None
        if auto:
            # mirror basecall_iter: params packed once, mesh pinned at
            # session creation, batches padded to batch_windows (rounded
            # up to the dp device count)
            self._params = pipeline.serving_params(params)
            self._mesh = shd.get_mesh()
            dp = shd.dp_size(self._mesh)
            if self._mesh is not None:
                self._params = pipeline._place_params(self._params,
                                                      self._mesh)
            B = pipeline.chunk.batch_windows
            if B % dp:
                B += dp - B % dp
            self._B = B

    # -- feeding ------------------------------------------------------------
    def feed(self, chunk) -> List[ProvisionalBases]:
        """Append one raw-signal chunk ((t,) or (t, C), any size).

        Returns the provisional patches this chunk unlocked (bound mode;
        engine-driven sessions always return [] here — the engine decodes
        on its own step cadence)."""
        if self._result is not None:
            raise RuntimeError("session already finalized")
        self.buffer.feed(chunk)
        self.n_chunks += 1
        return self._drain() if self.auto else []

    def end(self) -> None:
        """Mark the pore's stream complete (tail windows become ready)."""
        if not self.buffer.ended:
            self.buffer.end()

    # -- the engine-facing decode surface -----------------------------------
    def ready(self) -> int:
        """Windows whose samples are complete and not yet handed out."""
        return self.buffer.ready()

    def next_window(self) -> Tuple[np.ndarray, int]:
        """Pop the next ready window: ((window, C), decoder logit_length)."""
        win, valid = self.buffer.next_window()
        return win, int(self.pipe.mcfg.output_frames(valid))

    @telemetry.span("stream.push")
    def push_decoded(self, read, length: int,
                     score: float) -> List[ProvisionalBases]:
        """Record one window's decode; emit newly closed consensus bases."""
        read = np.asarray(read, np.int32)
        self._reads.append(read)
        self._lengths.append(int(length))
        self._scores.append(float(score))
        patches = self.stitcher.push(read, int(length), float(score))
        self.events.extend(patches)
        return patches

    @property
    def done(self) -> bool:
        """True once the stream ended and every window is decoded."""
        return (self.buffer.ended and self.buffer.ready() == 0
                and len(self._reads) == self.buffer.emitted)

    # -- progress + results --------------------------------------------------
    def progress(self) -> StreamProgress:
        """Snapshot for eject policies / dashboards (cheap, host-side)."""
        read, lp = self.stitcher.emitted()
        return StreamProgress(
            read=read, length=int(read.size), base_logprobs=lp,
            window_scores=np.asarray(self._scores, np.float32),
            window_lengths=np.asarray(self._lengths, np.int32),
            n_windows=len(self._reads), n_chunks=self.n_chunks,
            n_samples=self.buffer.n_fed)

    def _settle(self) -> BasecallResult:
        """Vote what's decoded into a result + the reconciling patch."""
        if not self._reads:
            res = BasecallResult.empty(self.pipe.max_read_len)
        else:
            res = BasecallResult.from_window_reads(
                np.stack(self._reads),
                np.asarray(self._lengths, np.int32),
                max_read_len=self.pipe.max_read_len)
        self.events.append(self.stitcher.flush(res.read, res.length))
        self._result = res
        return res

    def finalize(self) -> BasecallResult:
        """End the stream, decode the tail, and vote the final consensus.

        Bitwise identical to ``pipe.basecall`` on the concatenated
        signal: same windows, same decode trace, same
        ``from_window_reads`` finalization.  Appends the reconciling
        terminal patch to ``events`` (so folding every patch with
        :func:`apply_patches` reproduces ``result.read[:length]``)."""
        if self._result is not None:
            return self._result
        self.end()
        if self.auto:
            self._drain()
        elif not self.done:
            raise RuntimeError("engine-driven session not fully decoded; "
                               "the engine finalizes it")
        return self._settle()

    def eject(self) -> BasecallResult:
        """Abandon the stream NOW: settle the windows decoded so far into
        a provisional result (what an ejected request resolves with)."""
        if self._result is None:
            self._settle()
        return self._result

    # -- bound-mode decoding -------------------------------------------------
    def _drain(self) -> List[ProvisionalBases]:
        patches: List[ProvisionalBases] = []
        while self.buffer.ready() > 0:
            take = min(self.buffer.ready(), self._B)
            wins, frames = [], []
            for _ in range(take):
                w, f = self.next_window()
                wins.append(w)
                frames.append(f)
            pad = self._B - take
            if pad:
                wins += [np.zeros_like(wins[0])] * pad
                frames += [0] * pad
            grp = jnp.asarray(np.stack(wins))
            fl = jnp.asarray(np.asarray(frames, np.int32))
            if self._mesh is not None:
                grp = jax.device_put(
                    grp, shd.batch_sharding(self._mesh, grp.ndim))
                fl = jax.device_put(
                    fl, shd.batch_sharding(self._mesh, fl.ndim))
            with shd.use_mesh(self._mesh):
                reads, lens, scores = self.pipe._decode_windows(
                    self._params, grp, fl)
            reads, lens = np.asarray(reads), np.asarray(lens)
            scores = np.asarray(scores)
            for i in range(take):
                patches += self.push_decoded(reads[i], int(lens[i]),
                                             float(scores[i]))
        return patches


# ---------------------------------------------------------------------------
# the streaming engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StreamRequest:
    """One pore's chunk stream to base-call incrementally.

    ``chunks`` is any iterable of raw-signal arrays ((t,) or (t, C), any
    sizes — a list, a generator, a live feed).  The engine pulls lazily:
    by default just enough each step to complete the lane's next window
    (work-conserving); ``chunks_per_step`` caps the pull to model a pore's
    fixed arrival cadence (latency benchmarks).  ``eject`` is consulted
    from the ``eject_after_chunks``-th chunk on, each step, until it
    answers ``accept`` or ``eject``."""
    chunks: Any
    eject: Optional[EjectPolicy] = None
    eject_after_chunks: int = 4
    chunks_per_step: Optional[int] = None
    priority: int = 0
    deadline: Optional[float] = None
    #: hosted-model routing (multi-tenant fleets): a request naming a
    #: model its engine does not serve resolves with a clear ``"error"``
    model: Optional[str] = None


@dataclasses.dataclass
class _StreamLane:
    rid: int
    request: StreamRequest
    session: Optional[StreamingSession] = None
    it: Optional[Iterator] = None
    exhausted: bool = False
    n_chunks: int = 0
    verdict: Optional[str] = None            # None | ACCEPT | EJECT
    status: str = STATUS_OK
    result: Optional[BasecallResult] = None


#: livelock guard: max chunk pulls per lane per step under the
#: work-conserving default (an adversarial stream of empty chunks must
#: not wedge the engine loop)
_MAX_PULLS_PER_STEP = 4096


class StreamingBasecallEngine:
    """Continuous-batching step-executor for live chunk streams.

    The ReadUntil counterpart of ``BasecallEngine``: one request is one
    pore's chunk iterable, admitted into a lane whose
    :class:`StreamingSession` turns chunks into ready windows.  Each
    engine step pulls every lane's chunks, assembles ONE (B, window, C)
    batch from the lanes' next ready windows (idle lanes contribute an
    inert zero window with ``logit_length 0``), decodes it through the
    pipeline's jitted stage — dp-sharded under the construction-time mesh
    exactly like batch serving — then streams newly closed consensus
    bases and consults each lane's eject policy.  An ``eject`` verdict
    retires the lane immediately: the slot readmits from the queue and
    the server resolves the request with status ``"ejected"`` (and the
    provisional read as its value).

    Args:
        pipeline: the :class:`BasecallPipeline` whose jitted decode stage
            (and serving artifact) every step consumes.
        params: optional checkpoint override (defaults to the pipeline's).
        batch_slots: device lanes **per dp device** (pool is
            ``batch_slots * dp`` under an ambient mesh at construction).

    Example::

        eng = StreamingBasecallEngine(pipe, batch_slots=8)
        srv = Server(eng)
        for ev in srv.stream(StreamRequest(chunks=pore_chunks)):
            ...                        # ProvisionalBases patches, then final
    """

    event_kind = "bases"

    def __init__(self, pipeline: BasecallPipeline, params=None,
                 batch_slots: int = 8, model_id: Optional[str] = None):
        self.pipe = pipeline
        self.model_id = model_id
        if params is None and pipeline.params is None:
            raise ValueError("StreamingBasecallEngine needs initialized "
                             "params")
        self.mesh = shd.get_mesh()
        self.dp = shd.dp_size(self.mesh)
        self.B = batch_slots * self.dp
        self.params = pipeline.serving_params(params)
        if self.mesh is not None:
            self.params = pipeline._place_params(self.params, self.mesh)
        self.sched: SlotScheduler[_StreamLane] = SlotScheduler(self.B)
        self._zero = np.zeros((pipeline.chunk.window,
                               pipeline.mcfg.in_channels), np.float32)
        self.steps = 0
        self.ejected = 0

    def _mesh_ctx(self):
        return shd.use_mesh(self.mesh)

    # -- EngineProtocol request adapters -----------------------------------
    def make_request(self, rid: int, r: StreamRequest) -> _StreamLane:
        return _StreamLane(rid=rid, request=r)

    def degenerate(self, r: StreamRequest) -> bool:
        """A sized, empty chunk container has nothing to stream."""
        try:
            return len(r.chunks) == 0
        except TypeError:
            return False                     # unsized iterators stream on

    def empty_result(self, r: StreamRequest) -> BasecallResult:
        return BasecallResult.empty(self.pipe.max_read_len)

    def model_of(self, r) -> Optional[str]:
        """The model id serving ``r`` (its ``model=``, or this engine's)."""
        return getattr(r, "model", None) or self.model_id

    def validate(self, r: StreamRequest) -> Optional[str]:
        """Reject malformed stream requests — and streams routed to a
        model this engine does not host — at submit, not mid-lane."""
        m = getattr(r, "model", None)
        if m is not None and m != self.model_id:
            hosts = (f"[{self.model_id!r}]" if self.model_id is not None
                     else "one anonymous model (no model= routing)")
            return f"unknown model {m!r}: this server hosts {hosts}"
        if not hasattr(r.chunks, "__iter__"):
            return f"chunks must be iterable, got {type(r.chunks).__name__}"
        if r.chunks_per_step is not None and r.chunks_per_step < 1:
            return f"chunks_per_step must be >= 1, got {r.chunks_per_step}"
        if r.eject is not None and r.eject_after_chunks < 1:
            return (f"eject_after_chunks must be >= 1, "
                    f"got {r.eject_after_chunks}")
        return None

    def progress(self, native: _StreamLane) -> List[ProvisionalBases]:
        return native.session.events if native.session is not None else []

    def result_of(self, native: _StreamLane) -> BasecallResult:
        assert native.result is not None
        return native.result

    def final_status(self, native: _StreamLane) -> str:
        """``"ejected"`` for lanes the eject policy abandoned, else ok —
        the ``Server.step`` resolution hook."""
        return native.status

    # -- admission ---------------------------------------------------------
    def submit(self, lane: _StreamLane):
        self.sched.submit(lane)

    @telemetry.span("admit.read")
    def _admit_one(self, slot: int, lane: _StreamLane):
        lane.session = StreamingSession(self.pipe, auto=False)
        lane.it = iter(lane.request.chunks)

    def admit(self) -> List[int]:
        return self.sched.admit(self._admit_one)

    # -- stepping ----------------------------------------------------------
    def active_mask(self) -> np.ndarray:
        return self.sched.active_mask()

    def _pull(self, lane: _StreamLane) -> None:
        """Advance one lane's chunk intake for this step.

        Work-conserving by default: pull until the session has a ready
        window (or the stream ends); with ``chunks_per_step`` set, pull
        exactly that many — the fixed-cadence pore model."""
        limit = lane.request.chunks_per_step
        pulled = 0
        while not lane.exhausted:
            if limit is None:
                if (lane.session.ready() > 0
                        or pulled >= _MAX_PULLS_PER_STEP):
                    break
            elif pulled >= limit:
                break
            try:
                chunk = next(lane.it)
            except StopIteration:
                lane.exhausted = True
                lane.session.end()
                break
            lane.session.feed(chunk)
            lane.n_chunks += 1
            pulled += 1

    def _maybe_eject(self, slot: int, lane: _StreamLane) -> bool:
        """Consult the lane's eject policy; True when the lane was
        ejected (slot freed, request retiring as ``"ejected"``)."""
        r = lane.request
        if (r.eject is None or lane.verdict is not None
                or lane.n_chunks < r.eject_after_chunks):
            return False
        verdict = r.eject(lane.session.progress())
        if verdict == ACCEPT:
            lane.verdict = ACCEPT
            return False
        if verdict != EJECT:
            return False                     # CONTINUE: ask again next step
        lane.verdict = EJECT
        lane.status = STATUS_EJECTED
        lane.result = lane.session.eject()
        self.ejected += 1
        self.sched.retire(slot, lane.rid)
        return True

    @telemetry.span("engine.step")
    def step(self):
        """Pull chunks, decode every lane's next ready window in one
        batch, stream closed bases, rule on ejects, retire done lanes."""
        self.steps += 1
        with telemetry.span("engine.assemble"):
            lanes = list(enumerate(self.sched.slots))
            for _, lane in lanes:
                if lane is not None:
                    self._pull(lane)
            wins, frames, live = [], [], []
            for slot, lane in lanes:
                if lane is not None and lane.session.ready() > 0:
                    w, f = lane.session.next_window()
                    wins.append(w)
                    frames.append(f)
                    live.append(slot)
                else:
                    wins.append(self._zero)
                    frames.append(0)
            if live:
                wins = np.stack(wins)
                frames = np.asarray(frames, np.int32)
        if live:
            with telemetry.span("engine.transfer"):
                batch, fl = jnp.asarray(wins), jnp.asarray(frames)
                if self.mesh is not None:
                    batch = jax.device_put(
                        batch, shd.batch_sharding(self.mesh, batch.ndim))
                    fl = jax.device_put(
                        fl, shd.batch_sharding(self.mesh, fl.ndim))
            with telemetry.span("engine.dispatch"), self._mesh_ctx():
                reads, lens, scores = self.pipe._decode_windows(
                    self.params, batch, fl)
            with telemetry.span("engine.readback"):
                reads, lens = np.asarray(reads), np.asarray(lens)
                scores = np.asarray(scores)
        with telemetry.span("engine.retire"):
            for slot in live:
                lane = self.sched.slots[slot]
                lane.session.push_decoded(reads[slot], int(lens[slot]),
                                          float(scores[slot]))
            for slot, lane in enumerate(self.sched.slots):
                if lane is None:
                    continue
                if self._maybe_eject(slot, lane):
                    continue
                if lane.session.done:
                    lane.result = lane.session.finalize()
                    self.sched.retire(slot, lane.rid)


__all__ = ["CONTINUE", "ACCEPT", "EJECT", "EjectPolicy", "ScoreEjectPolicy",
           "ProvisionalBases", "apply_patches", "StreamProgress",
           "StreamingSession", "StreamRequest", "StreamingBasecallEngine"]
