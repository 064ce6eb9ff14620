"""Transport-agnostic serving front-end: the request lifecycle over both
engines.

The paper's headline number is end-to-end base-calling *throughput*, so
serving needs a real request lifecycle — submit -> queue -> stream ->
retire — not a per-call driver loop.  This module owns that lifecycle:

    eng = BasecallEngine(pipe, batch_slots=8)          # pure step-executor
    srv = Server(eng, max_queue=64, backpressure="block")
    fut = srv.submit(BasecallRequest(signal=sig))      # -> ServeFuture
    res = fut.result()                                 # drives the loop
    for ev in srv.stream(BasecallRequest(signal=sig)): # per-window events
        ...
    srv.metrics()        # requests/s, occupancy, queue depth, p50/p99

``Server`` wraps any ``EngineProtocol`` implementation
(``serve.engine.ServingEngine`` for token LMs, ``serve.basecall_engine.
BasecallEngine`` for signal reads) as a pure step-executor: the engines
own what one unit of work means (a decoded token, a signal window); the
server owns admission (bounded queue + explicit backpressure policy),
priorities, deadlines, cancellation, event fan-out, and metrics.

The server is a cooperative single-thread event loop: ``step()`` advances
the engine one scheduler tick, and ``ServeFuture.result()`` / ``stream()``
drive ``step()`` until their request completes.  A transport (HTTP,
asyncio, RPC) pumps ``step()`` from its own executor — nothing here
depends on threads, which is what makes the front-end transport-agnostic.

Backpressure policies when the admission queue is full at ``submit()``:

    reject      raise ``QueueFull`` (caller sheds load)
    block       drive engine steps until a queue slot frees (cooperative)
    shed-oldest drop the oldest queued request (its future resolves with
                status "shed") and admit the newcomer
"""
from __future__ import annotations

import dataclasses
import time
from typing import (Any, Callable, Dict, Iterator, List, Optional, Protocol,
                    Sequence, runtime_checkable)

import numpy as np

from repro import telemetry
from repro.serve.scheduler import SlotScheduler

BACKPRESSURE_POLICIES = ("reject", "block", "shed-oldest")

#: terminal request statuses
STATUS_OK = "ok"
STATUS_CANCELLED = "cancelled"
STATUS_EXPIRED = "expired"
STATUS_SHED = "shed"
STATUS_ERROR = "error"
STATUS_EJECTED = "ejected"


class QueueFull(RuntimeError):
    """Admission queue at capacity under the ``reject`` policy."""


# ---------------------------------------------------------------------------
# requests / results / events
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BasecallRequest:
    """One raw-signal read to base-call (served by ``BasecallEngine`` or,
    with ``model=``, a hosted tenant of ``MultiModelBasecallEngine``)."""
    signal: np.ndarray                 # (T,) or (T, C) raw samples
    priority: int = 0                  # higher admits first
    deadline: Optional[float] = None   # seconds after submit (server clock)
    #: hosted-model routing: which of the server's packed artifacts serves
    #: this read (None -> the engine's default).  A model the engine does
    #: not host resolves with a clear ``"error"`` result at submit.
    model: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class LMRequest:
    """One token-LM generation (served by ``ServingEngine``)."""
    prompt: np.ndarray                 # (P,) int token ids
    max_tokens: int = 32
    eos_id: Optional[int] = None
    priority: int = 0
    deadline: Optional[float] = None
    #: hosted-model routing, as on :class:`BasecallRequest`
    model: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class ServeEvent:
    """One incremental output: a decoded token / a decoded signal window.

    ``kind`` is the engine's ``event_kind`` ("token" | "window") or
    "final"; ``index`` counts events of that kind per request."""
    rid: int
    kind: str
    index: int
    payload: Any


@dataclasses.dataclass(frozen=True)
class ServeResult:
    """Terminal state of one request.

    ``value`` is engine-shaped: a ``pipeline.BasecallResult`` for signal
    reads, the generated token list for LM requests — and None when the
    request did not complete (cancelled / expired / shed / error).  An
    ``"error"`` status carries the rejection reason in ``error`` (e.g. a
    request whose prompt + max_tokens exceeds the engine's KV capacity —
    resolved at submit, before it could wedge a lane)."""
    rid: int
    status: str
    value: Any
    submitted_at: float
    finished_at: float
    n_events: int = 0
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.status == STATUS_OK

    @property
    def latency(self) -> float:
        return self.finished_at - self.submitted_at


@dataclasses.dataclass(frozen=True)
class ModelMetrics:
    """Per hosted-model slice of one ``Server.metrics()`` snapshot
    (multi-tenant serving: one row set per model id, so a cold tenant or
    an error-prone client shows up per model, not diluted pool-wide)."""
    submitted: int = 0
    completed: int = 0
    errors: int = 0
    ejected: int = 0
    #: time-averaged occupancy of THIS model's slot group (engines
    #: exposing ``model_occupancy``; 0.0 for single-group engines)
    occupancy: float = 0.0
    requests_per_s: float = 0.0
    latency_p50_s: float = 0.0
    latency_p99_s: float = 0.0


@dataclasses.dataclass(frozen=True)
class ServerMetrics:
    """One ``Server.metrics()`` snapshot — the serving counterpart of the
    fig9 latency breakdown (requests/s + occupancy + queue + tails)."""
    steps: int
    submitted: int
    completed: int
    cancelled: int
    expired: int
    shed: int
    rejected: int
    queue_depth: int
    active: int
    occupancy: float            # time-averaged over engine steps
    elapsed_s: float
    requests_per_s: float
    latency_p50_s: float
    latency_p99_s: float
    #: dp device count behind the engine (1 without a mesh) and the
    #: time-averaged occupancy of each device's lane group — the scale-out
    #: utilization axis: one cold device shows up here, not diluted into
    #: the pool-wide mean
    devices: int = 1
    occupancy_per_device: tuple = (0.0,)
    #: requests rejected at submit by engine validation (e.g. prompt +
    #: max_tokens over the KV capacity) — resolved with status "error"
    errors: int = 0
    #: streaming lanes abandoned early by their eject policy (ReadUntil)
    #: — resolved with status "ejected" and the provisional read
    ejected: int = 0
    #: submit -> FIRST incremental event latency tails — the streaming
    #: responsiveness axis (how quickly a pore sees provisional bases),
    #: distinct from the full-request latency percentiles above
    ttfe_p50_s: float = 0.0
    ttfe_p99_s: float = 0.0
    #: submit -> admitted to a lane (queue wait) tails, on the server's
    #: clock; one sample per request, at its first admission
    queue_wait_p50_s: float = 0.0
    queue_wait_p99_s: float = 0.0
    #: per hosted-model metric slices, keyed by model id (empty for
    #: engines/requests without ``model=`` routing)
    per_model: Dict[str, ModelMetrics] = dataclasses.field(
        default_factory=dict)

    def rows(self, prefix: str = "serve") -> List[tuple]:
        """``benchmarks._util.emit``-shaped CSV rows (pool-wide rows, then
        one row set per hosted model id)."""
        per_dev = " ".join(f"{o:.3f}" for o in self.occupancy_per_device)
        out = [
            (f"{prefix}/requests_per_s", f"{self.requests_per_s:.2f}",
             f"{self.completed} completed in {self.elapsed_s:.2f}s"),
            (f"{prefix}/occupancy", f"{self.occupancy:.3f}",
             f"{self.steps} engine steps; per-device [{per_dev}] "
             f"over {self.devices} dp device(s)"),
            (f"{prefix}/queue_depth", str(self.queue_depth),
             f"shed={self.shed} rejected={self.rejected} "
             f"expired={self.expired}"),
            (f"{prefix}/latency_p50_s", f"{self.latency_p50_s:.4f}", ""),
            (f"{prefix}/latency_p99_s", f"{self.latency_p99_s:.4f}", ""),
            (f"{prefix}/ttfe_p50_s", f"{self.ttfe_p50_s:.4f}",
             f"ejected={self.ejected}"),
            (f"{prefix}/ttfe_p99_s", f"{self.ttfe_p99_s:.4f}", ""),
            (f"{prefix}/queue_wait_p50_s", f"{self.queue_wait_p50_s:.4f}",
             "submit -> admitted to a lane"),
            (f"{prefix}/queue_wait_p99_s", f"{self.queue_wait_p99_s:.4f}",
             ""),
        ]
        for mid in sorted(self.per_model):
            m = self.per_model[mid]
            p = f"{prefix}/model/{mid}"
            out += [
                (f"{p}/requests_per_s", f"{m.requests_per_s:.2f}",
                 f"{m.completed} completed of {m.submitted} submitted"),
                (f"{p}/occupancy", f"{m.occupancy:.3f}", ""),
                (f"{p}/latency_p50_s", f"{m.latency_p50_s:.4f}", ""),
                (f"{p}/latency_p99_s", f"{m.latency_p99_s:.4f}", ""),
                (f"{p}/errors", str(m.errors), f"ejected={m.ejected}"),
            ]
        return out


# ---------------------------------------------------------------------------
# the engine contract
# ---------------------------------------------------------------------------

@runtime_checkable
class EngineProtocol(Protocol):
    """What ``Server`` needs from an engine: a pure step-executor.

    Engines own slot bookkeeping via one ``SlotScheduler`` and define one
    unit of work (``step``); the server owns the request lifecycle.  The
    driver loop the engines used to hand-roll (``run()``) lives in
    ``Server`` now — engines must not grow one back.

    Optional extensions (duck-typed via ``getattr``, not required by the
    protocol):

    * ``validate(request) -> Optional[str]`` — a non-None return is an
      error message and the server resolves the request with status
      ``"error"`` at submit instead of queueing it (``ServingEngine``
      uses this to reject requests that would overflow its KV cache).
    * ``final_status(native) -> str`` — the terminal status for a
      retired request (default ``"ok"``; ``StreamingBasecallEngine``
      returns ``"ejected"`` for lanes its eject policy abandoned).
    * ``model_of(request) -> Optional[str]`` — the hosted-model id
      serving a request (its ``model=`` resolved against the engine's
      default); the server keys per-model ``metrics()`` slices on it.
    * ``model_occupancy() -> Dict[str, float]`` — instantaneous per-model
      slot-group occupancy, accumulated into per-model metrics
      (``MultiModelBasecallEngine``).
    * ``device_occupancy() -> np.ndarray`` — instantaneous (dp,)
      per-device occupancy for engines whose lane -> device layout is not
      one contiguous pool-wide fold (multi-tenant groups are each
      lane-major over dp on their own).
    """
    sched: SlotScheduler
    steps: int
    event_kind: str

    def make_request(self, rid: int, request: Any) -> Any:
        """API request -> the engine-native slot record."""

    def degenerate(self, request: Any) -> bool:
        """True when the request is valid but empty (zero-length signal,
        ``max_tokens <= 0`` / empty prompt): completes at admission with
        ``empty_result`` instead of occupying a slot."""

    def empty_result(self, request: Any) -> Any:
        """The ``ServeResult.value`` for a degenerate request."""

    def admit(self) -> List[int]:
        """Fill free slots from ``sched.queue``; returns admitted slots."""

    def step(self) -> None:
        """Advance every occupied lane one unit of work; retire finished
        requests into ``sched.finished``."""

    def progress(self, native: Any) -> Sequence:
        """Monotone per-request outputs so far (tokens / window reads);
        the server turns new entries into ``ServeEvent``s."""

    def result_of(self, native: Any) -> Any:
        """Final payload of a retired native request."""


# ---------------------------------------------------------------------------
# futures
# ---------------------------------------------------------------------------

class ServeFuture:
    """Handle to one submitted request.

    ``result()`` cooperatively drives the server loop until this request
    reaches a terminal state — the single-thread analogue of awaiting."""

    def __init__(self, server: "Server", rid: int):
        self._server = server
        self.rid = rid

    def done(self) -> bool:
        """True once this request reached a terminal state."""
        rec = self._server._records.get(self.rid)
        # a missing record means the request reached a terminal state and
        # its record aged out of retain_results — done, result unreadable
        return rec is None or rec.result is not None

    def result(self, max_steps: int = 1_000_000) -> ServeResult:
        """Drive the server loop until this request is terminal, then
        return its ``ServeResult`` (raises ``TimeoutError`` past the
        ``max_steps`` budget)."""
        rec = self._server._record(self.rid)
        while rec.result is None and max_steps > 0:
            self._server.step()
            max_steps -= 1
        if rec.result is None:
            raise TimeoutError(f"request {self.rid} not done "
                               f"within the step budget")
        return rec.result

    def cancel(self) -> bool:
        """Cancel this request (queued or in-flight); False once terminal."""
        return self._server.cancel(self.rid)

    def events(self) -> List[ServeEvent]:
        """Events observed so far (grows as the server steps)."""
        return list(self._server._record(self.rid).events)


@dataclasses.dataclass
class _Record:
    rid: int
    request: Any
    native: Any                       # engine-native request (None if degen)
    priority: int
    submitted_at: float
    expires_at: Optional[float]
    model: Optional[str] = None       # per-model metrics key (or None)
    admitted: bool = False            # queue wait recorded
    events: List[ServeEvent] = dataclasses.field(default_factory=list)
    emitted: int = 0
    result: Optional[ServeResult] = None


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------

class Server:
    """Request lifecycle over one engine: bounded admission queue,
    priority ordering, deadlines, cancellation, streaming, metrics."""

    def __init__(self, engine: EngineProtocol, *, max_queue: int = 64,
                 backpressure: str = "reject",
                 retain_results: int = 4096,
                 clock: Callable[[], float] = time.monotonic):
        if backpressure not in BACKPRESSURE_POLICIES:
            raise ValueError(f"unknown backpressure {backpressure!r}; "
                             f"one of {BACKPRESSURE_POLICIES}")
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if retain_results < 1:
            raise ValueError(
                f"retain_results must be >= 1, got {retain_results}")
        self.engine = engine
        self.max_queue = max_queue
        self.backpressure = backpressure
        # terminal records are kept for late future.result()/events()
        # reads, but only the most recent `retain_results` of them — a
        # long-running server must not grow memory with requests served
        self.retain_results = retain_results
        self.clock = clock
        self.results: Dict[int, ServeResult] = {}
        self._records: Dict[int, _Record] = {}
        self._live: Dict[int, _Record] = {}      # not yet terminal
        self._terminal_order: List[int] = []     # FIFO for eviction
        self._next_rid = 0
        self._latencies: List[float] = []
        self._occ_sum = 0.0
        # per-dp-device occupancy accumulator (lazily sized from the
        # engine's dp attribute; engines without one count as 1 device)
        self._occ_dev_sum: Optional[np.ndarray] = None
        self._counts = {STATUS_OK: 0, STATUS_CANCELLED: 0,
                        STATUS_EXPIRED: 0, STATUS_SHED: 0, STATUS_ERROR: 0,
                        STATUS_EJECTED: 0, "rejected": 0, "submitted": 0}
        # per hosted-model metric state, keyed by the engine's model_of()
        # (requests without model routing never create a slice)
        self._per_model: Dict[str, dict] = {}
        self._ttfe: List[float] = []             # submit -> first event
        self._queue_waits: List[float] = []      # submit -> admitted
        self._started_at: Optional[float] = None

    def _model_id_of(self, request: Any) -> Optional[str]:
        fn = getattr(self.engine, "model_of", None)
        if fn is not None:
            return fn(request)
        return getattr(request, "model", None)

    def _mstats(self, mid: str) -> dict:
        ms = self._per_model.get(mid)
        if ms is None:
            ms = dict(self._counts, latencies=[], occ_sum=0.0)
            for k in ms:
                if k not in ("latencies", "occ_sum"):
                    ms[k] = 0
            self._per_model[mid] = ms
        return ms

    # -- submission ---------------------------------------------------------

    def submit(self, request: Any) -> ServeFuture:
        """Enqueue one request; returns immediately with a future.

        Degenerate requests (``engine.degenerate``) resolve here with an
        empty ok result — they never occupy a queue entry or a slot.
        Requests the engine's (optional) ``validate`` hook rejects — e.g.
        ``prompt + max_tokens`` over the KV capacity, which would wedge a
        lane — resolve here with status ``"error"`` and the reason in
        ``ServeResult.error``.  A full queue applies the backpressure
        policy (see module doc).

        Args:
            request: a :class:`BasecallRequest` / :class:`LMRequest` (or
                anything the engine's ``make_request`` understands), with
                optional ``priority`` and ``deadline`` attributes.

        Returns:
            A :class:`ServeFuture`; ``future.result()`` cooperatively
            drives the loop until this request is terminal.

        Raises:
            QueueFull: queue at capacity under the ``reject`` policy (or
                ``shed-oldest`` with nothing of ours to shed).

        Example::

            fut = srv.submit(BasecallRequest(signal=sig, priority=1))
            res = fut.result()          # ServeResult; res.value stitched
        """
        now = self.clock()
        if self._started_at is None:
            self._started_at = now
        rid = self._next_rid
        self._next_rid += 1
        self._counts["submitted"] += 1
        prio = getattr(request, "priority", 0)
        ddl = getattr(request, "deadline", None)
        mid = self._model_id_of(request)
        rec = _Record(rid=rid, request=request, native=None, priority=prio,
                      submitted_at=now, model=mid,
                      expires_at=None if ddl is None else now + ddl)
        if mid is not None:
            self._mstats(mid)["submitted"] += 1
        self._records[rid] = rec
        if self.engine.degenerate(request):
            self._resolve(rec, STATUS_OK, self.engine.empty_result(request))
            return ServeFuture(self, rid)
        # engines may veto requests their cache cannot serve (duck-typed:
        # ``validate`` is an optional EngineProtocol extension) — resolve
        # with a clear error result instead of wedging a lane later
        err = getattr(self.engine, "validate", lambda r: None)(request)
        if err is not None:
            # counted ONCE, as an error: validation rejections (unknown
            # model, over-capacity request) resolve before the queue is
            # consulted, so they can never also count as a backpressure
            # rejection — pool-wide and per-model alike
            self._resolve(rec, STATUS_ERROR, None, error=err)
            return ServeFuture(self, rid)

        queue = self.engine.sched.queue
        while len(queue) >= self.max_queue:
            if self.backpressure == "reject":
                self._counts["rejected"] += 1
                del self._records[rid]
                raise QueueFull(
                    f"admission queue at capacity ({self.max_queue}); "
                    f"policy=reject")
            if self.backpressure == "block":
                self.step()
                continue
            # shed-oldest: drop the longest-queued entry WE own to make
            # room (entries submitted straight to the engine are not ours
            # to shed; with none of our own queued, behave like reject)
            owned = [r for q in queue
                     if (r := self._owner_of(q)) is not None]
            if not owned:
                self._counts["rejected"] += 1
                del self._records[rid]
                raise QueueFull(
                    "admission queue full of requests not owned by this "
                    "server; cannot shed")
            oldest = min(owned, key=lambda r: r.submitted_at)
            self.engine.sched.cancel_queued(oldest.native)
            self._resolve(oldest, STATUS_SHED, None)

        rec.native = self.engine.make_request(rid, request)
        self._live[rid] = rec
        # priority insertion: higher priority first, FIFO within a class
        # (entries we don't own rank as priority 0)
        pos = len(queue)
        while pos > 0 and prio > self._priority_of(queue[pos - 1]):
            pos -= 1
        queue.insert(pos, rec.native)
        return ServeFuture(self, rid)

    def _owner_of(self, native: Any) -> Optional[_Record]:
        """This server's live record for a queued native, or None when the
        entry was submitted straight to the engine (a colliding rid does
        not fool the identity check)."""
        rec = self._live.get(getattr(native, "rid", None))
        return rec if rec is not None and rec.native is native else None

    def _priority_of(self, native: Any) -> int:
        rec = self._owner_of(native)
        return rec.priority if rec is not None else 0

    def stream(self, request: Any,
               max_steps: int = 1_000_000) -> Iterator[ServeEvent]:
        """Submit and yield incremental events as the request decodes.

        Args:
            request: as for :meth:`submit`.
            max_steps: server-step budget before ``TimeoutError``.

        Returns:
            An iterator of :class:`ServeEvent` — one per decoded token /
            signal window, ending with a ``"final"`` event whose payload
            is the :class:`ServeResult`.

        Example::

            for ev in srv.stream(BasecallRequest(signal=sig)):
                print(ev.kind, ev.index)
        """
        fut = self.submit(request)
        rec = self._record(fut.rid)
        seen = 0
        while True:
            while seen < len(rec.events):
                yield rec.events[seen]
                seen += 1
            if rec.result is not None:
                return
            if max_steps <= 0:
                raise TimeoutError(f"request {fut.rid} not done "
                                   f"within the step budget")
            self.step()
            max_steps -= 1

    def cancel(self, rid: int) -> bool:
        """Cancel a queued or in-flight request.  False once terminal."""
        rec = self._records.get(rid)
        if rec is None or rec.result is not None:
            return False
        if self.engine.sched.cancel_queued(rec.native):
            self._resolve(rec, STATUS_CANCELLED, None)
            return True
        slot = self.engine.sched.slot_of(rec.native)
        if slot is not None:
            self.engine.sched.release(slot)
            self._resolve(rec, STATUS_CANCELLED, None)
            return True
        return False

    # -- the loop -----------------------------------------------------------

    def pending(self) -> bool:
        """True while any submitted request is not yet terminal."""
        return bool(self._live)

    @telemetry.span("server.step")
    def step(self) -> None:
        """One scheduler tick: expire -> admit -> engine step -> deliver."""
        self._expire()
        with telemetry.span("engine.admit"):
            admitted = self.engine.admit()
        sched = self.engine.sched
        self._note_admitted([sched.slots[s] for s in admitted])
        if sched.any_active():
            # occupancy is averaged over ENGINE steps (device launches),
            # not idle server ticks — it answers "how full were the lanes
            # we actually paid for", the paper's utilization axis
            self._occ_sum += sched.occupancy()
            dp = getattr(self.engine, "dp", 1)
            if self._occ_dev_sum is None or len(self._occ_dev_sum) != dp:
                self._occ_dev_sum = np.zeros((dp,))
            # engines whose lane -> device layout is not one pool-wide
            # contiguous fold (multi-tenant slot groups) expose their own
            # per-device view; everyone else folds the pool over dp
            dev_fn = getattr(self.engine, "device_occupancy", None)
            self._occ_dev_sum += (dev_fn() if dev_fn is not None
                                  else sched.group_occupancy(dp))
            mo_fn = getattr(self.engine, "model_occupancy", None)
            if mo_fn is not None:
                for mid, occ in mo_fn().items():
                    self._mstats(mid)["occ_sum"] += occ
            self.engine.step()
        self._pump_events()
        with telemetry.span("server.resolve"):
            for rid, native in sched.drain_finished().items():
                rec = self._records.get(rid)
                if rec is None or rec.native is not native:
                    # not ours: submitted straight to the engine (possibly
                    # with a colliding rid — identity disambiguates)
                    continue
                if rec.result is not None:
                    continue                        # already terminal
                # engines may retire a request in a non-ok terminal state
                # (duck-typed ``final_status``, e.g. a streaming lane the
                # eject policy abandoned resolves as "ejected" — with the
                # provisional read as its value)
                status = getattr(self.engine, "final_status",
                                 lambda n: STATUS_OK)(native)
                self._resolve(rec, status, self.engine.result_of(native))

    def run_until_idle(self, max_steps: int = 1_000_000
                       ) -> Dict[int, ServeResult]:
        """Drive until every submitted request is terminal; returns all
        results delivered so far (rid -> ServeResult)."""
        while self.pending() and max_steps > 0:
            self.step()
            max_steps -= 1
        if self.pending():
            raise TimeoutError("requests still pending after step budget")
        return dict(self.results)

    # -- internals ----------------------------------------------------------

    def _record(self, rid: int) -> _Record:
        rec = self._records.get(rid)
        if rec is None:
            raise KeyError(
                f"unknown request id {rid} (never submitted, or its "
                f"terminal record aged out of retain_results="
                f"{self.retain_results})")
        return rec

    def _note_admitted(self, natives: List[Any]) -> None:
        """Record the queue wait of each request first admitted this tick
        (a preempted and re-admitted request counts once)."""
        recs = [r for n in natives
                if (r := self._owner_of(n)) is not None and not r.admitted]
        if not recs:
            return
        now = self.clock()
        for rec in recs:
            rec.admitted = True
            self._queue_waits.append(now - rec.submitted_at)

    @telemetry.span("server.expire")
    def _expire(self) -> None:
        now = self.clock()
        for rec in [r for r in self._live.values()
                    if r.expires_at is not None and now >= r.expires_at]:
            if not self.engine.sched.cancel_queued(rec.native):
                slot = self.engine.sched.slot_of(rec.native)
                if slot is None:
                    continue                     # retiring this very step
                self.engine.sched.release(slot)
            self._resolve(rec, STATUS_EXPIRED, None)

    @telemetry.span("server.events")
    def _pump_events(self) -> None:
        kind = self.engine.event_kind
        now = self.clock()
        for rec in list(self._live.values()):
            if rec.native is None:
                continue
            out = self.engine.progress(rec.native)
            if rec.emitted == 0 and len(out) > 0:
                # time-to-first-event: the streaming responsiveness tail
                # (submit -> first provisional output, not the final)
                self._ttfe.append(now - rec.submitted_at)
            while rec.emitted < len(out):
                rec.events.append(ServeEvent(rid=rec.rid, kind=kind,
                                             index=rec.emitted,
                                             payload=out[rec.emitted]))
                rec.emitted += 1

    def _resolve(self, rec: _Record, status: str, value: Any,
                 error: Optional[str] = None) -> None:
        assert rec.result is None, f"request {rec.rid} resolved twice"
        res = ServeResult(rid=rec.rid, status=status, value=value,
                          submitted_at=rec.submitted_at,
                          finished_at=self.clock(), n_events=rec.emitted,
                          error=error)
        rec.result = res
        rec.events.append(ServeEvent(rid=rec.rid, kind="final",
                                     index=rec.emitted, payload=res))
        self.results[rec.rid] = res
        self._live.pop(rec.rid, None)
        self._counts[status] += 1
        if status == STATUS_OK:
            self._latencies.append(res.latency)
        if rec.model is not None:
            ms = self._mstats(rec.model)
            ms[status] += 1
            if status == STATUS_OK:
                ms["latencies"].append(res.latency)
        # bound terminal-record retention: a server that lives for
        # millions of requests must not pin every signal/result forever
        self._terminal_order.append(rec.rid)
        while len(self._terminal_order) > self.retain_results:
            old = self._terminal_order.pop(0)
            self._records.pop(old, None)
            self.results.pop(old, None)

    # -- observability ------------------------------------------------------

    def reset_metrics(self) -> None:
        """Zero the observability state (benchmarks call this after their
        warmup request so compile time stays out of the tails): delivered
        results, latencies, occupancy/step accounting, counters — and
        every per-model slice, in the same call, so pool-wide and
        per-model counters can never disagree about the epoch.
        In-flight requests are unaffected and still deliver."""
        for rid in self._terminal_order:
            self._records.pop(rid, None)
        self._terminal_order.clear()
        self.results.clear()
        self._latencies.clear()
        self._ttfe.clear()
        self._queue_waits.clear()
        self._occ_sum = 0.0
        self._occ_dev_sum = None
        self.engine.steps = 0
        for k in self._counts:
            self._counts[k] = 0
        self._per_model.clear()
        self._started_at = None

    def metrics(self) -> ServerMetrics:
        """Snapshot the serving observability state.

        Returns:
            A :class:`ServerMetrics` with requests/s, time-averaged slot
            occupancy (pool-wide and per dp device), queue depth,
            shed/rejected/expired counters, and p50/p99 latency.  Under a
            sharded engine ``devices`` is the mesh's dp size and
            ``occupancy_per_device`` has one entry per device's lane
            group.

        Example::

            m = srv.metrics()
            print(m.requests_per_s, m.occupancy_per_device)
        """
        steps = self.engine.steps
        now = self.clock()
        elapsed = (now - self._started_at
                   if self._started_at is not None else 0.0)
        lat = np.asarray(self._latencies) if self._latencies else None
        dp = getattr(self.engine, "dp", 1)
        if self._occ_dev_sum is not None and steps:
            occ_dev = tuple(float(o) for o in self._occ_dev_sum / steps)
        else:
            occ_dev = (0.0,) * dp
        per_model = {}
        for mid, ms in self._per_model.items():
            mlat = np.asarray(ms["latencies"]) if ms["latencies"] else None
            per_model[mid] = ModelMetrics(
                submitted=ms["submitted"],
                completed=ms[STATUS_OK],
                errors=ms[STATUS_ERROR],
                ejected=ms[STATUS_EJECTED],
                occupancy=ms["occ_sum"] / steps if steps else 0.0,
                requests_per_s=(ms[STATUS_OK] / elapsed
                                if elapsed > 0 else 0.0),
                latency_p50_s=(float(np.percentile(mlat, 50))
                               if mlat is not None else 0.0),
                latency_p99_s=(float(np.percentile(mlat, 99))
                               if mlat is not None else 0.0))
        return ServerMetrics(
            steps=steps,
            submitted=self._counts["submitted"],
            completed=self._counts[STATUS_OK],
            cancelled=self._counts[STATUS_CANCELLED],
            expired=self._counts[STATUS_EXPIRED],
            shed=self._counts[STATUS_SHED],
            rejected=self._counts["rejected"],
            errors=self._counts[STATUS_ERROR],
            queue_depth=len(self.engine.sched.queue),
            active=int(self.engine.sched.active_mask().sum()),
            occupancy=self._occ_sum / steps if steps else 0.0,
            elapsed_s=elapsed,
            requests_per_s=(self._counts[STATUS_OK] / elapsed
                            if elapsed > 0 else 0.0),
            latency_p50_s=float(np.percentile(lat, 50)) if lat is not None
            else 0.0,
            latency_p99_s=float(np.percentile(lat, 99)) if lat is not None
            else 0.0,
            devices=dp,
            occupancy_per_device=occ_dev,
            ejected=self._counts[STATUS_EJECTED],
            ttfe_p50_s=(float(np.percentile(self._ttfe, 50))
                        if self._ttfe else 0.0),
            ttfe_p99_s=(float(np.percentile(self._ttfe, 99))
                        if self._ttfe else 0.0),
            queue_wait_p50_s=(float(np.percentile(self._queue_waits, 50))
                              if self._queue_waits else 0.0),
            queue_wait_p99_s=(float(np.percentile(self._queue_waits, 99))
                              if self._queue_waits else 0.0),
            per_model=per_model,
        )


__all__ = ["BasecallRequest", "LMRequest", "ServeEvent", "ServeResult",
           "ServeFuture", "ServerMetrics", "ModelMetrics", "Server",
           "EngineProtocol", "QueueFull", "BACKPRESSURE_POLICIES"]
