"""Continuous-batching base-calling engine: long reads in, consensus out.

The LM engine's slot scheduler, reused for signals: a request is one
arbitrarily long raw-signal read, chunked into overlapping windows at
admission (``pipeline.chunking``).  Each engine step assembles one
(B, window, C) batch from every occupied lane's next window, runs the
pipeline's jitted quantized-DNN + CTC-decode stage ONCE for the whole
pool, and appends each lane's decoded window read.  A read whose windows
are exhausted retires immediately — its consensus is voted from the
accumulated window reads and the slot admits the next queued read, so
short reads never wait for long ones (iteration-level scheduling, same
policy as serve/engine.py).

The engine is a pure step-executor implementing ``serve.api.
EngineProtocol``; the request lifecycle (queueing, backpressure,
deadlines, cancellation, per-window streaming, the driver loop) lives in
``serve.api.Server``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core import voting
from repro.dist import sharding as shd
from repro.pipeline import chunking
from repro.pipeline.pipeline import BasecallPipeline, BasecallResult
from repro.serve.scheduler import SlotScheduler


@dataclasses.dataclass
class ReadRequest:
    rid: int
    signal: np.ndarray                   # (T,) or (T, C) raw samples
    windows: Optional[np.ndarray] = None  # (N, window, C), set at admission
    frame_lengths: Optional[np.ndarray] = None  # (N,) decoder logit_lengths
    cursor: int = 0
    reads: List[np.ndarray] = dataclasses.field(default_factory=list)
    lengths: List[int] = dataclasses.field(default_factory=list)
    result: Optional[BasecallResult] = None

    @property
    def done(self) -> bool:
        return self.result is not None


class _WindowView:
    """Constant-time sequence view over one request's decoded windows."""
    __slots__ = ("_req",)

    def __init__(self, req: ReadRequest):
        self._req = req

    def __len__(self) -> int:
        return len(self._req.reads)

    def __getitem__(self, i: int) -> Tuple[np.ndarray, int]:
        return (self._req.reads[i], self._req.lengths[i])


class BasecallEngine:
    """Continuous-batching step-executor for long signal reads.

    Args:
        pipeline: the :class:`BasecallPipeline` whose jitted decode stage
            (and serving artifact) every step consumes.
        params: optional checkpoint override (defaults to the pipeline's).
        batch_slots: device lanes **per dp device**.  Under an ambient
            ``dist.sharding.use_mesh`` mesh at construction the pool is
            ``batch_slots * dp_size`` lanes and each step's window batch
            is split over the mesh's data-parallel devices; without a
            mesh this is the total lane count (dp = 1).
        model_id: optional hosted-model name.  When set, requests naming a
            DIFFERENT ``model=`` resolve with a clear ``"error"`` result
            at submit instead of silently running the wrong weights, and
            the Server's per-model metrics key on it.  (Multi-model
            hosting lives in ``serve.multitenant.MultiModelBasecallEngine``;
            this keeps single-model fleets honestly routable.)

    Example::

        eng = BasecallEngine(pipe, batch_slots=8)
        srv = Server(eng)
        res = srv.submit(BasecallRequest(signal=sig)).result()
    """

    def __init__(self, pipeline: BasecallPipeline, params=None,
                 batch_slots: int = 8, model_id: Optional[str] = None):
        self.pipe = pipeline
        self.model_id = model_id
        if params is None and pipeline.params is None:
            raise ValueError("BasecallEngine needs initialized params")
        # slot capacity scales with the ambient mesh: batch_slots lanes
        # per dp device, one (B, window, C) batch split over all of them
        self.mesh = shd.get_mesh()
        self.dp = shd.dp_size(self.mesh)
        self.B = batch_slots * self.dp
        # the engine holds the quantize-once serving artifact, not float
        # weights: every step consumes the same PackedParams the pipeline
        # serves, which is what keeps engine ≡ pipeline bit for bit
        self.params = pipeline.serving_params(params)
        if self.mesh is not None:
            self.params = pipeline._place_params(self.params, self.mesh)
        self.sched: SlotScheduler[ReadRequest] = SlotScheduler(self.B)
        ck = pipeline.chunk
        self._zero = np.zeros((ck.window, pipeline.mcfg.in_channels),
                              np.float32)
        self.steps = 0
        # the vote's one compiled part, built now so no finished read
        # compiles (``core.voting.vote_host``)
        voting.warm_comparator(pipeline.max_read_len)

    @classmethod
    def from_registry(cls, registry, model_id: str,
                      **kw) -> "BasecallEngine":
        """A single-model engine serving a ``ModelRegistry`` tenant: the
        registry's cached packed artifact (quantize-once, re-packed
        bitwise-identically after eviction) plus its pipeline, with
        ``model_id`` routing installed."""
        pipe = registry.pipeline(model_id)
        return cls(pipe, params=registry.artifact(model_id),
                   model_id=model_id, **kw)

    def _mesh_ctx(self):
        """The construction-time mesh, re-installed around device calls so
        the jitted decode traces with its sharding constraints no matter
        what mesh (if any) is ambient when the server drives us
        (``use_mesh(None)`` masks an ambient mesh for a no-mesh engine)."""
        return shd.use_mesh(self.mesh)

    # -- EngineProtocol request adapters -----------------------------------
    event_kind = "window"

    def model_of(self, r) -> Optional[str]:
        """The model id serving ``r`` (its ``model=``, or this engine's)."""
        return getattr(r, "model", None) or self.model_id

    def validate(self, r) -> Optional[str]:
        """Requests routed to a model this engine does not host get a
        clear ``"error"`` result at submit."""
        m = getattr(r, "model", None)
        if m is not None and m != self.model_id:
            hosts = (f"[{self.model_id!r}]" if self.model_id is not None
                     else "one anonymous model (no model= routing)")
            return f"unknown model {m!r}: this server hosts {hosts}"
        return None

    def make_request(self, rid: int, r) -> ReadRequest:
        return ReadRequest(rid=rid, signal=np.asarray(r.signal))

    def degenerate(self, r) -> bool:
        """A zero-length signal chunks to zero windows: nothing to decode
        (misrouted models are never degenerate: ``validate`` errors them)."""
        if self.validate(r) is not None:
            return False
        return np.asarray(r.signal).shape[0] == 0

    def empty_result(self, r) -> BasecallResult:
        return BasecallResult.empty(self.pipe.max_read_len)

    def progress(self, native: ReadRequest) -> "_WindowView":
        # a lazy (read, length) view — the server polls progress() every
        # step, so materializing the zipped list each time would be
        # O(windows²) per read
        return _WindowView(native)

    def result_of(self, native: ReadRequest) -> BasecallResult:
        assert native.result is not None
        return native.result

    # -- admission ---------------------------------------------------------
    def submit(self, req: ReadRequest):
        self.sched.submit(req)

    @telemetry.span("admit.read")
    def _admit_one(self, slot: int, req: ReadRequest):
        req.windows = chunking.chunk_signal(req.signal, self.pipe.chunk)
        req.frame_lengths = self.pipe.window_logit_lengths(
            np.asarray(req.signal).shape[0])
        req.cursor = 0

    def admit(self) -> List[int]:
        admitted = self.sched.admit(self._admit_one)
        # an empty signal chunks to zero windows: retire it immediately
        # with an empty read instead of feeding step() an empty lane
        for slot in admitted:
            req = self.sched.slots[slot]
            if req is not None and req.windows.shape[0] == 0:
                self._finalize(req)
                self.sched.retire(slot, req.rid)
        return admitted

    # -- stepping ----------------------------------------------------------
    def active_mask(self) -> np.ndarray:
        return self.sched.active_mask()

    @telemetry.span("engine.step")
    def step(self):
        """Decode one window for every occupied lane in a single batch."""
        with telemetry.span("engine.assemble"):
            batch = np.stack([
                r.windows[r.cursor] if r is not None else self._zero
                for r in self.sched.slots])
            frames = np.asarray([
                r.frame_lengths[r.cursor] if r is not None else 0
                for r in self.sched.slots], np.int32)
        with telemetry.span("engine.transfer"):
            batch, frames = jnp.asarray(batch), jnp.asarray(frames)
            if self.mesh is not None:
                # B = batch_slots * dp by construction, so dim 0 divides
                batch = jax.device_put(
                    batch, shd.batch_sharding(self.mesh, batch.ndim))
                frames = jax.device_put(
                    frames, shd.batch_sharding(self.mesh, frames.ndim))
        with telemetry.span("engine.dispatch"), self._mesh_ctx():
            reads, lens, _scores = self.pipe._decode_windows(self.params,
                                                             batch, frames)
        with telemetry.span("engine.readback"):
            reads, lens = np.asarray(reads), np.asarray(lens)
        self.steps += 1
        with telemetry.span("engine.retire"):
            for slot, req in enumerate(self.sched.slots):
                if req is None:
                    continue
                req.reads.append(reads[slot])
                req.lengths.append(int(lens[slot]))
                req.cursor += 1
                if req.cursor >= req.windows.shape[0]:
                    self._finalize(req)
                    self.sched.retire(slot, req.rid)

    def _finalize(self, req: ReadRequest):
        if not req.reads:                      # zero-window (empty) signal
            req.result = BasecallResult.empty(self.pipe.max_read_len)
            return
        # the pipeline's own finalization — engine ≡ pipeline by sharing it
        req.result = BasecallResult.from_window_reads(
            np.stack(req.reads), np.asarray(req.lengths, np.int32),
            max_read_len=self.pipe.max_read_len)
