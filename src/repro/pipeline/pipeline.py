"""``BasecallPipeline`` — the one facade over the Helix base-calling path.

The paper's end-to-end claim is about the *whole* pipeline: quantized DNN
inference, CTC decode, and read voting as one accelerated path.  This
class wires those stages together once, so callers stop re-plumbing
model -> ``ctc_beam_search_batch`` -> ``consensus_reads`` by hand:

    pipe = BasecallPipeline.from_preset("guppy",
                                        quant=QuantConfig(enabled=True),
                                        backend="auto")
    params = pipe.init_params(jax.random.PRNGKey(0))
    result = pipe.basecall(long_raw_signal)          # chunk/batch/decode/vote

Compute routes through ``repro.kernels.registry``: the ``backend`` switch
("auto" | "pallas" | "interpret" | "ref") picks the integer Pallas serving
path or the jnp oracle for every matmul/GRU step in one place.

Three call surfaces:
  basecall(signal)        — arbitrarily long raw read: overlapping windows,
                            batched model + CTC beam decode, voted consensus
  basecall_iter(signal)   — same, streaming one window-batch at a time
                            (bounded device memory for very long reads)
  basecall_windows(batch) — fixed (B, window+2*margin) signal windows through
                            the fused SEAT-view + consensus serving path
                            (what the serving engine batches over slots)
plus ``trainer()`` — the warm-up/SEAT two-phase policy (pipeline/training).

Serving consumes the quantize-once ``PackedParams`` artifact
(``serving_params()``: packed lazily, cached on checkpoint identity,
invalidated by ``init_params``/``params`` rebinds), while training keeps
the float checkpoint — the train-vs-serve split of ARCHITECTURE.md.
``packed=False`` preserves the legacy repack-per-call path as an oracle.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core import ctc as ctc_lib
from repro.core import seat as seat_lib
from repro.core.quant import QuantConfig
from repro.data import genome
from repro.dist import sharding as shd
from repro.kernels.registry import Backend
from repro.models import basecaller as bc
from repro.pipeline import chunking
from repro.pipeline.training import PhasedTrainer, TrainPolicy

_SCALES = {"full": lambda n: bc.PRESETS[n], "demo": bc.demo_preset,
           "tiny": bc.tiny_preset}


def _fifo_put(cache: dict, key, value, cap: int = 4) -> None:
    """Insert into a small bounded cache, evicting the oldest entry.

    The one eviction policy behind the pipeline's pack/placement/per-mesh
    caches — values hold strong refs to whatever pins their id()-based
    keys, so a bounded FIFO is all the invalidation these need."""
    if len(cache) >= cap:
        cache.pop(next(iter(cache)))
    cache[key] = value

# the LSTM "no fused kernel" notice is a property of the build, not of any
# one pipeline — emit it once per process, not once per construction
_LSTM_KERNEL_WARNED = False


def _warn_lstm_once(mode: str) -> None:
    global _LSTM_KERNEL_WARNED
    if _LSTM_KERNEL_WARNED:
        return
    _LSTM_KERNEL_WARNED = True
    warnings.warn(
        "LSTM stacks have no fused kernel: the recurrent loop runs "
        "on the fake-quant path; only projections use the integer "
        f"backend ({mode}).", stacklevel=3)


def _reset_lstm_warning() -> None:
    """Test hook: make the next LSTM pipeline warn again."""
    global _LSTM_KERNEL_WARNED
    _LSTM_KERNEL_WARNED = False


@dataclasses.dataclass
class BasecallResult:
    """One long read's consensus + the per-window reads that voted it."""
    read: np.ndarray            # (span,) int32 base ids, padded -1
    length: int
    window_reads: np.ndarray    # (n_windows, max_read_len)
    window_lengths: np.ndarray  # (n_windows,)

    def sequence(self, alphabet: str = "ACGT") -> str:
        """The consensus read as a base string (e.g. ``"ACGT..."``)."""
        return "".join(alphabet[b] for b in self.read[: self.length])

    @classmethod
    def empty(cls, max_read_len: int) -> "BasecallResult":
        """The zero-window result (empty signal): one definition shared by
        the pipeline and the engine so they cannot diverge."""
        return cls(read=np.full((max_read_len,), -1, np.int32), length=0,
                   window_reads=np.zeros((0, max_read_len), np.int32),
                   window_lengths=np.zeros((0,), np.int32))

    @classmethod
    @telemetry.span("vote")
    def from_window_reads(cls, reads: np.ndarray, lengths: np.ndarray,
                          *, max_read_len: int,
                          span: Optional[int] = None) -> "BasecallResult":
        """Vote one read's per-window decodes into its consensus.

        THE single finalization of the serving path: ``BasecallPipeline.
        basecall`` and ``serve.BasecallEngine`` both call this, which is
        what keeps engine ≡ pipeline bit for bit (zero windows -> empty,
        one window -> that read, else overlap-stitched consensus)."""
        reads = np.asarray(reads)
        lengths = np.asarray(lengths, np.int32)
        if reads.shape[0] == 0:
            return cls.empty(max_read_len)
        if reads.shape[0] == 1:
            cons, clen = reads[0], int(lengths[0])
        else:
            span = span or max_read_len * reads.shape[0]
            cons, clen = chunking.stitch_reads(reads, lengths, span=span)
        return cls(read=cons, length=clen, window_reads=reads,
                   window_lengths=lengths)


class BasecallPipeline:
    """The one facade over chunk → quantized model → CTC decode → vote.

    Construct via :meth:`from_preset` (paper presets) or directly from a
    ``models.basecaller.BasecallerConfig``; then ``init_params`` (or bind a
    checkpoint via ``params=``) and call one of the three serving surfaces
    — :meth:`basecall`, :meth:`basecall_iter`, :meth:`basecall_windows` —
    or train through :meth:`trainer`.

    Under an ambient ``dist.sharding.use_mesh`` mesh every serving surface
    runs dp-sharded: the window batch splits over the mesh's data-parallel
    devices (params replicated), per-window reads are all-gathered before
    the shared stitch/vote, and results are bitwise identical to the
    single-device path.

    Args:
        mcfg: the base-caller architecture/quantization config.
        backend: kernel registry backend ("auto" | "pallas" | "interpret"
            | "ref") threaded through every projection and recurrent step.
        scfg: SEAT view/consensus config (defaults derived from ``mcfg``).
        chunk: long-read windowing config; ``chunk.window`` must equal
            ``mcfg.input_len``.
        beam_width: CTC beam width (1 = greedy).
        max_read_len: decode pad length per window (default
            ``mcfg.output_len``).
        decode_strip: frames per persistent ``beam_merge_multiframe``
            launch in the hash beam decode (``None``/``1`` = the per-frame
            ``beam_merge_topk`` oracle loop; results are bitwise equal).
        packed: serve from the quantize-once ``PackedParams`` artifact
            (False keeps the repack-per-call oracle path).
        params: optional float checkpoint to bind immediately.

    Example::

        pipe = BasecallPipeline.from_preset("guppy", scale="demo",
                                            backend="auto")
        pipe.init_params(jax.random.PRNGKey(0))
        result = pipe.basecall(long_raw_signal)
    """

    def __init__(self, mcfg: bc.BasecallerConfig, *,
                 backend: str | Backend = "auto",
                 scfg: Optional[seat_lib.SEATConfig] = None,
                 chunk: Optional[chunking.ChunkConfig] = None,
                 beam_width: int = 5,
                 max_read_len: Optional[int] = None,
                 decode_strip: Optional[int] = 8,
                 packed: bool = True,
                 params=None):
        self.mcfg = mcfg
        self.backend = (backend if isinstance(backend, Backend)
                        else Backend(backend))
        self.scfg = scfg or seat_lib.SEATConfig(
            n_views=3, view_stride=8, max_read_len=mcfg.output_len,
            consensus_span=2 * mcfg.output_len)
        self.chunk = chunk or chunking.ChunkConfig(
            window=mcfg.input_len, hop=max(1, mcfg.input_len // 2))
        if self.chunk.window != mcfg.input_len:
            raise ValueError(
                f"chunk window {self.chunk.window} != model input_len "
                f"{mcfg.input_len}")
        self.beam_width = beam_width
        self.max_read_len = max_read_len or mcfg.output_len
        self.decode_strip = decode_strip
        self.packed = packed
        # id(float tree) -> (float tree, artifact); the strong ref pins the
        # id. Small FIFO so pipeline-default + engine/params= overrides of
        # different checkpoints coexist without repacking each other out.
        self._pack_cache: dict = {}
        # (id(tree), id(mesh)) -> mesh-replicated copy of a serving tree;
        # same bounded-FIFO discipline (strong refs pin both ids)
        self._placed_cache: dict = {}
        self.params = params
        self._trainer: Optional[PhasedTrainer] = None
        if mcfg.rnn_type == "lstm" and self.backend.mode != "ref":
            _warn_lstm_once(self.backend.mode)

    # -- construction ------------------------------------------------------
    @classmethod
    def from_preset(cls, name: str, *, quant: Optional[QuantConfig] = None,
                    backend: str | Backend = "auto", scale: str = "demo",
                    **kw) -> "BasecallPipeline":
        """Pipeline for a paper preset ("guppy"/"scrappie"/"chiron").

        Args:
            name: preset name — one of ``models.basecaller.PRESETS``.
            quant: optional ``QuantConfig`` replacing the preset's.
            backend: kernel registry backend (see class docstring).
            scale: "full" (Table 3 structure), "demo" (CPU-trainable), or
                "tiny" (unit-test widths).
            **kw: forwarded to the constructor (``beam_width``, ``chunk``,
                ``packed``, ...).

        Returns:
            A ready-to-init :class:`BasecallPipeline`.

        Example::

            pipe = BasecallPipeline.from_preset("guppy", scale="tiny",
                                                backend="ref")
        """
        if name not in bc.PRESETS:
            raise KeyError(f"unknown preset {name!r}; "
                           f"one of {sorted(bc.PRESETS)}")
        if scale not in _SCALES:
            raise KeyError(f"unknown scale {scale!r}; "
                           f"one of {sorted(_SCALES)}")
        mcfg = _SCALES[scale](name)
        if quant is not None:
            mcfg = mcfg.with_quant(quant)
        return cls(mcfg, backend=backend, **kw)

    # -- params + the quantize-once serving artifact -----------------------
    @property
    def params(self):
        """The float training checkpoint (pack-source for serving)."""
        return self._params_value

    @params.setter
    def params(self, value):
        # any rebind (init_params, trainer checkpoint) invalidates the
        # packed artifacts so serving repacks from the new generation
        self._params_value = value
        self._pack_cache.clear()
        self._placed_cache.clear()

    def init_params(self, key):
        """Initialize (and bind) a fresh float checkpoint from ``key``."""
        self.params = bc.init_basecaller(key, self.mcfg)
        return self.params

    def serving_params(self, params=None):
        """The weights the serving closures consume.

        With ``packed=True`` (default) this is the quantize-once
        ``PackedParams`` artifact: built lazily on first use and cached
        keyed on the float tree's identity (a small bounded cache, so the
        pipeline default and ``params=`` overrides — e.g. an engine
        serving a different checkpoint — each pack once).  ``init_params``
        / ``pipe.params = ...`` rebinds clear the cache, so a checkpoint
        re-trained mid-session is re-packed, never served stale.
        ``packed=False`` returns the float tree (the legacy
        repack-per-call path, kept as the benchmark baseline and
        differential oracle).
        """
        p = self._params(params)
        if not self.packed or bc.is_packed(p):
            return p
        hit = self._pack_cache.get(id(p))
        if hit is not None and hit[0] is p:
            return hit[1]
        artifact = bc.pack_basecaller(p, self.mcfg)
        _fifo_put(self._pack_cache, id(p), (p, artifact))
        return artifact

    def pack_artifact(self, params=None):
        """Build the quantize-once serving artifact WITHOUT touching the
        pipeline's own cache.

        The external-cache hook (``serve.registry.ModelRegistry``'s
        evict -> re-pack path): the packer is jitted and deterministic, so
        every call returns a bitwise-identical artifact and the caller
        fully owns its lifetime — evicting it frees the memory.  With
        ``packed=False`` (or already-packed ``params``) the weights pass
        through unchanged, like :meth:`serving_params`."""
        p = self._params(params)
        if not self.packed or bc.is_packed(p):
            return p
        return bc.pack_basecaller(p, self.mcfg)

    def data_config(self, *, kmer: int = 1, mean_dwell: float = 6.0,
                    max_label_len: Optional[int] = None
                    ) -> genome.SignalConfig:
        """Synthetic-channel config matching this model's window/margins."""
        return genome.SignalConfig(
            window=self.mcfg.input_len, margin=self.scfg.margin,
            max_label_len=max_label_len or self.scfg.max_read_len,
            kmer=kmer, mean_dwell=mean_dwell)

    def _params(self, params):
        p = params if params is not None else self.params
        if p is None:
            raise ValueError("no params: pass params= or call init_params()")
        return p

    def _place_params(self, params, mesh):
        """Replicate a serving tree onto ``mesh``, cached per (tree, mesh).

        dp shards *windows*, never weights: every device holds the whole
        serving artifact (``dist.sharding.replicated_sharding_tree`` — the
        param-rule machinery under a match-all REPLICATE override).  The
        mesh keys by VALUE (like ``_per_mesh``'s jit cache), so a caller
        building an equal-but-new Mesh per call does not re-transfer the
        whole artifact each time; the tree keys by identity (strong ref in
        the value pins the id)."""
        key = (id(params), mesh)
        hit = self._placed_cache.get(key)
        if hit is not None and hit[0] is params:
            return hit[1]
        placed = jax.device_put(
            params, shd.replicated_sharding_tree(params, mesh))
        _fifo_put(self._placed_cache, key, (params, placed))
        return placed

    # -- jitted stages -----------------------------------------------------
    def _per_mesh(self, build):
        """One jitted instance per ambient mesh (bounded cache).

        ``dist.sharding.constrain`` resolves the ambient mesh at TRACE
        time and bakes it into the jaxpr, while ``jax.jit`` caches traces
        on abstract values only — so a single jit object traced under mesh
        A would silently reuse A's constraints (or crash on incompatible
        devices) under mesh B.  Each mesh therefore gets its own jit
        instance, first-traced under its own ``use_mesh``."""
        fns: dict = {}

        def dispatch(*args):
            key = shd.get_mesh()                 # hashable; None off-mesh
            fn = fns.get(key)
            if fn is None:
                fn = build()
                _fifo_put(fns, key, fn)
            return fn(*args)

        dispatch.cache = fns  # mesh -> jit fn; analysis retrace guard hook
        return dispatch

    @functools.cached_property
    def _decode_windows(self):
        """(params, windows (N, window, C), logit_lengths (N,)) ->
        (reads (N, L), lens (N,), scores (N,)).

        Decode runs on the hash-merge beam decoder (``ctc_beam_search_hash
        _batch``) whose per-frame merge/top-k dispatches through the kernel
        registry on this pipeline's backend; ``logit_lengths`` masks the
        zero-padded frames of tail windows out of the decode.  ``scores``
        is the top beam's total log-probability per window (greedy: the
        best path's summed per-frame max) — the confidence signal the
        streaming eject policy consumes.  Dispatches to one jitted
        instance per ambient mesh (see ``_per_mesh``).
        """
        return self._per_mesh(self._build_decode_windows)

    def _build_decode_windows(self):
        mcfg, backend = self.mcfg, self.backend
        W, L = self.beam_width, self.max_read_len
        strip = self.decode_strip

        @jax.jit
        def decode_windows(params, windows, logit_lengths):
            # under an ambient mesh the window batch stays split over the
            # logical "dp" axis through model + decode; the final replicate
            # is the all-gather that hands the host the full window set
            # for the shared stitch/vote (no-ops without a mesh)
            with jax.named_scope("stage:windows_in"):
                windows = shd.constrain(windows, ("dp", None, None))
            with jax.named_scope("stage:lengths_in"):
                logit_lengths = shd.constrain(logit_lengths, ("dp",))
            lps = bc.apply_basecaller(params, windows, mcfg, backend=backend)
            if W > 1:
                with jax.named_scope("stage:beam_in"):
                    lps = shd.constrain(lps, ("dp", None, None))
                reads, lens, scores = ctc_lib.ctc_beam_search_hash_batch(
                    lps, beam_width=W, max_len=L,
                    logit_lengths=logit_lengths, backend=backend,
                    strip_frames=strip)
                reads, lens, scores = reads[:, 0], lens[:, 0], scores[:, 0]
            else:
                reads, lens = jax.vmap(
                    lambda lp, ll: ctc_lib.ctc_greedy_decode(
                        lp, logit_length=ll))(lps, logit_lengths)
                reads = reads[:, :L] if reads.shape[1] >= L else jnp.pad(
                    reads, ((0, 0), (0, L - reads.shape[1])),
                    constant_values=-1)
                lens = jnp.minimum(lens, L)
                # greedy confidence: the best path's log-probability over
                # the valid (non-padded) frames — the W==1 analogue of the
                # top beam's total score
                T = lps.shape[1]
                frame_max = jnp.max(lps, axis=-1)              # (N, T)
                valid = jnp.arange(T)[None, :] < logit_lengths[:, None]
                scores = jnp.sum(jnp.where(valid, frame_max, 0.0), axis=-1)
            with jax.named_scope("stage:reads_out"):
                reads = shd.replicate(reads)
            with jax.named_scope("stage:lens_out"):
                lens = shd.replicate(lens)
            with jax.named_scope("stage:scores_out"):
                scores = shd.replicate(scores)
            return reads, lens, scores

        return decode_windows

    @functools.cached_property
    def _windows_fused(self):
        """Fused SEAT-view serving path over (B, window+2*margin, C).

        One jitted instance per ambient mesh (see ``_per_mesh``)."""
        return self._per_mesh(self._build_windows_fused)

    def _build_windows_fused(self):
        mcfg, scfg, backend = self.mcfg, self.scfg, self.backend
        W = self.beam_width
        strip = self.decode_strip

        @jax.jit
        def fn(params, signal):
            with jax.named_scope("stage:fused_signal_in"):
                signal = shd.constrain(
                    signal, ("dp",) + (None,) * (signal.ndim - 1))
            views, center = seat_lib.make_views(signal, scfg)
            lps = jnp.stack([
                bc.apply_basecaller(params, v, mcfg, backend=backend)
                for v in views])
            C, C_len = seat_lib.consensus_reads(lps, center, scfg)
            with jax.named_scope("stage:beam_in"):
                center_lps = shd.constrain(lps[center], ("dp", None, None))
            reads, lens, scores = ctc_lib.ctc_beam_search_hash_batch(
                center_lps, beam_width=W, max_len=scfg.max_read_len,
                backend=backend, strip_frames=strip)
            with jax.named_scope("stage:fused_out"):
                return tuple(shd.replicate(t) for t in
                             (C, C_len, reads[:, 0], lens[:, 0],
                              scores[:, 0]))

        return fn

    # -- declared sharding boundaries (read by repro.analysis) -------------
    def decode_stage_boundaries(self) -> Tuple[str, ...]:
        """Stage boundaries of the jitted decode-windows trace, in order.

        Every name must realize a ``sharding_constraint`` under an
        ambient mesh (``stage:<name>`` scopes above + the model's own
        ``serving_stage_boundaries``); ``repro.analysis`` enforces this.
        """
        names = (("windows_in", "lengths_in")
                 + bc.serving_stage_boundaries(self.mcfg))
        if self.beam_width > 1:
            names += ("beam_in",)
        return names + ("reads_out", "lens_out", "scores_out")

    def fused_stage_boundaries(self) -> Tuple[str, ...]:
        """Stage boundaries of the fused SEAT-view serving trace."""
        return (("fused_signal_in",)
                + bc.serving_stage_boundaries(self.mcfg)
                + ("beam_in", "fused_out"))

    def window_logit_lengths(self, n_samples: int) -> np.ndarray:
        """(N,) decoder ``logit_lengths`` for one read's chunked windows."""
        valid = chunking.window_valid_samples(n_samples, self.chunk)
        return np.asarray(self.mcfg.output_frames(valid), np.int32)

    # -- long-read base-calling --------------------------------------------
    def basecall_iter(self, signal, params=None
                      ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        """Stream (window_reads, window_lengths) one window-batch at a time.

        Device memory is bounded by ``chunk.batch_windows`` windows
        regardless of read length; the final partial batch is padded to
        the batch shape (one compiled program) and trimmed on host.

        Under an ambient ``dist.sharding.use_mesh`` mesh each batch is
        device-put split over the logical "dp" axis (the batch is rounded
        up to a multiple of the dp device count with inert zero-padding
        first — padded lanes carry ``logit_length == 0``, decode nothing,
        and are trimmed on host), params are replicated, and the decoded
        reads are all-gathered — so the yielded arrays are bitwise
        identical to the single-device path.

        Args:
            signal: (T,) or (T, C) raw current samples, any length.
            params: optional checkpoint override (defaults to the bound
                pipeline params; packed lazily via :meth:`serving_params`).

        Returns:
            An iterator of ``(reads (n, L) int32, lengths (n,) int32)``
            per window-batch, in window order.

        Example::

            for reads, lens in pipe.basecall_iter(sig):
                ...
        """
        # resolve params and the ambient mesh EAGERLY — a generator body
        # would not run until first next(), by which time the caller's
        # use_mesh block may have exited (the pin-at-creation contract)
        params = self.serving_params(params)
        mesh = shd.get_mesh()
        dp = shd.dp_size(mesh)
        if mesh is not None:
            params = self._place_params(params, mesh)
        return self._basecall_iter(signal, params, mesh, dp)

    def _basecall_iter(self, signal, params, mesh, dp
                       ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        windows = chunking.chunk_signal(signal, self.chunk)
        frame_lens = self.window_logit_lengths(np.asarray(signal).shape[0])
        N = windows.shape[0]
        B = self.chunk.batch_windows
        if B % dp:
            B += dp - B % dp          # every device batch divides "dp"
        for s in range(0, N, B):
            grp = windows[s: s + B]
            fl = frame_lens[s: s + B]
            n = grp.shape[0]
            if n < B:
                grp = np.concatenate(
                    [grp, np.zeros((B - n,) + grp.shape[1:], grp.dtype)])
                fl = np.concatenate([fl, np.zeros((B - n,), fl.dtype)])
            grp, fl = jnp.asarray(grp), jnp.asarray(fl)
            if mesh is not None:
                grp = jax.device_put(grp, shd.batch_sharding(mesh, grp.ndim))
                fl = jax.device_put(fl, shd.batch_sharding(mesh, fl.ndim))
            # re-pin the mesh captured at generator creation: a consumer
            # advancing this generator under a *different* ambient mesh
            # (or none) must not mix this batch's placement with a decode
            # trace built for that other mesh (use_mesh(None) masks outer
            # meshes the same way)
            with shd.use_mesh(mesh):
                reads, lens, _scores = self._decode_windows(params, grp, fl)
            yield np.asarray(reads[:n]), np.asarray(lens[:n])

    def basecall(self, signal, params=None,
                 span: Optional[int] = None) -> BasecallResult:
        """Base-call one arbitrarily long raw read end to end.

        Chunks into overlapping windows, batches them through the
        quantized model + CTC beam decode, and votes the per-window reads
        into a consensus aligned by their longest matches.  Runs
        dp-sharded (bitwise identically) under an ambient
        ``dist.sharding.use_mesh`` mesh — see :meth:`basecall_iter`.

        Args:
            signal: (T,) or (T, C) raw current samples; an empty signal
                returns an empty result, never a crash.
            params: optional checkpoint override.
            span: consensus grid length for the stitch/vote (defaults to
                ``max_read_len * n_windows``).

        Returns:
            A :class:`BasecallResult` — voted consensus read plus the
            per-window reads that elected it.

        Example::

            result = pipe.basecall(long_raw_signal)
            print(result.sequence())
        """
        reads, lens = [], []
        for r, l in self.basecall_iter(signal, params):
            reads.append(r)
            lens.append(l)
        if not reads:
            # empty signal => zero windows: an empty read, not a crash
            return BasecallResult.empty(self.max_read_len)
        return BasecallResult.from_window_reads(
            np.concatenate(reads), np.concatenate(lens),
            max_read_len=self.max_read_len, span=span)

    def stream(self, params=None):
        """Open an incremental :class:`~repro.serve.streaming.
        StreamingSession` bound to this pipeline.

        Feed raw-signal chunks as they arrive from a pore
        (``session.feed``), read provisional bases as overlap windows
        close, and ``session.finalize()`` into a :class:`BasecallResult`
        bitwise identical to :meth:`basecall` on the concatenated signal —
        chunk boundaries never change the result.  Captures the ambient
        ``dist.sharding.use_mesh`` mesh at creation, like
        :meth:`basecall_iter`.

        Args:
            params: optional checkpoint override (defaults to the bound
                pipeline params; packed via :meth:`serving_params`).

        Returns:
            A live ``StreamingSession`` decoding windows as they complete.

        Example::

            sess = pipe.stream()
            for chunk in chunks:
                sess.feed(chunk)
            result = sess.finalize()     # == pipe.basecall(full_signal)
        """
        # local import: serve.streaming imports this module for the
        # shared BasecallResult finalization
        from repro.serve.streaming import StreamingSession
        return StreamingSession(self, params=params)

    # -- fixed-window serving ----------------------------------------------
    def basecall_windows(self, signal_batch, params=None):
        """(B, window+2*margin, C) signal windows -> fused serving outputs.

        The SEAT 3-view vote next to the center view's best beam, all in
        one jitted call.  Under an ambient ``dist.sharding.use_mesh`` mesh
        the window batch is split over the logical "dp" axis; unlike
        :meth:`basecall` this surface serves a *caller-fixed* batch, so a
        batch that does not divide the dp device count raises a clear
        ``ValueError`` instead of being padded (padding here would change
        the shapes the caller handed us).

        Args:
            signal_batch: (B, window + 2*margin, C) fixed signal windows
                (the serving engine's slot batch shape).
            params: optional checkpoint override.

        Returns:
            ``(consensus (B, L), consensus_len (B,), top_read (B, L'),
            top_len (B,), top_score (B,))``.

        Example::

            C, C_len, top, top_len, score = pipe.basecall_windows(batch)
        """
        params = self.serving_params(params)
        batch = jnp.asarray(signal_batch)
        mesh = shd.get_mesh()
        if mesh is not None:
            dp = shd.dp_size(mesh)
            if batch.shape[0] % dp:
                raise ValueError(
                    f"basecall_windows: batch of {batch.shape[0]} windows "
                    f"does not divide the mesh's dp={dp} devices; pad the "
                    f"batch to a multiple of {dp} (basecall/basecall_iter "
                    f"pad automatically)")
            params = self._place_params(params, mesh)
            batch = jax.device_put(batch, shd.batch_sharding(mesh,
                                                             batch.ndim))
        return self._windows_fused(params, batch)

    # -- training ----------------------------------------------------------
    def trainer(self, policy: Optional[TrainPolicy] = None,
                opt=None) -> PhasedTrainer:
        """The warm-up + SEAT phase policy for THIS model's training path
        (fake-quant STE — never the integer serving backend)."""
        if self._trainer is None or policy is not None or opt is not None:
            mcfg = self.mcfg
            self._trainer = PhasedTrainer(
                lambda p, s: bc.apply_basecaller(p, s, mcfg),
                self.scfg, policy or TrainPolicy(), opt)
        return self._trainer

    def train_step(self, params, opt_state, batch, step: int):
        """One policy-scheduled update (see ``pipeline.training``)."""
        return self.trainer().step(params, opt_state, batch, step)
