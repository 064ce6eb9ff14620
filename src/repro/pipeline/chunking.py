"""Window chunking + read stitching for arbitrarily long raw-signal reads.

A nanopore read is minutes of current samples; the base-caller consumes
fixed windows (paper: 300 x 1).  ``chunk_signal`` slices a long read into
overlapping windows on the host (data prep, not a hot loop — the hot loop
is the batched model/decode over the resulting array), and
``stitch_reads`` votes the per-window reads back into one consensus via
the longest-match alignment of ``core.voting`` (paper §4.3/Fig 19 — the
window order is known, consecutive windows overlap).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from repro.core import voting as voting_lib


@dataclasses.dataclass(frozen=True)
class ChunkConfig:
    window: int            # samples the model consumes per call
    hop: int               # window start stride; hop < window => overlap
    batch_windows: int = 8  # windows batched per device call (memory bound)

    def __post_init__(self):
        if not (0 < self.hop <= self.window):
            raise ValueError(
                f"hop must be in (0, window]; got hop={self.hop} "
                f"window={self.window}")


def n_windows(n_samples: int, cfg: ChunkConfig) -> int:
    """Windows covering ``n_samples`` (final partial window zero-padded).

    An empty signal has ZERO windows — fabricating an all-zero window for
    it would decode garbage and waste a device call; callers get an empty
    read instead (``BasecallPipeline.basecall`` / ``BasecallEngine``).
    """
    if n_samples <= 0:
        return 0
    if n_samples <= cfg.window:
        return 1
    return 1 + -(-(n_samples - cfg.window) // cfg.hop)


def window_valid_samples(n_samples: int, cfg: ChunkConfig) -> np.ndarray:
    """(N,) true sample count per window (== window except a padded tail).

    ``chunk_signal`` zero-pads the final partial window; decoding those
    padded frames produces garbage bases, so the pipeline converts these
    counts to per-window ``logit_lengths`` for the beam decoder.
    """
    N = n_windows(n_samples, cfg)
    starts = np.arange(N, dtype=np.int64) * cfg.hop
    return np.minimum(cfg.window, np.maximum(n_samples - starts, 0)) \
        .astype(np.int32)


def complete_windows(n_samples: int, cfg: ChunkConfig) -> int:
    """Windows fully determined by the first ``n_samples`` of a stream.

    Window ``i`` covers samples ``[i*hop, i*hop + window)``; it is
    *complete* — its contents can never change as more samples arrive —
    once ``n_samples >= i*hop + window``.  The remaining (tail) windows of
    :func:`n_windows` only exist once the stream ENDS, because whether the
    tail is zero-padded depends on the final total length.
    """
    if n_samples < cfg.window:
        return 0
    return 1 + (n_samples - cfg.window) // cfg.hop


def overlap_depth(cfg: ChunkConfig) -> int:
    """Max windows any sample position can fall into (= ceil(window/hop)).

    The streaming stitcher's horizon: once this many newer windows have
    opened past a consensus position, no further window can vote there —
    the position's overlap window has closed.
    """
    return -(-cfg.window // cfg.hop)


class WindowBuffer:
    """Incremental :func:`chunk_signal`: samples in, windows out.

    Accumulates raw-signal chunks (``feed``) and hands out each overlap
    window exactly once (``next_window``) as soon as its samples are
    complete — bitwise identical to slicing the concatenated signal with
    :func:`chunk_signal`.  Consumed samples no window can still need are
    dropped, so memory is bounded by ``window + hop`` samples regardless
    of stream length.  ``end()`` closes the stream, releasing the
    zero-padded tail window (whose padding depends on the final length).
    """

    def __init__(self, cfg: ChunkConfig):
        self.cfg = cfg
        self.n_fed = 0          # total samples ever fed
        self.emitted = 0        # windows handed out so far
        self.ended = False
        self._buf: Optional[np.ndarray] = None   # (n, C) pending samples
        self._base = 0          # stream index of _buf[0]

    def feed(self, chunk: np.ndarray) -> int:
        """Append one raw chunk ((t,) or (t, C)); returns samples added.

        Chunks may be any size — including empty, or smaller than one
        window (nothing becomes ready until a window's worth arrives).
        """
        if self.ended:
            raise RuntimeError("WindowBuffer.feed after end()")
        sig = np.asarray(chunk, np.float32)
        if sig.ndim == 1:
            sig = sig[:, None]
        if sig.ndim != 2:
            raise ValueError(f"chunk must be (t,) or (t, C); "
                             f"got shape {sig.shape}")
        if sig.shape[0] == 0:
            if self._buf is None and sig.shape[1] != 1:
                self._buf = sig          # pin C even from an empty chunk
            return 0
        if self._buf is not None and sig.shape[1] != self._buf.shape[1]:
            raise ValueError(f"chunk has {sig.shape[1]} channels; "
                             f"stream started with {self._buf.shape[1]}")
        if self._buf is None or self._buf.shape[0] == 0:
            self._buf = sig.copy()
        else:
            self._buf = np.concatenate([self._buf, sig])
        self.n_fed += sig.shape[0]
        return sig.shape[0]

    def end(self) -> None:
        """Mark the stream complete: tail windows become ready."""
        self.ended = True

    @property
    def total_windows(self) -> Optional[int]:
        """Final window count (None until ``end()``)."""
        return n_windows(self.n_fed, self.cfg) if self.ended else None

    def ready(self) -> int:
        """Windows ready to emit right now (complete, or tail after end)."""
        done = (n_windows(self.n_fed, self.cfg) if self.ended
                else complete_windows(self.n_fed, self.cfg))
        return done - self.emitted

    def next_window(self) -> Tuple[np.ndarray, int]:
        """Pop the next ready window: ((window, C) float32, valid_samples).

        ``valid_samples`` is the window's true sample count (< window only
        for the zero-padded tail) — feed it through
        ``BasecallerConfig.output_frames`` for the decoder's
        ``logit_length``.  Raises when nothing is ready (check
        :meth:`ready`).
        """
        if self.ready() <= 0:
            raise RuntimeError("no window ready (buffer more samples, "
                               "or end() the stream for the tail)")
        cfg, i = self.cfg, self.emitted
        start = i * cfg.hop
        valid = min(cfg.window, self.n_fed - start)
        C = 1 if self._buf is None else self._buf.shape[1]
        out = np.zeros((cfg.window, C), np.float32)
        lo = start - self._base
        out[:valid] = self._buf[lo: lo + valid]
        self.emitted += 1
        # drop samples below the next unemitted window's start — bounded
        # memory is the point of streaming
        keep_from = self.emitted * cfg.hop
        if keep_from > self._base and self._buf is not None:
            drop = min(keep_from, self._base + self._buf.shape[0]) \
                - self._base
            self._buf = self._buf[drop:]
            self._base += drop
        return out, valid


def chunk_signal(signal: np.ndarray, cfg: ChunkConfig) -> np.ndarray:
    """(T,) or (T, C) raw read -> (n_windows, window, C) float32.

    The tail window is zero-padded — the pore signal is standardized to
    zero mean so padding is inert rather than a level step.
    """
    sig = np.asarray(signal, np.float32)
    if sig.ndim == 1:
        sig = sig[:, None]
    T, C = sig.shape
    N = n_windows(T, cfg)
    out = np.zeros((N, cfg.window, C), np.float32)
    for i in range(N):
        s = i * cfg.hop
        piece = sig[s: s + cfg.window]
        out[i, : piece.shape[0]] = piece
    return out


def stitch_reads(reads, lengths, span: int | None = None
                 ) -> Tuple[np.ndarray, int]:
    """Vote per-window reads (N, L) back into one consensus read.

    Thin alias over ``core.voting.vote_host`` (``core.voting.vote`` bit for
    bit, with no compile per read) so the pipeline has a single stitching
    entry point.  Returns (consensus (span,) padded -1, length).
    """
    return voting_lib.vote_host(reads, lengths, span=span)
