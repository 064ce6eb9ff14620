"""Faults planted in the program, under the harness's decode tap or
after it, for the output-check tests."""
import numpy as np


def _wrap_decode(pipe, alter):
    inner = pipe._decode_windows

    def broken(params, batch, frames):
        return alter(inner, params, batch, frames)

    pipe.__dict__["_decode_windows"] = broken
    return lambda: pipe.__dict__.__setitem__("_decode_windows", inner)


def altered_token(pipe, engine):
    """A base of every decoded window read changed where it is made."""
    def alter(inner, params, batch, frames):
        reads, lens, scores = inner(params, batch, frames)
        first = (reads[:, 0] + 1) % 4
        return reads.at[:, 0].set(np.where(lens > 0, first, reads[:, 0])), \
            lens, scores
    return _wrap_decode(pipe, alter)


def half_batch(pipe, engine):
    """Only the first half of the lanes decoded; the rest come back empty."""
    def alter(inner, params, batch, frames):
        half = batch.shape[0] // 2
        frames = frames.at[half:].set(0)
        return inner(params, batch.at[half:].set(0.0), frames)
    return _wrap_decode(pipe, alter)


def no_exchange(pipe, engine):
    """As if the gather between four chips were left out: every lane
    gets the result of its position on the first chip's shard."""
    def alter(inner, params, batch, frames):
        out = inner(params, batch, frames)
        q = batch.shape[0] // 4
        idx = np.arange(batch.shape[0]) % q
        return tuple(x[idx] for x in out)
    return _wrap_decode(pipe, alter)


def altered_consensus(pipe, engine):
    """A base of every finished read's consensus changed where it is
    voted."""
    inner = engine._finalize

    def broken(req):
        inner(req)
        r = req.result
        if r.length > 0:
            r.read = np.array(r.read)
            r.read[0] = (r.read[0] + 1) % 4

    engine._finalize = broken
    return None




class _EngineView:
    """The pipeline as the engine sees it, with its decode step's outputs
    altered after the harness's decode tap has kept them."""

    def __init__(self, pipe, alter):
        self._pipe, self._alter = pipe, alter

    def __getattr__(self, name):
        return getattr(self._pipe, name)

    def _decode_windows(self, params, batch, frames):
        return self._alter(self._pipe._decode_windows(params, batch, frames))


def swapped_lanes(pipe, engine):
    """Each lane's decoded window read stored with its neighbour's read:
    outputs attached to the wrong lane after the decode step."""
    def alter(out):
        return tuple(np.roll(np.asarray(x), 1, axis=0) for x in out)

    engine.pipe = _EngineView(pipe, alter)
    return lambda: setattr(engine, "pipe", pipe)
