"""The traffic drivers: one per ``kind`` of traffic mix.

A driver builds the serving stack (``serve.api.Server`` over an engine),
warms up the cell's own shapes, opens the measured window, and returns
what happened in it.  It uses:

* ``DecodeTap`` — wraps the pipeline's jitted decode step (as the
  repository's own chip smoke does) and keeps, for a few steps chosen
  from the seed, which window each lane held and the step's outputs
  (device arrays, read back only after the window): what the output
  check compares.
* the persistent compile cache is switched off when the window opens,
  so a compile inside the window is a real compile.

``closed_batch`` (mix ``flowcell``): a finished run's reads, closed
loop.  Before every ``Server.step`` the queue is topped up to
``queue_per_lane`` reads per lane, so lanes never run dry.  The lanes
start in steady state: the first read of each lane is the rest of a
read in flight (``traffic.Traffic``), so reads finish from the first
step at the rate a long-running batch settles at, and not all at once
after the shortest whole read.
"""
from __future__ import annotations

import contextlib
import time

import jax
import numpy as np
from jax.experimental.compilation_cache import compilation_cache

from traffic import Traffic

def persistent_cache_off():
    """Turn JAX's persistent compile cache off; returns the undo."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def undo():
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return undo


def covered(n: int, k: int, window: int, hop: int) -> int:
    """Samples of an ``n``-sample read that its first ``k`` windows hold."""
    return 0 if k <= 0 else min(n, (k - 1) * hop + window)


class DecodeTap:
    """Wraps ``pipe._decode_windows``; keeps the outputs of sampled steps.

    ``lanes()`` (the driver's) names each lane's window when the step is
    taken: ``(rid, window index, valid samples)`` or None for an idle
    lane."""

    def __init__(self, pipe, lanes, sample_times, spans):
        self.pipe = pipe
        self.inner = pipe._decode_windows
        self.lanes = lanes
        self.times = list(sample_times)      # absolute perf_counter times
        self.captured = []
        self.spans = spans
        pipe.__dict__["_decode_windows"] = self

    def __call__(self, params, batch, frames):
        # one step per sample time: a time that falls inside a long step
        # is served by the next step
        take = bool(self.times) and time.perf_counter() >= self.times[0]
        if take:
            self.times.pop(0)
            lanes = self.lanes()
        with self.spans.span("decode"):
            out = self.inner(params, batch, frames)
        if take:
            self.captured.append((lanes, out))
        return out

    def remove(self) -> None:
        self.pipe.__dict__["_decode_windows"] = self.inner

    def readback(self) -> list:
        """[(lanes, reads, lengths, scores)] as numpy."""
        return [(lanes,) + tuple(np.asarray(x) for x in out)
                for lanes, out in self.captured]


def _mesh_ctx(chips: int):
    """The ``("data",)`` mesh over the cell's chips, ambient while the
    engine is built (one chip: none)."""
    if chips == 1:
        return contextlib.nullcontext()
    from repro.dist import sharding as shd
    mesh = shd.make_mesh((chips,), ("data",), devices=jax.devices()[:chips])
    return shd.use_mesh(mesh)


# ---------------------------------------------------------------------------
# closed_batch
# ---------------------------------------------------------------------------

def run_closed_batch(pipe, params, cfg, mix, chips, seed, seconds, spans,
                     compiles, n_samples_checked, patch=None,
                     trace_hooks=None, setup_t0=0.0):
    from repro.pipeline.pipeline import BasecallResult
    from repro.serve import BasecallRequest, Server
    from repro.serve.basecall_engine import BasecallEngine

    window, hop = cfg["input_len"], cfg["hop"]
    per_chip = int(mix["lanes_per_chip"])
    lanes = per_chip * chips
    qcap = int(mix["queue_per_lane"]) * lanes
    traffic = Traffic(mix, cfg["assumed"]["samples_per_base"], seed,
                      in_flight=lanes)
    with _mesh_ctx(chips):
        engine = BasecallEngine(pipe, params=params, batch_slots=per_chip)
    srv = Server(engine, max_queue=qcap + lanes, backpressure="reject",
                 clock=time.perf_counter)
    signals = {}
    state = {"next": 0}

    def top_up():
        while len(engine.sched.queue) < qcap:
            s = traffic.read(0, state["next"]).full()
            state["next"] += 1
            fut = srv.submit(BasecallRequest(signal=s))
            signals[fut.rid] = s

    def lanes_now():
        return [None if r is None else
                (r.rid, r.cursor,
                 min(window, len(signals[r.rid]) - r.cursor * hop))
                for r in engine.sched.slots]

    def windows_done():
        k = {}
        for r in engine.sched.slots:
            if r is not None:
                k[r.rid] = len(r.reads)
        for rid, res in srv.results.items():
            if res.ok and res.value is not None:
                k[rid] = len(res.value.window_lengths)
        return k

    # spans around the engine step and the per-read stitch/vote
    engine.step = spans.wrap("engine_step", engine.step)
    vote_fn = BasecallResult.__dict__["from_window_reads"]
    BasecallResult.from_window_reads = classmethod(
        spans.wrap("vote", vote_fn.__func__))

    # set-up: fill every lane and run the first step (compiles the decode)
    top_up()
    srv.step()
    top_up()
    srv.step()

    if trace_hooks is not None:
        trace_hooks[0]()
    t_start = time.perf_counter()
    sample_at = t_start + traffic.sample_times(n_samples_checked,
                                               0.8 * seconds)
    if patch is not None:                  # a fault under the timed path
        undo = patch(pipe, engine)
    tap = DecodeTap(pipe, lanes_now, sample_at, spans)
    cache_on = persistent_cache_off()
    spans.on = True
    k0 = windows_done()
    t_end = t_start + seconds
    steps0 = engine.steps
    while time.perf_counter() < t_end:
        top_up()
        srv.step()
    t_stop = time.perf_counter()
    spans.on = False
    if trace_hooks is not None:
        trace_hooks[1]()
    k1 = windows_done()
    tap.remove()
    cache_on()
    if patch is not None and undo is not None:
        undo()
    BasecallResult.from_window_reads = vote_fn
    delivered = sum(covered(len(signals[rid]), k, window, hop)
                    - covered(len(signals[rid]), k0.get(rid, 0), window, hop)
                    for rid, k in k1.items())
    finished = [res for res in srv.results.values()
                if t_start <= res.finished_at]
    return {
        "t_start": t_start, "t_stop": t_stop,
        "window_s": t_stop - t_start,
        "steps": engine.steps - steps0,
        "samples": delivered,
        "windows": sum(k - k0.get(rid, 0) for rid, k in k1.items()),
        "attempted": state["next"],
        "failed": sum(not r.ok for r in srv.results.values()),
        "finished": [(r.rid, r.value) for r in finished if r.ok],
        "captured": tap.readback(),
        "signal_of": signals.__getitem__,
        "setup_s": t_start - setup_t0,
        "compiles": compiles.since(t_start, t_stop),
        "completed": len(finished),
        "lanes": lanes,
        "keep": (engine, srv),
    }


DRIVERS = {"closed_batch": run_closed_batch}
