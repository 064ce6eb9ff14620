"""Harness spans and the compile counter.

Spans wrap calls into the program from the benchmark's side: the program
files are not touched.  A span records its host interval; in a traced run
it also opens a ``jax.profiler.TraceAnnotation`` named ``cb:<name>`` so
that the trace reduction can say what the host was doing in each idle
gap of the device.  Every wrapped call ends on a host readback inside the
program (``np.asarray`` of its device results), so a span's host time
covers the device work it waited for.
"""
from __future__ import annotations

import collections
import contextlib
import time

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Spans:
    """Named host intervals, kept in memory until the run ends."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.on = False
        self.t = collections.defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.on:
            yield
            return
        t0 = time.perf_counter()
        if self.annotate:
            with jax.profiler.TraceAnnotation("cb:" + name):
                yield
        else:
            yield
        self.t[name].append((t0, time.perf_counter()))

    def wrap(self, name: str, fn):
        def wrapped(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)
        return wrapped


class CompileCounter:
    """XLA compilations, from ``jax.monitoring``.

    JAX reports one ``backend_compile_duration`` event per executable it
    builds or loads from the persistent cache; with that cache off (the
    measured window), each is a compile."""

    def __init__(self):
        self.events = []                      # (perf_counter at end, secs)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), float(duration)))

    def since(self, t0: float, t1: float = float("inf")) -> list:
        return [(t, d) for t, d in self.events if t0 <= t < t1]
