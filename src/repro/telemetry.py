"""Host spans and a compile ledger of the serving path.

``span(name)`` marks one phase of host work (a server tick, an engine
step, one read's vote).  On entry it opens a
``jax.profiler.TraceAnnotation`` named ``helix/<name>``, so in a profiled
run the span sits on the profiler's host plane, on the clock of the
device's operations; with no profiler session JAX's own check makes that
nearly free.  On exit it appends one :class:`Record` to a bounded ring,
with times on ``time.perf_counter`` (the clock ``Server(clock=
time.perf_counter)`` and benchmarks use).

The compile ledger listens to ``jax.monitoring`` from import on.  It
charges the seconds of JAX's tracing, lowering and backend
compile events to every span open in the compiling thread, and counts
each backend compile (one executable built) against every open span and
against its site: the innermost open span (``None`` outside any) and the
compiled function's name.  A compile event nested in another (an inner
``jit`` traced inside an outer trace) is already inside the outer
event's seconds and is not charged twice; the events' starts, which JAX
reports as scalars, say which are nested.

Recording is always on.  Reading::

    from repro import telemetry

    with telemetry.span("my.phase"):
        ...
    telemetry.records(t0, t1)       # Records that ran inside [t0, t1]
    telemetry.summary(t0, t1)       # per name: count, seconds, compiles
    telemetry.compiles_by_site()    # {(span or None, fun_name): compiles}
"""
from __future__ import annotations

import collections
import functools
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax

PREFIX = "helix/"
RING_SIZE = 100_000

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENTS = frozenset((TRACE_EVENT, LOWER_EVENT, BACKEND_COMPILE_EVENT))

_TraceAnnotation = jax.profiler.TraceAnnotation
_clock = time.perf_counter


class Record(NamedTuple):
    """One finished span: its name, the innermost span open around it
    (``None`` at top level), its ``perf_counter`` interval, and the
    compile seconds and backend compiles that ran inside it."""
    name: str
    parent: Optional[str]
    t0: float
    t1: float
    compile_s: float
    compiles: int


# plain tuples in Record's field order (a Record is built on read)
_ring: "collections.deque[tuple]" = collections.deque(maxlen=RING_SIZE)
_sites: "collections.Counter[Tuple[Optional[str], str]]" = \
    collections.Counter()


class _Local(threading.local):
    """Per thread: the open frames ``[name, t0, compile_s, compiles,
    annotation]`` and the depth of open compile events."""

    def __init__(self):
        self.frames: list = []
        self.depth = 0


_local = _Local()


class span:
    """Context manager and decorator: one named phase of host work.

    Example::

        with span("engine.step"):
            ...

        @span("vote")
        def vote(...): ...
    """
    __slots__ = ("name", "_label")

    def __init__(self, name: str):
        self.name = name
        self._label = PREFIX + name

    def __enter__(self) -> "span":
        ann = _TraceAnnotation(self._label)
        ann.__enter__()
        _local.frames.append([self.name, _clock(), 0.0, 0, ann])
        return self

    def __exit__(self, *exc) -> bool:
        t1 = _clock()
        frames = _local.frames
        name, t0, compile_s, compiles, ann = frames.pop()
        ann.__exit__(None, None, None)
        _ring.append((name, frames[-1][0] if frames else None,
                      t0, t1, compile_s, compiles))
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with self:
                return fn(*args, **kwargs)
        return wrapped


def _on_start(event: str, value, **kwargs) -> None:
    if event in COMPILE_EVENTS:
        _local.depth += 1


def _on_duration(event: str, secs: float, **kwargs) -> None:
    if event not in COMPILE_EVENTS:
        return
    _local.depth = max(_local.depth - 1, 0)
    frames = _local.frames
    if event == BACKEND_COMPILE_EVENT:
        _sites[(frames[-1][0] if frames else None,
                str(kwargs.get("fun_name")))] += 1
        for f in frames:
            f[3] += 1
    if _local.depth == 0:
        for f in frames:
            f[2] += secs


jax.monitoring.register_scalar_listener(_on_start)
jax.monitoring.register_event_duration_secs_listener(_on_duration)


def records(t0: float = float("-inf"),
            t1: float = float("inf")) -> List[Record]:
    """Finished spans that started at or after ``t0`` and ended by ``t1``
    (``perf_counter`` seconds), in the order they ended."""
    return [Record(*r) for r in list(_ring) if t0 <= r[2] and r[3] <= t1]


def compiles_by_site() -> Dict[Tuple[Optional[str], str], int]:
    """Backend compiles since import, keyed by (innermost open span or
    ``None``, compiled function's name)."""
    return dict(_sites)


def summary(t0: float = float("-inf"),
            t1: float = float("inf")) -> Dict[str, dict]:
    """Per span name over ``records(t0, t1)``: ``count``, ``seconds``,
    ``compile_s`` and ``compiles``."""
    out: Dict[str, dict] = {}
    for r in records(t0, t1):
        s = out.setdefault(r.name, {"count": 0, "seconds": 0.0,
                                    "compile_s": 0.0, "compiles": 0})
        s["count"] += 1
        s["seconds"] += r.t1 - r.t0
        s["compile_s"] += r.compile_s
        s["compiles"] += r.compiles
    return out


__all__ = ["span", "Record", "records", "compiles_by_site", "summary",
           "PREFIX", "RING_SIZE"]
