"""The program's own spans (``repro.telemetry``), for per-layer readers.

Two sources, both written inside the program:

* ``records(rd)``: the span records of the measured window, read from
  the program's ring in the process that ran it (``perf_counter`` times;
  compile seconds and backend compiles per span).
* ``trace_layout(rd)``: the traced run's first device's idle gaps, cut
  as ``xplane.reduce`` cuts them, and the ``helix/`` annotations of the
  host plane, both on the profiler's clock.

A program without ``repro.telemetry`` gives no records and no ``helix/``
annotations, so the readers that use them report nothing.
"""
from __future__ import annotations

import bisect

import harness
import xplane

PREFIX = "helix/"


def records(rd) -> list:
    """The program's span records that ran inside the measured window."""
    try:
        from repro import telemetry
    except ImportError:
        return []
    return telemetry.records(rd.run["t_start"], rd.run["t_stop"])


def named(recs: list, name: str) -> list:
    return [r for r in recs if r.name == name]


def seconds(recs: list) -> float:
    return sum(r.t1 - r.t0 for r in recs)


def nested_seconds(outer: list, inner: list) -> float:
    """Seconds of the ``inner`` records that lie inside an ``outer`` one
    (records of one name never overlap one another)."""
    iv = sorted((r.t0, r.t1) for r in outer)
    starts = [a for a, _ in iv]
    total = 0.0
    for r in inner:
        k = bisect.bisect_right(starts, r.t0) - 1
        if k >= 0 and r.t1 <= iv[k][1]:
            total += r.t1 - r.t0
    return total


def host_spans(path: str) -> list:
    """``[(start_ns, end_ns, name)]`` of the ``helix/`` annotations on the
    host plane of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        out.append((e.start_ns, e.start_ns + e.duration_ns,
                                    e.name[len(PREFIX):]))
    return out


def trace_layout(rd):
    """``(gaps, spans)`` of the traced run: the first device's idle gaps
    ``[(start_ns, end_ns)]`` and the ``helix/`` spans; None untraced."""
    if not rd.trace:
        return None
    path = xplane.find_xplane(str(harness.WORK / "trace" / rd.cell.name))
    devices, _, _ = xplane.read_planes(path)
    if not devices:
        return None
    first = sorted(devices.items())[0][1]
    merged = xplane._merge([(a, b) for a, b, _ in first])
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(merged[:-1], merged[1:])
            if s1 > e0]
    return gaps, host_spans(path)


def idle_outside(gaps: list, spans: list) -> float:
    """Percent of the gaps' time during which no span is open."""
    cover = xplane._merge([(s, e) for s, e, *_ in spans])
    total = outside = 0
    j = 0
    for a, b in sorted(gaps):
        total += b - a
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        inside, k = 0, j
        while k < len(cover) and cover[k][0] < b:
            inside += min(b, cover[k][1]) - max(a, cover[k][0])
            k += 1
        outside += (b - a) - inside
    return 100.0 * outside / total
