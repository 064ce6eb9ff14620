"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData`` and nothing else.  A device plane
(``/device:TPU:<n>``) carries its operations on the line ``XLA Ops``;
the host plane carries the harness spans, ``TraceAnnotation``s named
``cb:<span>``.  Both are on the profiler's one clock.

* modules: the device time of each XLA program (the ``XLA Modules``
  line; one event per execution), per device (mean over devices).
* busy: the union of a device's op intervals; ``busy_s`` is its mean
  over the devices in the trace, ``idle share = 1 - busy_s / window_s``.
* op time: the summed durations of each op name, per device (mean over
  devices); kernels are found by the name their ``pallas_call`` gives
  them.
* idle gaps: the intervals between a device's busy intervals, each
  labelled by the innermost harness span open at its midpoint
  (``host`` when none is).
"""
from __future__ import annotations

import collections
import glob
import gzip
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _merge(iv: list) -> list:
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def read_planes(path: str) -> tuple:
    """({device name: [(start_ns, end_ns, op name)]}, [(s, e, span)],
    {device name: [(start_ns, end_ns, module name)]}) of an
    ``.xplane.pb`` file (or its gzip)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices, spans, modules = {}, [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            for line in plane.lines:
                into = {OPS_LINE: devices, MODULES_LINE: modules}.get(
                    line.name)
                if into is not None:
                    into[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("cb:"):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name[3:]))
    return devices, spans, modules


_HLO = re.compile(r"^(%[\w.\-]+) = (.*?)\b([a-z][\w\-]*)\(")


def short_name(op: str) -> str:
    """``%name opcode result-type`` of an HLO op's text (types cut to 60
    characters)."""
    m = _HLO.match(op)
    if m is None:
        return op[:100]
    return f"{m.group(1)} {m.group(3)} {m.group(2).strip()[:60]}"


def _label(t: float, spans: list) -> str:
    best, width = "host", float("inf")
    for s, e, name in spans:
        if s <= t <= e and e - s < width:
            best, width = name, e - s
    return best


def reduce(devices: dict, spans: list, window_s: float,
           top: int = 10, modules: dict = None) -> dict:
    """Busy time, op time, module time and idle gaps of a traced
    window."""
    if not devices:
        return {"devices": 0, "busy_s": 0.0, "window_s": window_s}
    n = len(devices)
    mod_s, mod_n = collections.Counter(), collections.Counter()
    for evs in (modules or {}).values():
        for a, b, name in evs:
            mod_s[name] += (b - a) / 1e9 / n
            mod_n[name] += 1 / n
    busy, op_s, op_n = 0.0, collections.Counter(), collections.Counter()
    gaps = []
    spans = sorted(spans)
    for k, (name, evs) in enumerate(sorted(devices.items())):
        merged = _merge([(a, b) for a, b, _ in evs])
        busy += sum(b - a for a, b in merged) / 1e9
        for a, b, op in evs:
            op_s[op] += (b - a) / 1e9 / n
            op_n[op] += 1 / n
        if k == 0:
            for (_, e0), (s1, _) in zip(merged[:-1], merged[1:]):
                if s1 > e0:
                    gaps.append((s1 - e0, _label((e0 + s1) / 2, spans)))
    gaps.sort(reverse=True)
    return {
        "devices": n,
        "busy_s": busy / n,
        "window_s": window_s,
        "op_s": dict(op_s),
        "op_n": dict(op_n),
        "module_s": dict(mod_s),
        "module_n": dict(mod_n),
        "device_ops": [[short_name(op), s]
                       for op, s in op_s.most_common(top)],
        "idle_gaps": [[lab, g / 1e9] for g, lab in gaps[:top]],
    }
