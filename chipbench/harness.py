"""One run of one cell: build, warm up, measure, check, report.

Everything a cell is made of is found by name from ``BENCHMARK.json``:
its configuration (``configs/<config>.json``), its traffic mix
(``traffic/<traffic>.json``, whose ``kind`` picks the driver), its
per-layer metrics (``metrics/<metric>.py``, one reader each) and the
limits of its output check (``limits/<workload>.json``).  The
configuration's ``reference`` names its model family: the plain
reference ``references/<reference>.py`` and the program side
``families/<reference>.py``, which draws the weights, builds the
pipeline and counts a window's operations, so the harness itself knows
no model's shape.
"""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
WORK = HERE / "_work"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything it names."""

    def __init__(self, bench: dict, name: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; one of {sorted(cells)}")
        w = cells[name]
        self.name, self.chips = name, int(w["chips"])
        self.cfg = load_json(HERE / "configs" / f"{w['config']}.json")
        family_of(self.cfg)          # refuse before any run
        self.mix = load_json(HERE / "traffic" / f"{w['traffic']}.json")
        self.limits = load_json(HERE / "limits" / f"{name}.json")

        def mine(m):
            return "workloads" not in m or name in m["workloads"]
        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        self.per_layer = [m for m in bench["per_layer"] if mine(m)]


def seed32(seed: int) -> int:
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               & 0x7FFFFFFF)


def family_of(cfg: dict):
    """The program side of the configuration's model family,
    ``families/<reference>.py``, once it has said that it covers the
    configuration (its ``validate`` raises ``ValueError`` otherwise)."""
    name = cfg["reference"]
    missing = [f"chipbench/{d}/{name}.py" for d in ("families", "references")
               if not (HERE / d / f"{name}.py").exists()]
    if missing:
        raise ValueError(
            f"configuration {cfg['name']!r} names the model family "
            f"{name!r} in 'reference', which lacks {' and '.join(missing)}: "
            "a family is chipbench/families/<name>.py (the program side: "
            "validate, make_params, build_pipeline, macs_by_precision, "
            "PEAK_OF, tiny) beside chipbench/references/<name>.py (the "
            "plain side)")
    fam = load_module("families", name)
    fam.validate(cfg)
    return fam


def make_params(cfg: dict, seed: int):
    """Float weights of the configuration, drawn by its family on the
    device from the seed."""
    return family_of(cfg).make_params(cfg, seed)


def build_pipeline(cfg: dict, backend: str):
    """The program's pipeline at exactly the configuration's sizes."""
    return family_of(cfg).build_pipeline(cfg, backend)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under the benchmark's directory."""
    spec = importlib.util.spec_from_file_location(
        f"{kind}_" + name.replace(".", "_"), HERE / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    return load_module("metrics", name).read


def memory_peak(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def device_info(chips: int) -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "device_kind": d.device_kind, "count": chips}


class Readings:
    """What a per-layer metric reader may read."""

    def __init__(self, cell, run, spans, trace, peaks):
        self.cell, self.cfg, self.mix = cell, cell.cfg, cell.mix
        self.chips = cell.chips
        self.run, self.spans, self.trace, self.peaks = run, spans, trace, peaks

    def span_list(self, name: str) -> list:
        lo, hi = self.run["t_start"], self.run["t_stop"]
        return [(a, b) for a, b in self.spans.t.get(name, ())
                if lo <= a and b <= hi]

    def nested_seconds(self, outer: str, inner: str) -> float:
        """Seconds of ``inner`` spans that lie inside ``outer`` spans."""
        out = self.span_list(outer)
        total, j = 0.0, 0
        for a, b in self.span_list(inner):
            while j < len(out) and out[j][1] < a:
                j += 1
            if j < len(out) and out[j][0] <= a and b <= out[j][1]:
                total += b - a
        return total


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             backend: str = "pallas", patch=None, pipe=None,
             controls=(), t_process: float = None) -> dict:
    """Drive one run; return the result line's object.

    ``pipe`` reuses a pipeline (and its compiled decode step) across
    runs of one process; each of ``controls`` (see ``check.compare``)
    also puts the reference in the program's place (``_control``)."""
    import jax
    from drivers import DRIVERS
    from spans import CompileCounter, Spans
    import check

    t0 = time.perf_counter() if t_process is None else t_process
    spans = Spans(annotate=trace)
    compiles = CompileCounter()
    cfg, mix = cell.cfg, cell.mix
    if pipe is None:
        pipe = build_pipeline(cfg, backend)
    params = make_params(cfg, seed)
    pipe.params = params
    drive = DRIVERS[mix["kind"]]
    trace_dir = WORK / "trace" / cell.name
    state = {}

    def start_trace():
        if trace_dir.exists():
            shutil.rmtree(trace_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        state["trace_t0"] = time.perf_counter()

    def stop_trace():
        state["trace_t1"] = time.perf_counter()   # writing the trace is not
        jax.profiler.stop_trace()                 # part of the window

    # steps sampled for the output check: about 1,024 windows, as four
    # steps at 256 lanes
    lanes = int(mix["lanes_per_chip"])
    n_samples_checked = max(4, -(-1024 // (lanes * cell.chips)))
    run = drive(pipe, params, cfg, mix, cell.chips, seed, seconds, spans,
                compiles, n_samples_checked, patch=patch,
                trace_hooks=(start_trace, stop_trace) if trace else None,
                setup_t0=t0)
    mem = memory_peak(cell.chips)
    dev = dict(device_info(cell.chips), memory_peak_bytes=mem)
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            v = end_to_end_value(m["name"], run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    summary = None
    if trace:
        import xplane
        devices, hspans, modules = xplane.read_planes(
            xplane.find_xplane(str(trace_dir)))
        summary = xplane.reduce(
            devices, hspans, state["trace_t1"] - state["trace_t0"],
            modules=modules)
        dev["busy_s"] = summary.get("busy_s", 0.0)
        dev["window_s"] = summary["window_s"]
        peaks = load_json(HERE / "peaks.json")
        rd = Readings(cell, run, spans, summary,
                      peaks.get(dev["kind"]))
        if rd.peaks is None:
            raise KeyError(f"device kind {dev['kind']!r} is not in "
                           "peaks.json")
        for m in cell.per_layer:
            v = load_reader(m["name"])(rd)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    # free the program's state before the reference runs
    keep = run.pop("keep")
    del keep, pipe
    verdict = check.compare(cfg, params, run, cell.limits, seed)
    out = {"correct": bool(verdict["correct"]),
           "attempted": int(run["attempted"]),
           "failed": int(run["failed"]),
           "metrics": metrics, "device": dev}
    if summary is not None:
        out["breakdown"] = {"device_ops": summary.get("device_ops", []),
                            "idle_gaps": summary.get("idle_gaps", [])}
    out["checks"] = verdict["checks"]
    out["_numbers"] = verdict["numbers"]
    out["_run"] = {k: run[k] for k in ("window_s", "steps", "lanes",
                                       "completed") if k in run}
    out["_run"]["compiles_in_window"] = len(run["compiles"])
    out["_run"]["compile_s"] = sum(d for _, d in run["compiles"])
    out["_run"]["vote_s"] = [round(b - a, 3) for a, b in spans.t.get(
        "vote", ()) if run["t_start"] <= a < run["t_stop"]]
    out["_run"]["setup_s"] = run["setup_s"]
    if summary is not None:          # executions of each program traced
        out["_run"]["traced_module_n"] = summary.get("module_n", {})
    out["_control"] = [
        dict(c, **check.compare(cfg, params, run, cell.limits, seed,
                                control=c)["numbers"]) for c in controls]
    return out


def end_to_end_value(name: str, run: dict):
    """The end-to-end metrics the benchmark takes itself (host clock)."""
    if name == "setup_s":
        return run["setup_s"]
    if name == "samples_per_s" and "samples" in run:
        return run["samples"] / run["window_s"]
    return None


def emit(out: dict) -> None:
    """Notes and the compared numbers on stderr, then the result line on
    stdout."""
    print(f"run: {json.dumps(out['_run'])}", file=sys.stderr)
    print(f"compared: {json.dumps(out['_numbers'])}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    line = {k: v for k, v in out.items() if not k.startswith("_")}
    checks = line.pop("checks")
    line["checks"] = checks                     # last on the line
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
