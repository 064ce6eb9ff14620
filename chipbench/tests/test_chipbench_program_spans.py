"""CPU tests of the readers of the program's own spans
(``program_spans.py`` and the seven metrics that use it), on synthetic
records and a synthetic gap/span layout."""
import glob
import math
import os
import time

import pytest

import chipbench_tiny  # noqa: F401  (puts chipbench/ on the path)
import harness
import program_spans as ps
import xplane
from repro import telemetry
from repro.telemetry import Record

READERS = ["vote_compile_ms_per_read.batch", "vote_exec_ms_per_read.batch",
           "engine_host_ms_per_step.batch", "decode_wait_ms_per_step.batch",
           "admit_ms_per_read.batch", "server_self_ms_per_step.batch",
           "idle_outside_spans_pct.batch"]


def rec(name, parent, t0, t1, compile_s=0.0, compiles=0):
    return Record(name, parent, t0, t1, compile_s, compiles)


# two server ticks; the first admits a read and finishes one (a vote of
# 2 s, 1.5 s of it compiling), the second only decodes.  A readback and a
# vote outside any engine step (as a pipeline call would record) are not
# the engine's.
TICKS = [
    rec("admit.read", "engine.admit", 0.6, 0.9),
    rec("engine.admit", "server.step", 0.5, 1.0),
    rec("engine.assemble", "engine.step", 1.0, 1.5),
    rec("engine.readback", "engine.step", 2.0, 6.0),
    rec("vote", "engine.retire", 7.0, 9.0, 1.5, 40),
    rec("engine.retire", "engine.step", 6.0, 9.0, 1.5, 40),
    rec("engine.step", "server.step", 1.0, 9.0, 1.5, 40),
    rec("server.step", None, 0.0, 10.0, 1.5, 40),
    rec("engine.admit", "server.step", 10.0, 10.1),
    rec("engine.readback", "engine.step", 10.5, 11.5),
    rec("engine.step", "server.step", 10.2, 11.8),
    rec("server.step", None, 10.0, 12.0),
    rec("engine.readback", None, 20.0, 21.0),
    rec("vote", None, 21.0, 21.5, 0.25, 3),
]


@pytest.fixture
def synthetic(monkeypatch):
    def use(recs, layout=None):
        monkeypatch.setattr(ps, "records", lambda rd: list(recs))
        monkeypatch.setattr(ps, "trace_layout", lambda rd: layout)
    return use


def read(name):
    return harness.load_reader(name)(None)


def test_vote_compile_and_exec_split_each_vote(synthetic):
    synthetic(TICKS)
    # votes of 2.0 s (1.5 compiling) and 0.5 s (0.25 compiling)
    assert math.isclose(read("vote_compile_ms_per_read.batch"), 875.0)
    assert math.isclose(read("vote_exec_ms_per_read.batch"), 375.0)
    total = read("vote_compile_ms_per_read.batch") \
        + read("vote_exec_ms_per_read.batch")
    assert math.isclose(total, 1e3 * (2.0 + 0.5) / 2)


def test_engine_host_and_decode_wait_split_the_step(synthetic):
    synthetic(TICKS)
    # steps 8.0 + 1.6 s; readbacks inside them 4.0 + 1.0 s; the vote
    # inside them 2.0 s; the readback and vote outside are not counted
    assert math.isclose(read("decode_wait_ms_per_step.batch"), 2500.0)
    assert math.isclose(read("engine_host_ms_per_step.batch"), 1300.0)
    # together: the engine step less its votes
    assert math.isclose(read("engine_host_ms_per_step.batch")
                        + read("decode_wait_ms_per_step.batch"),
                        1e3 * (9.6 - 2.0) / 2)


def test_admission_and_server_self_time(synthetic):
    synthetic(TICKS)
    assert math.isclose(read("admit_ms_per_read.batch"), 300.0)
    # ticks 10 + 2 s less engine steps 9.6 s and admissions 0.6 s
    assert math.isclose(read("server_self_ms_per_step.batch"), 900.0)


def test_a_step_that_crosses_the_tick_is_not_nested():
    outer = [rec("server.step", None, 0.0, 1.0),
             rec("server.step", None, 2.0, 3.0)]
    inner = [rec("engine.step", "x", 0.5, 0.9),      # inside the first
             rec("engine.step", "x", 0.9, 2.5),      # crosses both
             rec("engine.step", "x", 2.0, 3.0)]      # exactly the second
    assert math.isclose(ps.nested_seconds(outer, inner), 0.4 + 1.0)
    assert ps.nested_seconds([], inner) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_empty_window_reads_none(synthetic, name):
    synthetic([], None)
    assert read(name) is None


@pytest.mark.parametrize("name", READERS[:-1])
def test_a_window_without_the_readers_span_reads_none(synthetic, name):
    # a window of ticks that never reached the spans the reader needs
    synthetic([rec("server.expire", "server.step", 0.0, 0.1)])
    assert read(name) is None


def test_idle_outside_spans_overlap_and_nesting(synthetic):
    ms = 1_000_000
    gaps = [(0, 10 * ms), (20 * ms, 30 * ms), (40 * ms, 41 * ms)]
    spans = [(5 * ms, 25 * ms, "server.step"),
             (6 * ms, 7 * ms, "vote"),               # nested: counted once
             (40 * ms, 45 * ms, "server.step")]      # covers the third gap
    # uncovered: 0-5 ms and 25-30 ms of 21 ms of gaps
    want = 100.0 * 10 / 21
    assert math.isclose(ps.idle_outside(gaps, spans), want)
    synthetic([], (gaps, spans))
    assert math.isclose(read("idle_outside_spans_pct.batch"), want)
    synthetic([], (gaps, []))            # a program without helix/ spans
    assert read("idle_outside_spans_pct.batch") is None
    assert ps.idle_outside(gaps, []) == 100.0


def test_trace_layout_cuts_gaps_as_the_reduction_does(monkeypatch):
    ms = 1_000_000
    devices = {"/device:TPU:1": [(0, 9 * ms, "x")],
               "/device:TPU:0": [(0, 2 * ms, "a"), (1 * ms, 3 * ms, "b"),
                                 (5 * ms, 6 * ms, "c"),
                                 (6 * ms, 7 * ms, "d"),
                                 (8 * ms, 9 * ms, "e")]}
    spans = [(3 * ms, 4 * ms, "server.step")]
    monkeypatch.setattr(xplane, "find_xplane", lambda d: "trace.xplane.pb")
    monkeypatch.setattr(xplane, "read_planes",
                        lambda path: (devices, [], {}))
    monkeypatch.setattr(ps, "host_spans", lambda path: spans)

    class Rd:
        trace = {"devices": 2}

        class cell:
            name = "guppy.flowcell"

    gaps, got = ps.trace_layout(Rd)
    assert gaps == [(3 * ms, 5 * ms), (7 * ms, 8 * ms)]
    assert got == spans
    red = xplane.reduce(devices, [], window_s=0.01)
    assert sorted(g for _, g in red["idle_gaps"]) == sorted(
        (b - a) / 1e9 for a, b in gaps)
    Rd.trace = None                                   # untraced run
    assert ps.trace_layout(Rd) is None


def test_records_are_the_programs_own_in_the_window():
    with telemetry.span("chipbench.before"):
        pass

    class Rd:
        run = {"t_start": time.perf_counter()}

    with telemetry.span("chipbench.inside"):
        pass
    Rd.run["t_stop"] = time.perf_counter()
    with telemetry.span("chipbench.after"):
        pass
    assert [r.name for r in ps.records(Rd)] == ["chipbench.inside"]


def test_host_spans_read_a_profiler_trace(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("chipbench.traced"):
            with telemetry.span("vote"):
                pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    spans = ps.host_spans(path)
    assert sorted(name for _, _, name in spans) == ["chipbench.traced",
                                                    "vote"]
    (outer,) = [s for s in spans if s[2] == "chipbench.traced"]
    (inner,) = [s for s in spans if s[2] == "vote"]
    assert outer[0] <= inner[0] <= inner[1] <= outer[1]
