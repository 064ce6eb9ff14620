"""``repro.telemetry``: host spans, the compile ledger, the ``helix/``
profiler annotations, the span tree the serving path records, and the
server's queue wait."""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core.quant import QuantConfig
from repro.pipeline import BasecallPipeline, chunking
from repro.serve import BasecallRequest, Server
from repro.serve.basecall_engine import BasecallEngine
from repro.serve.scheduler import SlotScheduler
from repro.serve.streaming import StreamingBasecallEngine, StreamRequest


def _since(t0):
    return telemetry.records(t0)


# -- spans --------------------------------------------------------------------

def test_span_nesting_and_parent_names():
    t0 = time.perf_counter()
    with telemetry.span("outer"):
        with telemetry.span("mid"):
            with telemetry.span("leaf"):
                pass
        with telemetry.span("leaf"):
            pass
    recs = _since(t0)
    assert [(r.name, r.parent) for r in recs] == [
        ("leaf", "mid"), ("mid", "outer"), ("leaf", "outer"),
        ("outer", None)]
    outer = recs[-1]
    assert all(outer.t0 <= r.t0 <= r.t1 <= outer.t1 for r in recs)
    s = telemetry.summary(t0)
    assert s["leaf"]["count"] == 2 and s["outer"]["count"] == 1


def test_span_decorator_and_exception_still_record():
    @telemetry.span("decorated")
    def boom(x):
        """Docstring kept."""
        raise ValueError(x)

    assert boom.__doc__ == "Docstring kept."
    t0 = time.perf_counter()
    with pytest.raises(ValueError):
        with telemetry.span("around"):
            boom(1)
    assert [(r.name, r.parent) for r in _since(t0)] == [
        ("decorated", "around"), ("around", None)]
    # the stack unwound: a new span is top level again
    with telemetry.span("after"):
        pass
    assert _since(t0)[-1].parent is None


def test_each_thread_nests_its_own_spans():
    import threading
    t0 = time.perf_counter()
    done = threading.Event()

    def worker():
        with telemetry.span("thread.outer"):
            with telemetry.span("thread.inner"):
                pass
        done.set()

    with telemetry.span("main.open"):
        th = threading.Thread(target=worker)
        th.start()
        th.join(timeout=30)
    assert done.is_set() and not th.is_alive()
    parents = {r.name: r.parent for r in _since(t0)}
    assert parents == {"thread.inner": "thread.outer", "thread.outer": None,
                       "main.open": None}


def test_ring_is_bounded():
    t0 = time.perf_counter()
    for _ in range(telemetry.RING_SIZE + 10):
        with telemetry.span("many"):
            pass
    assert len(telemetry.records()) == telemetry.RING_SIZE
    assert len(_since(t0)) == telemetry.RING_SIZE


def test_records_window_excludes_spans_that_cross_it():
    with telemetry.span("before"):
        t0 = time.perf_counter()
    with telemetry.span("inside"):
        pass
    t1 = time.perf_counter()
    with telemetry.span("after"):
        pass
    assert [r.name for r in telemetry.records(t0, t1)] == ["inside"]


# -- the compile ledger -------------------------------------------------------

def test_fresh_jit_is_charged_to_its_span_and_cached_call_to_nothing():
    def scaled_tanh_sum(x):
        return jnp.sum(jnp.tanh(x) * 3.0)

    f = jax.jit(scaled_tanh_sum)
    x = jnp.arange(7.0)
    before = telemetry.compiles_by_site()
    t0 = time.perf_counter()
    with telemetry.span("first"):
        with telemetry.span("first.inner"):
            f(x).block_until_ready()
    with telemetry.span("second"):
        f(x).block_until_ready()
    inner, first, second = _since(t0)
    assert first.compiles >= 1 and first.compile_s > 0
    assert (inner.compiles, inner.compile_s) == (first.compiles,
                                                 first.compile_s)
    assert (second.compiles, second.compile_s) == (0, 0.0)
    # compile seconds are never more than the span's own seconds: nested
    # trace events are charged once
    assert first.compile_s <= first.t1 - first.t0
    after = telemetry.compiles_by_site()
    site = ("first.inner", "jit(scaled_tanh_sum)")
    assert after.get(site, 0) - before.get(site, 0) == 1


def test_compiles_outside_any_span_are_keyed_by_none():
    def lonely_cube(x):
        return x * x * x

    before = telemetry.compiles_by_site()
    jax.jit(lonely_cube)(jnp.ones(3)).block_until_ready()
    after = telemetry.compiles_by_site()
    site = (None, "jit(lonely_cube)")
    assert after.get(site, 0) - before.get(site, 0) == 1


def test_nested_jit_traces_are_charged_once():
    """Forty inner jits traced inside one outer trace: their trace events
    lie inside the outer one's, and charging them again would put the
    span's compile seconds above its own seconds."""
    def make(k):
        @jax.jit
        def inner(x):
            for _ in range(20):
                x = jnp.sin(x) * k + jnp.cos(x)
            return x
        return inner

    inners = [make(float(k)) for k in range(40)]

    @jax.jit
    def outer(x):
        for f in inners:
            x = f(x)
        return x

    x = jnp.ones(5)
    t0 = time.perf_counter()
    with telemetry.span("nested.compile"):
        outer(x).block_until_ready()
    (r,) = _since(t0)
    assert r.compiles == 1                    # one executable: outer
    assert 0.5 * (r.t1 - r.t0) < r.compile_s <= r.t1 - r.t0


def test_profiler_trace_holds_helix_events_on_its_host_plane(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("traced.outer"):
            with telemetry.span("traced.inner"):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    assert {"helix/traced.outer", "helix/traced.inner"} <= names


# -- the serving path's span tree ---------------------------------------------

@pytest.fixture(scope="module")
def tiny_pipe():
    pipe = BasecallPipeline.from_preset(
        "guppy", scale="tiny",
        quant=QuantConfig(enabled=True, bits_w=5, bits_a=5),
        backend="ref", beam_width=3)
    pipe.init_params(jax.random.PRNGKey(0))
    return pipe


def _signals():
    rng = np.random.default_rng(3)
    return [rng.standard_normal(n).astype(np.float32)
            for n in (520, 260, 700)]


def _serve(pipe, signals):
    srv = Server(BasecallEngine(pipe, batch_slots=2), max_queue=8)
    futs = [srv.submit(BasecallRequest(signal=s)) for s in signals]
    srv.run_until_idle()
    return [f.result().value for f in futs]


ENGINE_PHASES = {"engine.assemble", "engine.transfer", "engine.dispatch",
                 "engine.readback", "engine.retire"}
SERVER_PHASES = {"server.expire", "engine.admit", "engine.step",
                 "server.events", "server.resolve"}


def test_engine_through_server_records_the_span_tree(tiny_pipe):
    t0 = time.perf_counter()
    results = _serve(tiny_pipe, _signals())
    recs = _since(t0)
    parents = {}
    for r in recs:
        parents.setdefault(r.name, set()).add(r.parent)
    assert parents["server.step"] == {None}
    for name in SERVER_PHASES:
        assert parents[name] == {"server.step"}, name
    for name in ENGINE_PHASES:
        assert parents[name] == {"engine.step"}, name
    assert parents["admit.read"] == {"engine.admit"}
    assert parents["vote"] == {"engine.retire"}
    s = telemetry.summary(t0)
    assert s["admit.read"]["count"] == 3
    assert s["vote"]["count"] == len(results) == 3
    n_steps = s["server.step"]["count"]
    assert all(s[n]["count"] == n_steps for n in SERVER_PHASES)


def test_a_built_engine_votes_reads_without_compiling(tiny_pipe):
    """The engine compiles the vote's comparator when it is built, so
    reads of three window counts finish with no compile under ``vote``."""
    signals = _signals()
    counts = {chunking.n_windows(len(s), tiny_pipe.chunk) for s in signals}
    assert len(counts) == 3 and min(counts) >= 2
    srv = Server(BasecallEngine(tiny_pipe, batch_slots=2), max_queue=8)

    def vote_sites():
        return {k: n for k, n in telemetry.compiles_by_site().items()
                if k[0] in ("vote", "vote.compare")}

    before = vote_sites()
    t0 = time.perf_counter()
    futs = [srv.submit(BasecallRequest(signal=s)) for s in signals]
    srv.run_until_idle()
    assert all(f.result().ok for f in futs)
    s = telemetry.summary(t0)
    assert s["vote"]["count"] == 3 and s["vote.compare"]["count"] == 3
    assert s["vote"]["compiles"] == 0
    assert vote_sites() == before


def test_results_are_equal_with_and_without_a_profiler(tiny_pipe, tmp_path):
    plain = _serve(tiny_pipe, _signals())
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = _serve(tiny_pipe, _signals())
    finally:
        jax.profiler.stop_trace()
    assert len(plain) == len(traced)
    for a, b in zip(plain, traced):
        assert a.length == b.length
        np.testing.assert_array_equal(a.read, b.read)
        np.testing.assert_array_equal(a.window_reads, b.window_reads)


def test_streaming_engine_records_push_and_admission(tiny_pipe):
    sig = _signals()[0]
    srv = Server(StreamingBasecallEngine(tiny_pipe, batch_slots=2))
    t0 = time.perf_counter()
    res = srv.submit(StreamRequest(
        chunks=[sig[:200], sig[200:400], sig[400:]])).result()
    assert res.ok
    recs = _since(t0)
    parents = {}
    for r in recs:
        parents.setdefault(r.name, set()).add(r.parent)
    assert parents["admit.read"] == {"engine.admit"}
    assert parents["stream.push"] == {"engine.retire"}
    assert parents["vote"] == {"engine.retire"}
    assert parents["engine.readback"] == {"engine.step"}


# -- queue wait ---------------------------------------------------------------

class _Native:
    def __init__(self, rid, work):
        self.rid, self.work, self.out = rid, work, []


class _Request:
    def __init__(self, work):
        self.work = work


class _OneUnitEngine:
    """Each step gives every lane one unit; a lane retires after its
    request's ``work`` units."""
    event_kind = "unit"

    def __init__(self, slots):
        self.sched = SlotScheduler(slots)
        self.steps = 0

    def make_request(self, rid, r):
        return _Native(rid, r.work)

    def degenerate(self, r):
        return False

    def empty_result(self, r):
        return []

    def admit(self):
        return self.sched.admit(lambda slot, req: None)

    def step(self):
        self.steps += 1
        for slot, req in enumerate(self.sched.slots):
            if req is not None:
                req.out.append(len(req.out))
                if len(req.out) >= req.work:
                    self.sched.retire(slot, req.rid)

    def progress(self, native):
        return native.out

    def result_of(self, native):
        return list(native.out)


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_queue_wait_under_a_fake_clock_and_its_reset():
    clock = _Clock()
    srv = Server(_OneUnitEngine(1), max_queue=8, clock=clock)
    for _ in range(3):
        srv.submit(_Request(work=2))            # all submitted at t=0
    while srv.pending():
        srv.step()                               # admits at t = 0, 2, 4
        clock.t += 1.0
    m = srv.metrics()
    waits = [0.0, 2.0, 4.0]
    assert m.queue_wait_p50_s == pytest.approx(np.percentile(waits, 50))
    assert m.queue_wait_p99_s == pytest.approx(np.percentile(waits, 99))
    rows = dict((k, v) for k, v, _ in m.rows())
    assert rows["serve/queue_wait_p50_s"] == f"{m.queue_wait_p50_s:.4f}"
    assert "serve/queue_wait_p99_s" in rows
    srv.reset_metrics()
    m = srv.metrics()
    assert (m.queue_wait_p50_s, m.queue_wait_p99_s) == (0.0, 0.0)


def test_queue_wait_counts_each_request_once():
    clock = _Clock()
    srv = Server(_OneUnitEngine(2), max_queue=8, clock=clock)
    srv.submit(_Request(work=3))
    clock.t = 5.0
    srv.step()                                   # admitted after 5 s
    clock.t = 9.0
    srv.run_until_idle()
    assert srv.metrics().queue_wait_p99_s == pytest.approx(5.0)
    assert len(srv._queue_waits) == 1
