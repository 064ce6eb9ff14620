"""Streaming basecalling (ReadUntil): chunk-size invariance, provisional
patch reconstruction, adaptive ejection, and the serving lifecycle.

Layers of proof:
  * ``WindowBuffer`` is bitwise ``chunk_signal`` under ANY chunking of
    the stream (1-sample / ragged / whole-read; hypothesis-driven), with
    bounded memory;
  * ``StreamingSession.finalize`` ≡ ``BasecallPipeline.basecall`` on the
    concatenated signal — bitwise, for every chunking, short (< window)
    and empty streams included, with and without a 4-device dp mesh;
  * folding every ``ProvisionalBases`` patch a stream emits reconstructs
    the exact final consensus (the incremental stitcher's contract);
  * ``StreamingBasecallEngine`` under ``Server``: golden-read parity,
    eject after N chunks frees the lane (slot conservation) and resolves
    ``"ejected"`` without perturbing concurrent lanes, cancel mid-stream
    terminates the consumer's generator, TTFE/ejected metrics;
  * the model-level chunk-boundary contract:
    ``apply_basecaller(rnn_state=..., return_state=True)`` splits a
    forward-only stack bitwise at any boundary, and refuses alternating
    stacks whose reversed walks integrate future samples.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.quant import QuantConfig
from repro.pipeline import BasecallPipeline
from repro.pipeline.chunking import (ChunkConfig, WindowBuffer, chunk_signal,
                                     complete_windows, n_windows,
                                     overlap_depth, window_valid_samples)
from repro.serve.api import Server, STATUS_EJECTED
from repro.serve.streaming import (ACCEPT, CONTINUE, EJECT, ProvisionalBases,
                                   ScoreEjectPolicy, StreamingBasecallEngine,
                                   StreamProgress, StreamRequest,
                                   apply_patches)

jax.config.update("jax_platform_name", "cpu")

QUANT = QuantConfig(enabled=True, bits_w=5, bits_a=5)


def _pipe(backend="auto", **kw):
    pipe = BasecallPipeline.from_preset("guppy", scale="tiny", quant=QUANT,
                                        backend=backend, beam_width=3, **kw)
    pipe.init_params(jax.random.PRNGKey(0))
    return pipe


_CACHE = {}


def _tiny_pipe():
    # module-level cache instead of a fixture: @given tests (whose shim
    # wrapper hides the signature from pytest) share it too
    if "pipe" not in _CACHE:
        _CACHE["pipe"] = _pipe()
    return _CACHE["pipe"]


@pytest.fixture(scope="module")
def tiny_pipe():
    return _tiny_pipe()


def _signal(n, seed=0):
    return np.random.default_rng(seed).standard_normal(n).astype(np.float32)


def _chunkings(sig, rng):
    """Three chunkings of one signal: whole-read, ragged, 1-sample."""
    n = len(sig)
    cuts = np.sort(rng.integers(0, n + 1, size=rng.integers(1, 8)))
    ragged = np.split(sig, cuts)
    ones = [sig[i:i + 1] for i in range(n)]
    return {"whole": [sig], "ragged": ragged, "one": ones}


# ---------------------------------------------------------------------------
# WindowBuffer ≡ chunk_signal
# ---------------------------------------------------------------------------

@settings(max_examples=25)
@given(n=st.integers(min_value=0, max_value=400),
       seed=st.integers(min_value=0, max_value=10_000))
def test_window_buffer_bitwise_matches_chunk_signal(n, seed):
    cfg = ChunkConfig(window=120, hop=60, batch_windows=4)
    rng = np.random.default_rng(seed)
    sig = rng.standard_normal(n).astype(np.float32)
    want = chunk_signal(sig, cfg)
    want_valid = window_valid_samples(n, cfg)
    for name, chunks in _chunkings(sig, rng).items():
        buf = WindowBuffer(cfg)
        got, valids = [], []
        for c in chunks:
            buf.feed(c)
            while buf.ready() > 0:           # complete windows stream early
                w, v = buf.next_window()
                got.append(w)
                valids.append(v)
        buf.end()
        while buf.ready() > 0:
            w, v = buf.next_window()
            got.append(w)
            valids.append(v)
        assert buf.total_windows == want.shape[0]
        assert len(got) == want.shape[0], name
        if got:
            np.testing.assert_array_equal(np.stack(got), want, err_msg=name)
            np.testing.assert_array_equal(np.asarray(valids), want_valid)


def test_window_buffer_bounded_memory():
    """Consumed samples are dropped: the buffer never holds more than
    window + hop samples no matter how long the stream runs."""
    cfg = ChunkConfig(window=120, hop=60)
    buf = WindowBuffer(cfg)
    for i in range(200):
        buf.feed(np.full(17, float(i), np.float32))
        while buf.ready() > 0:
            buf.next_window()
        held = 0 if buf._buf is None else buf._buf.shape[0]
        assert held <= cfg.window + cfg.hop


def test_window_buffer_misuse_raises():
    cfg = ChunkConfig(window=10, hop=5)
    buf = WindowBuffer(cfg)
    with pytest.raises(RuntimeError):
        buf.next_window()                    # nothing ready
    buf.feed(np.zeros((3, 2), np.float32))
    with pytest.raises(ValueError):
        buf.feed(np.zeros((3, 3), np.float32))   # channel mismatch
    with pytest.raises(ValueError):
        buf.feed(np.zeros((2, 2, 2), np.float32))
    buf.end()
    with pytest.raises(RuntimeError):
        buf.feed(np.zeros(3, np.float32))    # feed after end


def test_complete_windows_consistent_with_n_windows():
    cfg = ChunkConfig(window=120, hop=60)
    for n in range(0, 400, 7):
        c, total = complete_windows(n, cfg), n_windows(n, cfg)
        assert 0 <= c <= total
        # complete windows never change as more samples arrive
        assert complete_windows(n + 1, cfg) >= c
    assert overlap_depth(cfg) == 2
    assert overlap_depth(ChunkConfig(window=120, hop=120)) == 1


# ---------------------------------------------------------------------------
# chunk-size invariance: StreamingSession ≡ pipe.basecall, bitwise
# ---------------------------------------------------------------------------

def _assert_result_equal(got, want, msg=""):
    assert got.length == want.length, msg
    np.testing.assert_array_equal(got.read, want.read, err_msg=msg)
    np.testing.assert_array_equal(got.window_reads, want.window_reads,
                                  err_msg=msg)
    np.testing.assert_array_equal(got.window_lengths, want.window_lengths,
                                  err_msg=msg)


@settings(max_examples=8, deadline=None)
@given(n=st.integers(min_value=0, max_value=300),
       seed=st.integers(min_value=0, max_value=1_000))
def test_session_chunk_size_invariance(n, seed):
    """Any chunking of the stream — 1-sample, ragged, whole-read — yields
    the batch path's exact result, and folding the provisional patches
    reconstructs the exact final consensus."""
    pipe = _tiny_pipe()
    rng = np.random.default_rng(seed)
    sig = rng.standard_normal(n).astype(np.float32)
    want = pipe.basecall(sig)
    for name, chunks in _chunkings(sig, rng).items():
        sess = pipe.stream()
        for c in chunks:
            sess.feed(c)
        got = sess.finalize()
        _assert_result_equal(got, want, msg=name)
        np.testing.assert_array_equal(apply_patches(sess.events),
                                      want.read[:want.length], err_msg=name)


def test_session_short_and_empty_streams(tiny_pipe):
    """A first chunk smaller than one window streams a valid (possibly
    empty) read — not a shape error; an empty stream finalizes empty."""
    for n in (0, 1, 5, 119):
        sig = _signal(n, seed=n)
        sess = tiny_pipe.stream()
        if n:
            sess.feed(sig)
        got = sess.finalize()
        _assert_result_equal(got, tiny_pipe.basecall(sig), msg=f"n={n}")
    sess = tiny_pipe.stream()
    assert sess.finalize().length == 0
    with pytest.raises(RuntimeError):
        sess.feed(_signal(8))                # finalized session is closed


def test_session_finalize_idempotent(tiny_pipe):
    sess = tiny_pipe.stream()
    sess.feed(_signal(250))
    a = sess.finalize()
    b = sess.finalize()
    assert a is b


def test_session_under_mesh_matches_unmeshed(tiny_pipe, host_mesh4):
    from repro.dist import sharding as shd

    sig = _signal(400, seed=3)
    want = tiny_pipe.basecall(sig)
    with shd.use_mesh(host_mesh4):
        sess = tiny_pipe.stream()            # mesh pinned at creation
        for i in range(0, len(sig), 61):
            sess.feed(sig[i:i + 61])
        got = sess.finalize()
    _assert_result_equal(got, want, msg="dp=4 session")


# ---------------------------------------------------------------------------
# the incremental stitcher's patch contract
# ---------------------------------------------------------------------------

def test_apply_patches_semantics():
    p = [ProvisionalBases(0, np.array([1, 2, 3], np.int32)),
         ProvisionalBases(3, np.array([0, 1], np.int32)),
         ProvisionalBases(2, np.array([3], np.int32))]  # revising flush
    np.testing.assert_array_equal(apply_patches(p), [1, 2, 3])
    np.testing.assert_array_equal(apply_patches(p[:2]), [1, 2, 3, 0, 1])


def test_mid_stream_patches_are_append_only(tiny_pipe):
    sess = tiny_pipe.stream()
    emitted = 0
    for i in range(0, 700, 53):
        for patch in sess.feed(_signal(700, seed=9)[i:i + 53]):
            assert patch.start == emitted
            emitted += len(patch)
    sess.finalize()


# ---------------------------------------------------------------------------
# the streaming engine under the server
# ---------------------------------------------------------------------------

def _chunks_of(sig, k):
    for i in range(0, len(sig), k):
        yield sig[i:i + k]


def test_engine_stream_bitwise_matches_pipeline(tiny_pipe):
    sig = _signal(641, seed=7)
    want = tiny_pipe.basecall(sig)
    srv = Server(StreamingBasecallEngine(tiny_pipe, batch_slots=2))
    events = list(srv.stream(StreamRequest(chunks=_chunks_of(sig, 37))))
    final = events[-1]
    assert final.kind == "final" and final.payload.status == "ok"
    _assert_result_equal(final.payload.value, want)
    np.testing.assert_array_equal(
        apply_patches(e.payload for e in events[:-1]),
        want.read[:want.length])
    assert all(e.kind == "bases" for e in events[:-1])


def test_engine_concurrent_lanes_all_match(tiny_pipe):
    """More streams than slots, different chunkings per pore: every lane
    bitwise-matches its own batch-path result."""
    srv = Server(StreamingBasecallEngine(tiny_pipe, batch_slots=2))
    sigs = [_signal(n, seed=n) for n in (130, 380, 77, 641, 250)]
    futs = [srv.submit(StreamRequest(chunks=_chunks_of(s, 23 + 11 * i)))
            for i, s in enumerate(sigs)]
    res = srv.run_until_idle()
    for f, s in zip(futs, sigs):
        assert res[f.rid].status == "ok"
        _assert_result_equal(res[f.rid].value, tiny_pipe.basecall(s))


def test_engine_under_mesh_matches_single_device(tiny_pipe, host_mesh4):
    from repro.dist import sharding as shd

    sigs = [_signal(n, seed=n) for n in (380, 641)]
    want = [tiny_pipe.basecall(s) for s in sigs]
    with shd.use_mesh(host_mesh4):
        eng = StreamingBasecallEngine(tiny_pipe, batch_slots=1)  # B = 4
    assert eng.B == 4
    srv = Server(eng)                        # driven without ambient mesh
    futs = [srv.submit(StreamRequest(chunks=_chunks_of(s, 41)))
            for s in sigs]
    res = srv.run_until_idle()
    for f, w in zip(futs, want):
        _assert_result_equal(res[f.rid].value, w, msg="dp=4 engine")


def test_engine_degenerate_and_validation(tiny_pipe):
    srv = Server(StreamingBasecallEngine(tiny_pipe, batch_slots=2))
    res = srv.submit(StreamRequest(chunks=[])).result()
    assert res.status == "ok" and res.value.length == 0
    bad = srv.submit(StreamRequest(chunks=42)).result()
    assert bad.status == "error" and "iterable" in bad.error
    bad = srv.submit(StreamRequest(chunks=_chunks_of(_signal(10), 5),
                                   chunks_per_step=0)).result()
    assert bad.status == "error"
    # a stream of nothing but empty chunks must terminate, not livelock
    res = srv.submit(
        StreamRequest(chunks=iter([np.zeros(0, np.float32)] * 3))).result()
    assert res.status == "ok" and res.value.length == 0


# ---------------------------------------------------------------------------
# adaptive ejection (ReadUntil)
# ---------------------------------------------------------------------------

def test_eject_frees_slot_and_spares_concurrent_lanes(tiny_pipe):
    """The eject verdict retires the lane immediately: the request
    resolves "ejected" with the provisional read, the slot conserves
    (queued work admits into it), and concurrent lanes are bit-exact."""
    seen = []

    def policy(p):
        seen.append(p)
        return EJECT

    eng = StreamingBasecallEngine(tiny_pipe, batch_slots=2)
    srv = Server(eng)
    keep_sig = _signal(641, seed=1)
    f_keep = srv.submit(StreamRequest(chunks=_chunks_of(keep_sig, 37)))
    f_ej = srv.submit(StreamRequest(chunks=_chunks_of(_signal(5000, 2), 61),
                                    eject=policy, eject_after_chunks=3))
    f_queued = srv.submit(                   # waits for the ejected slot
        StreamRequest(chunks=_chunks_of(keep_sig, 50)))
    res = srv.run_until_idle()
    assert res[f_ej.rid].status == STATUS_EJECTED
    want = tiny_pipe.basecall(keep_sig)
    _assert_result_equal(res[f_keep.rid].value, want)
    _assert_result_equal(res[f_queued.rid].value, want)
    # the policy saw real progress, no earlier than the chunk threshold
    assert seen and all(isinstance(p, StreamProgress) for p in seen)
    assert seen[0].n_chunks >= 3
    # the ejected lane only consumed a prefix of its (long) stream
    assert seen[-1].n_samples < 5000
    # slots fully reclaimed
    assert eng.sched.slots.count(None) == eng.B
    assert eng.ejected == 1
    m = srv.metrics()
    assert m.ejected == 1 and m.completed == 2


def test_eject_verdicts_continue_and_accept(tiny_pipe):
    """CONTINUE keeps consulting; ACCEPT stops consulting and the read
    completes normally."""
    calls = {"n": 0}

    def accept_after_two(p):
        calls["n"] += 1
        return ACCEPT if calls["n"] >= 2 else CONTINUE

    sig = _signal(641, seed=4)
    srv = Server(StreamingBasecallEngine(tiny_pipe, batch_slots=1))
    res = srv.submit(StreamRequest(chunks=_chunks_of(sig, 37),
                                   eject=accept_after_two,
                                   eject_after_chunks=2)).result()
    assert res.status == "ok"
    _assert_result_equal(res.value, tiny_pipe.basecall(sig))
    assert calls["n"] == 2                   # ACCEPT silenced the policy


def test_score_eject_policy_thresholds():
    def prog(scores, lengths):
        return StreamProgress(
            read=np.zeros(int(sum(lengths)), np.int32),
            length=int(sum(lengths)),
            base_logprobs=np.zeros(int(sum(lengths)), np.float32),
            window_scores=np.asarray(scores, np.float32),
            window_lengths=np.asarray(lengths, np.int32),
            n_windows=len(scores), n_chunks=len(scores),
            n_samples=120 * len(scores))

    pol = ScoreEjectPolicy(threshold=-1.0, min_bases=8)
    assert pol(prog([-0.5], [4])) == CONTINUE          # not enough bases
    assert pol(prog([-4.0, -4.0], [5, 5])) == ACCEPT   # -0.8/base >= -1
    assert pol(prog([-20.0, -20.0], [5, 5])) == EJECT  # -4.0/base < -1


# ---------------------------------------------------------------------------
# cancellation mid-stream
# ---------------------------------------------------------------------------

def _endless_chunks(seed=0):
    rng = np.random.default_rng(seed)
    while True:
        yield rng.standard_normal(37).astype(np.float32)


def test_cancel_mid_stream_terminates_consumer(tiny_pipe):
    """cancel() on a stream()-consumed request must terminate the
    generator with a final "cancelled" event — even for an endless
    chunk source that would otherwise stream forever."""
    srv = Server(StreamingBasecallEngine(tiny_pipe, batch_slots=2))
    events = []
    gen = srv.stream(StreamRequest(chunks=_endless_chunks()), max_steps=500)
    for ev in gen:
        events.append(ev)
        srv.cancel(ev.rid)                   # cancel on the first event
    final = events[-1]
    assert final.kind == "final"
    assert final.payload.status == "cancelled"
    assert srv.engine.sched.slots.count(None) == srv.engine.B


def test_cancel_queued_stream_request(tiny_pipe):
    srv = Server(StreamingBasecallEngine(tiny_pipe, batch_slots=1))
    f1 = srv.submit(StreamRequest(chunks=_chunks_of(_signal(380), 37)))
    f2 = srv.submit(StreamRequest(chunks=_endless_chunks()))
    assert f2.cancel()
    assert f1.result().status == "ok"
    assert f2.result().status == "cancelled"


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def test_metrics_ttfe_and_ejected_counters(tiny_pipe):
    srv = Server(StreamingBasecallEngine(tiny_pipe, batch_slots=2))
    srv.submit(StreamRequest(chunks=_chunks_of(_signal(641, 5), 37)))
    srv.submit(StreamRequest(chunks=_chunks_of(_signal(900, 6), 61),
                             eject=lambda p: EJECT, eject_after_chunks=2))
    srv.run_until_idle()
    m = srv.metrics()
    assert m.ejected == 1
    assert m.ttfe_p50_s >= 0.0 and m.ttfe_p99_s >= m.ttfe_p50_s
    rows = dict((r[0], r[1]) for r in m.rows())
    assert "serve/ttfe_p50_s" in rows and "serve/ttfe_p99_s" in rows
    srv.reset_metrics()
    m2 = srv.metrics()
    assert m2.ejected == 0 and m2.ttfe_p50_s == 0.0


# ---------------------------------------------------------------------------
# model-level chunk-boundary state contract
# ---------------------------------------------------------------------------

def test_rnn_state_split_is_bitwise_for_uni_stacks():
    """Splitting a forward-only stack at any RNN-time boundary and
    re-entering with the carried state is bitwise identical to the
    unsplit run (the gru_seq state-in/state-out contract)."""
    from repro.models import basecaller as bc

    # float math, kernel-1 conv: no receptive-field halo and no dynamic
    # per-tensor act-quant scales (whose whole-sequence abs-max would
    # differ across splits) — the state contract itself is what's tested
    cfg = dataclasses.replace(
        bc.tiny_preset(), rnn_direction="uni",
        conv=(bc.ConvSpec(1, 16, 1),))
    params = bc.init_basecaller(jax.random.PRNGKey(1), cfg)
    sig = jnp.asarray(_signal(cfg.input_len, seed=8)[:, None][None])
    full = bc.apply_basecaller(params, sig, cfg)
    for cut in (1, cfg.input_len // 3, cfg.input_len - 1):
        lps_a, state = bc.apply_basecaller(params, sig[:, :cut], cfg,
                                           return_state=True)
        lps_b = bc.apply_basecaller(params, sig[:, cut:], cfg,
                                    rnn_state=state)
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(lps_a), np.asarray(lps_b)], axis=1),
            np.asarray(full), err_msg=f"cut={cut}")


def test_rnn_state_io_rejects_non_uni_stacks():
    from repro.models import basecaller as bc

    cfg = dataclasses.replace(bc.tiny_preset(), quant=QUANT)  # alt
    params = bc.init_basecaller(jax.random.PRNGKey(1), cfg)
    sig = jnp.zeros((1, 30, 1))
    with pytest.raises(ValueError, match="uni"):
        bc.apply_basecaller(params, sig, cfg, return_state=True)
    with pytest.raises(ValueError, match="uni"):
        bc.apply_basecaller(params, sig, cfg,
                            rnn_state=bc.init_rnn_state(cfg, 1))


# ---------------------------------------------------------------------------
# golden-read parity (trained pipeline)
# ---------------------------------------------------------------------------

def test_golden_session_and_engine_bitwise_match_basecall(golden_pipeline,
                                                          golden_read):
    pipe, params, _ = golden_pipeline
    _, sig = golden_read
    want = pipe.basecall(sig, params)
    sess = pipe.stream(params)
    for i in range(0, len(sig), 100):
        sess.feed(sig[i:i + 100])
    _assert_result_equal(sess.finalize(), want, msg="golden session")
    np.testing.assert_array_equal(apply_patches(sess.events),
                                  want.read[:want.length])
    srv = Server(StreamingBasecallEngine(pipe, params=params, batch_slots=2))
    res = srv.submit(StreamRequest(chunks=_chunks_of(sig, 100))).result()
    assert res.status == "ok"
    _assert_result_equal(res.value, want, msg="golden engine")
