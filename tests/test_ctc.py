"""CTC loss / decode correctness vs brute-force oracles."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ctc as ctc_lib

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# brute-force oracles
# ---------------------------------------------------------------------------

def brute_force_logp(log_probs: np.ndarray, labels, blank: int) -> float:
    """Σ over ALL alignments (paths) that collapse to `labels`."""
    T, A = log_probs.shape
    total = -np.inf
    for path in itertools.product(range(A), repeat=T):
        # collapse: remove repeats then blanks
        out, prev = [], None
        for s in path:
            if s != prev and s != blank:
                out.append(s)
            prev = s
        if out == list(labels):
            lp = sum(log_probs[t, s] for t, s in enumerate(path))
            total = np.logaddexp(total, lp)
    return total


def all_decodes_ranked(log_probs: np.ndarray, blank: int):
    """Exact posterior over all label sequences (tiny T/A only)."""
    T, A = log_probs.shape
    scores = {}
    for path in itertools.product(range(A), repeat=T):
        out, prev = [], None
        for s in path:
            if s != prev and s != blank:
                out.append(s)
            prev = s
        lp = sum(log_probs[t, s] for t, s in enumerate(path))
        key = tuple(out)
        scores[key] = np.logaddexp(scores.get(key, -np.inf), lp)
    return sorted(scores.items(), key=lambda kv: -kv[1])


def _rand_logprobs(rng, T, A):
    x = rng.standard_normal((T, A)).astype(np.float32)
    return jax.nn.log_softmax(jnp.asarray(x), axis=-1)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,A,labels", [
    (4, 3, [0, 1]),
    (5, 3, [1]),
    (5, 5, [0, 0]),       # repeat needs a blank between
    (6, 5, [2, 1, 2]),
    (3, 4, []),           # empty label: all-blank paths
])
def test_ctc_loss_matches_bruteforce(T, A, labels):
    rng = np.random.default_rng(42 + T + A + len(labels))
    lp = _rand_logprobs(rng, T, A)
    blank = A - 1
    want = -brute_force_logp(np.asarray(lp), labels, blank)
    L = max(len(labels), 1)
    lab = jnp.full((L,), 0, jnp.int32).at[: len(labels)].set(
        jnp.asarray(labels, jnp.int32) if labels else jnp.zeros((0,), jnp.int32))
    got = ctc_lib.ctc_loss(lp, lab, label_length=len(labels))
    np.testing.assert_allclose(float(got), want, rtol=1e-5, atol=1e-5)


def test_ctc_loss_label_padding_invariance():
    rng = np.random.default_rng(0)
    lp = _rand_logprobs(rng, 8, 5)
    lab1 = jnp.array([0, 2, 1], jnp.int32)
    lab2 = jnp.array([0, 2, 1, 3, 3, 0], jnp.int32)  # extra garbage padding
    a = ctc_lib.ctc_loss(lp, lab1, label_length=3)
    b = ctc_lib.ctc_loss(lp, lab2, label_length=3)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-6)


def test_ctc_loss_logit_length_masking():
    rng = np.random.default_rng(1)
    lp8 = _rand_logprobs(rng, 8, 5)
    lab = jnp.array([1, 2], jnp.int32)
    a = ctc_lib.ctc_loss(lp8[:5], lab)
    b = ctc_lib.ctc_loss(lp8, lab, logit_length=5)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-6)


def test_ctc_loss_impossible_label():
    # label longer than frames => probability 0 => loss ~ +inf (NEG-bounded)
    rng = np.random.default_rng(2)
    lp = _rand_logprobs(rng, 2, 5)
    lab = jnp.array([0, 1, 2], jnp.int32)
    loss = float(ctc_lib.ctc_loss(lp, lab))
    assert loss > 1e8


def test_ctc_loss_gradients_finite():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((12, 5)).astype(np.float32))
    lab = jnp.array([0, 1, 1, 2], jnp.int32)

    def f(logits):
        return ctc_lib.ctc_loss(jax.nn.log_softmax(logits, -1), lab)

    g = jax.grad(f)(x)
    assert np.all(np.isfinite(np.asarray(g)))
    # grad wrt a softmax distribution sums to ~0 per frame
    np.testing.assert_allclose(np.asarray(g).sum(-1), 0.0, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(T=st.integers(2, 5), A=st.integers(2, 4), seed=st.integers(0, 10_000))
def test_ctc_loss_is_proper_nll(T, A, seed):
    """-ln p >= 0 i.e. p(D|R) <= 1, and total prob over decodes == 1."""
    rng = np.random.default_rng(seed)
    lp = _rand_logprobs(rng, T, A)
    ranked = all_decodes_ranked(np.asarray(lp), blank=A - 1)
    total = -np.inf
    for key, s in ranked:
        total = np.logaddexp(total, s)
        if len(key) > 0:
            loss = float(ctc_lib.ctc_loss(
                lp, jnp.asarray(key, jnp.int32), label_length=len(key)))
            assert loss >= -1e-4
            np.testing.assert_allclose(loss, -s, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(total, 0.0, atol=1e-5)  # Σ_D p(D|R) == 1


# ---------------------------------------------------------------------------
# greedy decode
# ---------------------------------------------------------------------------

def test_greedy_decode_collapse():
    A, blank = 5, 4
    # path: a a - b b - - a  -> collapse to a b a
    ids = [0, 0, 4, 1, 1, 4, 4, 0]
    lp = jnp.log(jax.nn.one_hot(jnp.asarray(ids), A) * 0.9 + 0.02)
    read, n = ctc_lib.ctc_greedy_decode(lp)
    assert int(n) == 3
    assert list(np.asarray(read[:3])) == [0, 1, 0]
    assert np.all(np.asarray(read[3:]) == -1)


def test_greedy_decode_logit_length():
    A = 5
    ids = [0, 4, 1, 4, 2, 4]
    lp = jnp.log(jax.nn.one_hot(jnp.asarray(ids), A) * 0.9 + 0.02)
    read, n = ctc_lib.ctc_greedy_decode(lp, logit_length=3)
    assert int(n) == 2
    assert list(np.asarray(read[:2])) == [0, 1]


# ---------------------------------------------------------------------------
# beam search
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,A,seed", [(3, 3, 0), (4, 3, 1), (5, 3, 2),
                                      (4, 4, 3), (5, 4, 4)])
def test_beam_search_finds_map_decode(T, A, seed):
    """With a wide beam, prefix beam search must find the exact MAP read."""
    rng = np.random.default_rng(seed)
    lp = _rand_logprobs(rng, T, A)
    ranked = all_decodes_ranked(np.asarray(lp), blank=A - 1)
    want_read, want_score = ranked[0]
    prefixes, lens, scores = ctc_lib.ctc_beam_search(lp, beam_width=16)
    got = tuple(np.asarray(prefixes[0][: int(lens[0])]))
    assert got == want_read, f"beam {got} != exact {want_read}"
    np.testing.assert_allclose(float(scores[0]), want_score, rtol=1e-4,
                               atol=1e-4)


def test_beam_search_scores_vs_forward_algorithm():
    """Pruned beam scores lower-bound the exact probability; with a beam wide
    enough to cover every reachable prefix they match it exactly."""
    rng = np.random.default_rng(7)
    # lower bound under pruning
    lp = _rand_logprobs(rng, 5, 4)
    prefixes, lens, scores = ctc_lib.ctc_beam_search(lp, beam_width=8)
    for k in range(4):
        L = int(lens[k])
        if L == 0:
            continue
        lab = jnp.asarray(np.asarray(prefixes[k][:L]), jnp.int32)
        exact = -float(ctc_lib.ctc_loss(lp, lab))
        assert float(scores[k]) <= exact + 1e-4
    # exact when nothing is pruned: T=3, A=3 has <= 15 reachable prefixes
    lp = _rand_logprobs(rng, 3, 3)
    prefixes, lens, scores = ctc_lib.ctc_beam_search(lp, beam_width=32)
    for k in range(8):
        L = int(lens[k])
        if L == 0 or float(scores[k]) < -1e8:
            continue
        lab = jnp.asarray(np.asarray(prefixes[k][:L]), jnp.int32)
        exact = -float(ctc_lib.ctc_loss(lp, lab))
        np.testing.assert_allclose(float(scores[k]), exact, rtol=1e-4, atol=1e-4)


def test_beam_search_paper_example():
    """Fig. 4d: A beats AA/A-/-A/-- after merging at t=1."""
    # probs: t0: A=0.3, -=0.5 (top-2 kept), t1: A=0.3, -=0.4
    p = jnp.asarray([[0.3, 0.15, 0.05, 0.0, 0.5],
                     [0.3, 0.2, 0.1, 0.0, 0.4]])
    lp = jnp.log(p + 1e-9)
    prefixes, lens, scores = ctc_lib.ctc_beam_search(lp, beam_width=2)
    got = tuple(np.asarray(prefixes[0][: int(lens[0])]))
    assert got == (0,)  # "A"
    # p(A) = p(AA)+p(A-)+p(-A) = .09+.12+.15 = .36 > p(--)=.2
    np.testing.assert_allclose(float(jnp.exp(scores[0])), 0.36, atol=1e-3)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_beam_search_monotone_in_width(seed):
    """At every beam width the best score is bounded by the exact
    posterior: it never exceeds the probability of its own read, which
    never exceeds the MAP read's.  (The best score itself is NOT monotone
    in width: pruning at a wider beam can keep a different, worse read —
    seed 3550 is one such case.)"""
    rng = np.random.default_rng(seed)
    lp = _rand_logprobs(rng, 6, 4)
    exact = dict(all_decodes_ranked(np.asarray(lp), blank=3))
    best = max(exact.values())
    for W in (1, 2, 4, 8):
        prefixes, lens, scores = ctc_lib.ctc_beam_search(lp, beam_width=W)
        read = tuple(np.asarray(prefixes[0][: int(lens[0])]))
        assert float(scores[0]) <= exact[read] + 1e-5 <= best + 1e-5


def test_beam_search_batch_shapes():
    rng = np.random.default_rng(11)
    lp = jax.nn.log_softmax(
        jnp.asarray(rng.standard_normal((3, 7, 5)).astype(np.float32)), -1)
    prefixes, lens, scores = ctc_lib.ctc_beam_search_batch(lp, beam_width=4)
    assert prefixes.shape == (3, 4, 7)
    assert lens.shape == (3, 4)
    assert scores.shape == (3, 4)
    assert bool(jnp.all(scores[:, 0] >= scores[:, 1]))
