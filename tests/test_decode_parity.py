"""Differential harness: hash-merge beam decoder vs the dense-merge oracle.

``ctc_beam_search`` (dense O(C^2*L) prefix-equality merge) stays in the
tree as the semantic ground truth; the serving decoder
``ctc_beam_search_hash_batch`` must agree with it — top-1 prefixes
identical, scores within 1e-4 — on randomized inputs, across every
registered backend of the fused ``beam_merge_topk`` op, including
non-tile-aligned candidate counts (C = W * A is whatever the draw says,
never a lane multiple by construction).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ctc as ctc_lib
from repro.kernels import registry

jax.config.update("jax_platform_name", "cpu")

# "auto" resolves through the registry default (REPRO_DEFAULT_BACKEND in
# the CI backend matrix); ref/interpret pin the two CPU-testable paths
BACKENDS = ("auto", "ref", "interpret")


def _rand_logprobs(rng, T, A):
    x = rng.standard_normal((T, A)).astype(np.float32)
    return jax.nn.log_softmax(jnp.asarray(x), axis=-1)


def _top_prefix(prefixes, lengths):
    return tuple(np.asarray(prefixes[0][: int(lengths[0])]))


# ---------------------------------------------------------------------------
# randomized differential: hash == dense oracle
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), T=st.integers(2, 12),
       A=st.integers(2, 6), W=st.integers(1, 9))
def test_hash_decoder_matches_dense_oracle(seed, T, A, W):
    rng = np.random.default_rng(seed)
    lp = _rand_logprobs(rng, T, A)
    dp, dl, ds = ctc_lib.ctc_beam_search(lp, beam_width=W)
    want = _top_prefix(dp, dl)
    for backend in BACKENDS:
        hp, hl, hs = ctc_lib.ctc_beam_search_hash(lp, beam_width=W,
                                                  backend=backend)
        got = _top_prefix(hp, hl)
        assert got == want, f"[{backend}] {got} != {want}"
        np.testing.assert_allclose(float(hs[0]), float(ds[0]),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"backend={backend}")


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_hash_decoder_backend_parity_full_state(seed):
    """ref and interpret must agree on the ENTIRE beam state bit for bit
    (the fused kernel pads to the lane tile; padding must be inert)."""
    rng = np.random.default_rng(seed)
    lp = jax.nn.log_softmax(jnp.asarray(
        rng.standard_normal((3, 9, 5)).astype(np.float32)), -1)
    ll = jnp.asarray(rng.integers(1, 10, (3,)), jnp.int32)
    out = {}
    for backend in ("ref", "interpret"):
        out[backend] = ctc_lib.ctc_beam_search_hash_batch(
            lp, beam_width=6, logit_lengths=ll, backend=backend)
    for a, b in zip(out["ref"], out["interpret"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# logit_lengths semantics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_hash_decoder_masked_equals_sliced(backend):
    """Decoding T frames with logit_length=n == decoding the n-frame slice."""
    rng = np.random.default_rng(3)
    lp = _rand_logprobs(rng, 10, 5)
    for n in (1, 4, 7, 10):
        a = ctc_lib.ctc_beam_search_hash(lp, beam_width=4, logit_length=n,
                                         max_len=10, backend=backend)
        b = ctc_lib.ctc_beam_search_hash(lp[:n], beam_width=4, max_len=10,
                                         backend=backend)
        assert _top_prefix(a[0], a[1]) == _top_prefix(b[0], b[1]), n
        np.testing.assert_allclose(float(a[2][0]), float(b[2][0]),
                                   rtol=1e-5, atol=1e-5)


def test_hash_decoder_batch_matches_per_example():
    rng = np.random.default_rng(11)
    lp = jax.nn.log_softmax(jnp.asarray(
        rng.standard_normal((4, 8, 4)).astype(np.float32)), -1)
    ll = jnp.asarray([8, 2, 5, 8], jnp.int32)
    bp, bl, bs = ctc_lib.ctc_beam_search_hash_batch(
        lp, beam_width=5, logit_lengths=ll, backend="ref")
    for i in range(4):
        pp, pl, ps = ctc_lib.ctc_beam_search_hash(
            lp[i], beam_width=5, logit_length=ll[i], backend="ref")
        np.testing.assert_array_equal(np.asarray(bp[i]), np.asarray(pp))
        np.testing.assert_array_equal(np.asarray(bl[i]), np.asarray(pl))
        np.testing.assert_allclose(np.asarray(bs[i]), np.asarray(ps),
                                   rtol=1e-6, atol=1e-6)


def test_hash_decoder_zero_length_is_empty():
    rng = np.random.default_rng(0)
    lp = _rand_logprobs(rng, 6, 5)
    p, l, s = ctc_lib.ctc_beam_search_hash(lp, beam_width=3, logit_length=0,
                                           backend="ref")
    assert int(l[0]) == 0
    assert float(s[0]) == 0.0          # empty prefix, probability 1
    assert np.all(np.asarray(p) == -1)


# ---------------------------------------------------------------------------
# structure / edge cases
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_hash_decoder_monotone_in_width(seed):
    """At every beam width the best score never exceeds the exact
    probability of its read (the CTC forward algorithm) — hash merging
    pools mass only from real duplicates.  The same bound the dense
    oracle satisfies; the best score itself is not monotone in width."""
    rng = np.random.default_rng(seed)
    lp = _rand_logprobs(rng, 6, 4)
    for W in (1, 2, 4, 8):
        prefixes, lens, scores = ctc_lib.ctc_beam_search_hash(
            lp, beam_width=W, backend="ref")
        read = jnp.asarray(prefixes[0][: int(lens[0])], jnp.int32)
        exact = (float(jnp.sum(lp[:, 3])) if read.size == 0
                 else -float(ctc_lib.ctc_loss(lp, read)))
        assert float(scores[0]) <= exact + 1e-5


def test_hash_decoder_max_len_cap():
    """A small max_len caps prefixes without corrupting live beams (capped
    extension candidates are dead lanes; dense oracle agrees on top-1)."""
    rng = np.random.default_rng(5)
    lp = _rand_logprobs(rng, 9, 4)
    dp, dl, _ = ctc_lib.ctc_beam_search(lp, beam_width=6, max_len=2)
    hp, hl, _ = ctc_lib.ctc_beam_search_hash(lp, beam_width=6, max_len=2,
                                             backend="ref")
    assert int(hl[0]) <= 2
    assert _top_prefix(hp, hl) == _top_prefix(dp, dl)


def test_hash_decoder_paper_example():
    """Fig. 4d: merging puts "A" ahead of "--" at beam width 2."""
    p = jnp.asarray([[0.3, 0.15, 0.05, 0.0, 0.5],
                     [0.3, 0.2, 0.1, 0.0, 0.4]])
    lp = jnp.log(p + 1e-9)
    prefixes, lens, scores = ctc_lib.ctc_beam_search_hash(lp, beam_width=2,
                                                          backend="ref")
    assert _top_prefix(prefixes, lens) == (0,)
    np.testing.assert_allclose(float(jnp.exp(scores[0])), 0.36, atol=1e-3)


def test_hash_decoder_dispatches_through_registry():
    """set_default_backend must steer the decoder's "auto" path."""
    rng = np.random.default_rng(1)
    lp = _rand_logprobs(rng, 5, 4)
    prev = registry.get_default_backend()
    try:
        registry.set_default_backend("ref")
        a = ctc_lib.ctc_beam_search_hash(lp, beam_width=4)
        registry.set_default_backend("interpret")
        b = ctc_lib.ctc_beam_search_hash(lp, beam_width=4)
    finally:
        registry.set_default_backend(prev)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_allclose(np.asarray(a[2]), np.asarray(b[2]),
                               rtol=1e-6, atol=1e-6)
