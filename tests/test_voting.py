"""Read-voting: longest-match alignment + consensus (paper Fig. 19)."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import telemetry
from repro.core import voting

jax.config.update("jax_platform_name", "cpu")

A, C, G, T = 0, 1, 2, 3


def _pad(read, L):
    return jnp.asarray(read + [-1] * (L - len(read)), jnp.int32)


def test_paper_fig19_example():
    """R1=ACTA, R2=CTAG, R3=GAGAT  ->  consensus ACTAGAT."""
    reads = jnp.stack([_pad([A, C, T, A], 5), _pad([C, T, A, G], 5),
                       _pad([G, A, G, A, T], 5)])
    lens = jnp.asarray([4, 4, 5], jnp.int32)
    cons, clen = voting.vote(reads, lens, span=12)
    got = list(np.asarray(cons[: int(clen)]))
    assert got == [A, C, T, A, G, A, T], got


def test_longest_common_substring_basic():
    r1, l1 = _pad([A, C, T, A], 6), 4
    r2, l2 = _pad([C, T, A, G], 6), 4
    m, s1, s2 = voting.longest_common_substring(r1, l1, r2, l2)
    assert int(m) == 3 and int(s1) == 1 and int(s2) == 0  # "CTA"


def test_lcs_no_match():
    m, s1, s2 = voting.longest_common_substring(
        _pad([A, A], 4), 2, _pad([G, G], 4), 2)
    assert int(m) == 0


def test_lcs_respects_lengths():
    # matching chars hidden beyond the true length must not count
    r1 = _pad([A, C], 5).at[2].set(G)   # junk past len
    r2 = _pad([G, G], 5)
    m, _, _ = voting.longest_common_substring(r1, 2, r2, 2)
    assert int(m) == 0


def test_vote_majority_fixes_random_error():
    """Random error in one read is outvoted (paper Fig. 3 'random error')."""
    good = [A, C, G, T, A, C]
    bad = [A, C, G, G, A, C]  # one substitution
    reads = jnp.stack([_pad(good, 8), _pad(bad, 8), _pad(good, 8)])
    lens = jnp.asarray([6, 6, 6], jnp.int32)
    cons, clen = voting.vote(reads, lens)
    assert list(np.asarray(cons[: int(clen)])) == good


def test_vote_systematic_error_survives():
    """If ALL reads carry the same wrong base, voting cannot fix it."""
    bad = [A, C, G, G, A, C]
    reads = jnp.stack([_pad(bad, 8)] * 3)
    lens = jnp.asarray([6, 6, 6], jnp.int32)
    cons, clen = voting.vote(reads, lens)
    assert list(np.asarray(cons[: int(clen)])) == bad


def test_vote_matches_reference_oracle():
    rng = np.random.default_rng(5)
    base = rng.integers(0, 4, size=20).tolist()
    # overlapping windows of the same sequence
    reads_list = [base[0:10], base[4:14], base[8:18]]
    L = 12
    reads = jnp.stack([_pad(r, L) for r in reads_list])
    lens = jnp.asarray([len(r) for r in reads_list], jnp.int32)
    cons, clen = voting.vote(reads, lens, span=40)
    want = voting.vote_reference(reads_list)
    assert list(np.asarray(cons[: int(clen)])) == want


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 4),
       overlap=st.integers(3, 6))
def test_vote_recovers_sequence_from_clean_overlapping_reads(seed, n, overlap):
    """Clean overlapping windows of a sequence vote back the sequence."""
    rng = np.random.default_rng(seed)
    win = overlap + 4
    step = win - overlap
    length = step * (n - 1) + win
    base = rng.integers(0, 4, size=length).tolist()
    # ensure unique overlaps are likely; skip degenerate repeats
    reads_list = [base[k * step: k * step + win] for k in range(n)]
    L = win
    reads = jnp.stack([_pad(r, L) for r in reads_list])
    lens = jnp.full((n,), win, jnp.int32)
    cons, clen = voting.vote(reads, lens, span=2 * length)
    got = list(np.asarray(cons[: int(clen)]))
    want = voting.vote_reference(reads_list)
    assert got == want  # jnp implementation == python oracle


def test_vote_batch_shape():
    reads = jnp.full((3, 4, 6), -1, jnp.int32).at[:, :, :3].set(1)
    lens = jnp.full((3, 4), 3, jnp.int32)
    cons, clen = voting.vote_batch(reads, lens, span=10)
    assert cons.shape == (3, 10) and clen.shape == (3,)


def test_encode_3bit_paper_codes():
    codes = np.asarray(voting.encode_3bit(jnp.asarray([0, 1, 2, 3, 4])))
    assert codes.tolist() == [[0, 0, 1], [0, 1, 0], [1, 0, 0], [0, 0, 0],
                              [1, 0, 1]]


# -- the serving path's host vote ---------------------------------------------

P = voting.PAIRS


def _overlapping(n, seed, width=12, hop=6, err=0.05):
    """``n`` overlapping windows of one random sequence, 5 % substituted."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, 4, size=hop * n + width)
    out = []
    for k in range(n):
        r = base[k * hop: k * hop + width].copy()
        hit = rng.random(width) < err
        r[hit] = rng.integers(0, 4, size=int(hit.sum()))
        out.append(r.tolist())
    return out


def _with_empty_windows():
    reads = _overlapping(9, seed=2)
    reads[0] = reads[4] = reads[5] = []          # first, and two in a row
    return reads


def _no_match():
    # window 2 shares no base with window 1: it is appended after it
    return [[A, C, A, C, C], [C, A, C, C, A, A], [G, T, G, G, T], [T, G, G]]


def _clamped():
    # window 1 matches window 0 four bases in, so its offset is -4 and
    # the chain clamps it to 0; windows 2-3 chain on from there
    return [[A, C, G, T], [T, T, G, T, A, C, G, T],
            [T, A, C, G, T, G, A], [G, T, G, A, C, C]]


VOTE_CASES = {**{f"n{n}": (lambda n=n: _overlapping(n, seed=n))
                 for n in (2, 3, P, P + 1, P + 2, 2 * P + 5)},
              "empty_windows": _with_empty_windows,
              "no_match": _no_match, "clamped": _clamped}


@pytest.mark.parametrize("case", sorted(VOTE_CASES))
def test_vote_host_is_bitwise_vote_and_reference(case):
    """The serving vote equals the jnp ``vote`` bit for bit and the Python
    oracle base for base, in ceil((N-1)/PAIRS) comparator launches."""
    reads_list = VOTE_CASES[case]()
    n, width = len(reads_list), 12
    reads = np.stack([np.asarray(_pad(r, width)) for r in reads_list])
    lens = np.asarray([len(r) for r in reads_list], np.int32)
    span = width * n
    rel = voting.relative_offsets(reads, lens)
    if case == "no_match":
        assert rel[1] == lens[1]
    if case == "clamped":
        assert rel[0] < 0 and np.cumsum(rel).min() < 0
    t0 = time.perf_counter()
    got, got_len = voting.vote_host(reads, lens, span=span)
    launches = telemetry.summary(t0)["vote.compare"]["count"]
    assert launches == -(-(n - 1) // P)
    want, want_len = voting.vote(jnp.asarray(reads), jnp.asarray(lens),
                                 span=span)
    assert got.shape == (span,) and got.dtype == np.int32
    assert got_len == int(want_len)
    np.testing.assert_array_equal(got, np.asarray(want))
    assert got[:got_len].tolist() == voting.vote_reference(reads_list)
