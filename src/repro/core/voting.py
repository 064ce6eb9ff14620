"""Read voting: longest-match alignment + per-position majority consensus.

Paper §4.3 / Fig. 19: a vote (a) finds the longest match between consecutive
reads, (b) aligns them by that match, and (c) takes a per-position majority.
Helix runs step (a) on a SOT-MRAM binary-comparator array — every substring of
R1 is stored in a row and compared against a substring of R2 in one shot, a
mismatch current on the source line marking inequality.  The TPU-native
rendition is a dense equality matrix ``eq[i,j] = (r1[i] == r2[j])`` reduced
along diagonals (``kernels/vote_cmp`` provides the Pallas tile kernel; this
module is the algorithmic layer and pure-jnp fallback).

All jnp functions are fixed-shape and jit/vmap-safe; reads are int arrays
padded with -1 past their length.  DNA symbols use the paper's 3-bit encoding
ids [A,C,G,T,-] = [0,1,2,3,4] (see ``encode_3bit``).

``vote_host`` is the serving path's vote: :func:`vote` bit for bit, with the
pairwise comparisons in one fixed-shape compiled launch and the offset chain
and majority grid in exact integer numpy, so a read's vote compiles nothing.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry

# paper Fig. 19(c): A:001 C:010 T:000 G:100 -:101
SYM2BITS = jnp.array([
    [0, 0, 1],  # A
    [0, 1, 0],  # C
    [1, 0, 0],  # G
    [0, 0, 0],  # T
    [1, 0, 1],  # - (blank / gap)
], jnp.int32)


def encode_3bit(read: jnp.ndarray) -> jnp.ndarray:
    """(L,) symbol ids -> (L, 3) bit planes (paper's comparator encoding)."""
    safe = jnp.clip(read, 0, SYM2BITS.shape[0] - 1)
    return SYM2BITS[safe]


def equality_matrix(r1: jnp.ndarray, l1, r2: jnp.ndarray, l2) -> jnp.ndarray:
    """eq[i,j] = 1 if r1[i] == r2[j] and both positions are valid."""
    v1 = jnp.arange(r1.shape[0]) < l1
    v2 = jnp.arange(r2.shape[0]) < l2
    eq = (r1[:, None] == r2[None, :]) & v1[:, None] & v2[None, :]
    return eq


def longest_common_substring(r1: jnp.ndarray, l1, r2: jnp.ndarray, l2
                             ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Longest common substring via the run-length DP on the equality matrix.

    M[i,j] = eq[i,j] * (M[i-1,j-1] + 1);  the maximum entry is the match length
    and its position gives the end indices in both reads.

    Returns (length, start1, start2) — all int32 scalars. length==0 when no
    character matches.
    """
    eq = equality_matrix(r1, l1, r2, l2).astype(jnp.int32)
    L1, L2 = eq.shape

    def row(prev, eq_row):
        shifted = jnp.concatenate([jnp.zeros((1,), jnp.int32), prev[:-1]])
        cur = eq_row * (shifted + 1)
        return cur, cur

    _, M = jax.lax.scan(row, jnp.zeros((L2,), jnp.int32), eq)
    flat = jnp.argmax(M.reshape(-1))
    best = M.reshape(-1)[flat]
    i_end, j_end = flat // L2, flat % L2
    start1 = i_end - best + 1
    start2 = j_end - best + 1
    return best, jnp.where(best > 0, start1, 0), jnp.where(best > 0, start2, 0)


def pairwise_offset(r1, l1, r2, l2) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Offset of r2 relative to r1 implied by their longest match.

    If r1[s1:s1+m] == r2[s2:s2+m], aligning those means r2 starts at
    ``s1 - s2`` in r1's coordinate frame.  Returns (offset, match_len).
    When no match exists, r2 is appended after r1 (offset = l1).
    """
    m, s1, s2 = longest_common_substring(r1, l1, r2, l2)
    off = jnp.where(m > 0, s1 - s2, l1)
    return off.astype(jnp.int32), m


def align_offsets(reads: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """Chain pairwise longest-match offsets into global read offsets (R,).

    Reads are in sequencing order (consecutive reads overlap — paper: "the
    order of these reads is already known"), so read k is aligned against
    read k-1 and offsets accumulate.
    """
    def align_next(carry, read_len):
        prev_read, prev_len, prev_off = carry
        read, length = read_len
        rel, _ = pairwise_offset(prev_read, prev_len, read, length)
        off = jnp.maximum(prev_off + rel, 0)  # clamp per step, then chain
        return (read, length, off), off

    (_, _, _), offs = jax.lax.scan(
        align_next, (reads[0], lengths[0], jnp.zeros((), jnp.int32)),
        (reads[1:], lengths[1:]))
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), offs])  # (R,)


def consensus_grid(reads: jnp.ndarray, lengths: jnp.ndarray,
                   offsets: jnp.ndarray, n_symbols: int = 4,
                   span: int | None = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Majority vote on the aligned coordinate grid.

    Returns (consensus (span,), covered (span,) bool); uncovered positions
    hold -1.
    """
    R, L = reads.shape
    if span is None:
        span = 2 * L
    pos = offsets[:, None] + jnp.arange(L)[None, :]          # (R, L)
    valid = (jnp.arange(L)[None, :] < lengths[:, None]) & (pos < span)
    sym = jnp.clip(reads, 0, n_symbols - 1)
    counts = jnp.zeros((span, n_symbols), jnp.int32)
    counts = counts.at[jnp.where(valid, pos, span),
                       jnp.where(valid, sym, 0)].add(1, mode="drop")
    covered = counts.sum(axis=1) > 0
    consensus = jnp.where(covered,
                          jnp.argmax(counts, axis=1).astype(jnp.int32), -1)
    return consensus, covered


def vote(reads: jnp.ndarray, lengths: jnp.ndarray, n_symbols: int = 4,
         span: int | None = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Align consecutive reads by longest match and majority-vote a consensus.

    Args:
      reads: (R, L) int32, padded with -1.
      lengths: (R,) int32 true lengths.
      n_symbols: vote alphabet (4 DNA bases).
      span: length of the consensus coordinate grid (default 2*L).

    Returns (consensus (span,) padded -1, consensus_length).
    """
    R, L = reads.shape
    if span is None:
        span = 2 * L
    offsets = align_offsets(reads, lengths)
    consensus, covered = consensus_grid(reads, lengths, offsets, n_symbols, span)
    # compact: drop any interior uncovered holes (rare: disjoint reads)
    keep = covered
    wpos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    out = jnp.full((span,), -1, jnp.int32)
    out = out.at[jnp.where(keep, wpos, span)].set(
        jnp.where(covered, consensus, 0), mode="drop")
    return out, keep.sum().astype(jnp.int32)


def vote_batch(reads, lengths, n_symbols: int = 4, span: int | None = None):
    """(B, R, L) -> (B, span) consensus. vmap of :func:`vote`."""
    f = functools.partial(vote, n_symbols=n_symbols, span=span)
    return jax.vmap(f)(reads, lengths)


# ---------------------------------------------------------------------------
# serving path: one fixed-shape comparator + host chaining and grid
# ---------------------------------------------------------------------------

#: window pairs per comparator launch: a read of up to ``PAIRS + 1`` windows
#: votes in one launch, a longer one in ``ceil((N - 1) / PAIRS)``
PAIRS = 256


@functools.cache
def _comparator(max_read_len: int):
    """``pairwise_offset`` of ``(PAIRS, max_read_len)`` window pairs, jitted
    once per read width: the one compiled part of :func:`vote_host`."""
    def vote_compare(r1, l1, r2, l2):
        return pairwise_offset(r1, l1, r2, l2)[0]
    return jax.jit(jax.vmap(vote_compare))


def warm_comparator(max_read_len: int) -> None:
    """Compile :func:`vote_host`'s comparator for one read width now (an
    engine calls this at build, so no served read's vote compiles)."""
    reads = np.full((PAIRS, max_read_len), -1, np.int32)
    lens = np.zeros((PAIRS,), np.int32)
    jax.block_until_ready(
        _comparator(max_read_len)(reads, lens, reads, lens))


def relative_offsets(reads: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """(N-1,) offset of window k in window k-1's frame, k = 1..N-1.

    A pair's offset depends only on the pair, so every pair of a read goes
    through the comparator at once, ``PAIRS`` a launch; the last launch is
    padded with length-0 windows whose results are dropped."""
    N, L = reads.shape
    n_pairs = N - 1
    n_launch = -(-n_pairs // PAIRS)
    rows = np.full((n_launch * PAIRS + 1, L), -1, np.int32)
    lens = np.zeros((n_launch * PAIRS + 1,), np.int32)
    rows[:N], lens[:N] = reads, lengths
    cmp = _comparator(L)
    rel = np.empty((n_launch * PAIRS,), np.int64)
    for i in range(n_launch):
        a, b = i * PAIRS, (i + 1) * PAIRS
        with telemetry.span("vote.compare"):
            rel[a:b] = np.asarray(cmp(rows[a:b], lens[a:b],
                                      rows[a + 1:b + 1], lens[a + 1:b + 1]))
    return rel[:n_pairs]


def chain_offsets(rel: np.ndarray) -> np.ndarray:
    """:func:`align_offsets`' chain ``off_k = max(off_{k-1} + rel_k, 0)``,
    ``off_0 = 0``, in closed form: a Lindley recursion, so with
    ``S = [0, cumsum(rel)]`` it is ``S - running_min(S)``.  (N,) int32."""
    s = np.concatenate([np.zeros((1,), np.int64),
                        np.cumsum(rel, dtype=np.int64)])
    return (s - np.minimum.accumulate(s)).astype(np.int32)


def consensus_host(reads: np.ndarray, lengths: np.ndarray,
                   offsets: np.ndarray, span: int) -> Tuple[np.ndarray, int]:
    """:func:`consensus_grid` plus :func:`vote`'s compaction in numpy:
    (consensus (span,) padded -1, consensus length)."""
    n_symbols = 4
    col = np.arange(reads.shape[1])
    pos = offsets[:, None].astype(np.int64) + col
    valid = (col < lengths[:, None]) & (pos < span)
    sym = np.clip(reads, 0, n_symbols - 1)
    counts = np.bincount((pos * n_symbols + sym)[valid],
                         minlength=span * n_symbols
                         ).reshape(span, n_symbols)
    cons = counts[counts.any(axis=1)].argmax(axis=1)   # first maximum
    out = np.full((span,), -1, np.int32)
    out[: cons.size] = cons
    return out, int(cons.size)


def vote_host(reads, lengths, span: int | None = None
              ) -> Tuple[np.ndarray, int]:
    """:func:`vote` bit for bit, compiling nothing once
    :func:`warm_comparator` has run for the read width.

    Args:
      reads: (N, L) int, padded with -1.
      lengths: (N,) int true lengths.
      span: length of the consensus coordinate grid (default 2*L).

    Returns (consensus (span,) int32 padded -1, consensus_length).
    """
    reads = np.asarray(reads, np.int32)
    lengths = np.asarray(lengths, np.int32)
    if span is None:
        span = 2 * reads.shape[1]
    offsets = chain_offsets(relative_offsets(reads, lengths))
    return consensus_host(reads, lengths, offsets, span)


# ---------------------------------------------------------------------------
# host-side (numpy-flavoured) oracle for tests
# ---------------------------------------------------------------------------

def vote_reference(reads_list, n_symbols: int = 4):
    """Plain-Python consensus used as a test oracle. reads_list: list[list[int]]."""
    import numpy as np

    def lcs(a, b):
        best, s1, s2 = 0, 0, 0
        prev = [0] * (len(b) + 1)
        for i in range(1, len(a) + 1):
            cur = [0] * (len(b) + 1)
            for j in range(1, len(b) + 1):
                if a[i - 1] == b[j - 1]:
                    cur[j] = prev[j - 1] + 1
                    if cur[j] > best:
                        best, s1, s2 = cur[j], i - cur[j], j - cur[j]
            prev = cur
        return best, s1, s2

    offsets = [0]
    for k in range(1, len(reads_list)):
        m, s1, s2 = lcs(reads_list[k - 1], reads_list[k])
        rel = (s1 - s2) if m > 0 else len(reads_list[k - 1])
        offsets.append(max(offsets[-1] + rel, 0))
    span = max(off + len(r) for off, r in zip(offsets, reads_list))
    counts = np.zeros((span, n_symbols), np.int64)
    for off, r in zip(offsets, reads_list):
        for i, c in enumerate(r):
            if 0 <= c < n_symbols and off + i < span:
                counts[off + i, c] += 1
    out = [int(np.argmax(row)) for row in counts if row.sum() > 0]
    return out
