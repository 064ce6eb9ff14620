"""Pallas TPU kernel: CTC beam-merge (paper §4.3, Fig. 18).

Helix writes the beam's per-base probabilities onto the diagonal of an NVM
dot-product array and closes bit-line transistors to MERGE the probabilities
of candidate sequences that collapse to the same read
(p(A) = p(A₀A₁)+p(A₀-₁)+p(-₀A₁)+p(-₀-₁)).

The digital equivalent of "closing transistors between bit-lines" is a
masked reduction over an equality matrix: given candidate scores s (log
domain) and eq[i,j] = 1 iff candidates i and j collapse to the same prefix,

    merged[i] = log Σ_j eq[i,j] · exp(s[j])

computed per row with max-subtraction for stability.  The (C×C) masked
sum-product is the same crossbar-shaped operation, on the VPU.

Tiling: grid (B, C/bi); each step holds an (bi, C) eq tile and the full
(1, C) score row in VMEM — C is the candidate count (beam·alphabet, ≤ a few
hundred), so a full row fits comfortably.  Scores ride as (B, 1, C) and
the result as (B, C, 1), so every block's last two dims are either
(8, 128)-aligned or the array's own, as the TPU's tiling rule requires.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from repro.kernels.ctc_merge.ref import MASK  # oracle fill, bitwise-shared

NEG = -1.0e9


def _merge_kernel(eq_ref, s_ref, o_ref):
    # widen before comparing: an i1 mask derived from int8 tiles has a
    # layout Mosaic cannot broadcast against the f32 score row
    eq = eq_ref[0].astype(jnp.int32)     # (bi, C)
    s = s_ref[0]                         # (1, C) f32
    masked = jnp.where(eq > 0, s, NEG)   # broadcast row scores
    m = jnp.max(masked, axis=1, keepdims=True)
    ssum = jnp.sum(jnp.exp(masked - m), axis=1, keepdims=True)
    o_ref[0] = m + jnp.log(ssum)         # (bi, 1)


def ctc_merge_pallas(eq: jnp.ndarray, scores: jnp.ndarray,
                     *, bi: int = 128, interpret: bool = False
                     ) -> jnp.ndarray:
    """eq (B, C, C) int8, scores (B, C) f32 -> merged (B, C) f32."""
    B, C, C2 = eq.shape
    assert C == C2 and C % bi == 0

    grid = (B, C // bi)
    return pl.pallas_call(
        _merge_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bi, C), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, C), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bi, 1), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, C, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(eq, scores[:, None, :])[:, :, 0]


# ---------------------------------------------------------------------------
# fused hash-merge + top-k (the whole per-frame beam update in one kernel)
# ---------------------------------------------------------------------------

def row_tile(B: int) -> int:
    """Batch rows per block: a sublane multiple (8), at most 128."""
    return min(128, -(-B // 8) * 8)


def _select(hit, x):
    """Per row, the one value of ``x`` where ``hit`` holds, as (rows, 1).

    A masked lane reduction, not a slice: a lane-offset slice leaves a
    vector layout that Mosaic cannot broadcast back across lanes.  Every
    other lane contributes the reduction's identity (0 for integers, -inf
    for floats), so the result is the selected value bit for bit; a row
    with no hit reads that identity.
    """
    if jnp.issubdtype(x.dtype, jnp.integer):
        return jnp.sum(jnp.where(hit, x, 0), axis=1, keepdims=True)
    return jnp.max(jnp.where(hit, x, -jnp.inf), axis=1, keepdims=True)


def lane_col(x, j):
    """Column ``j`` (static or traced) of a (rows, n) tile as (rows, 1)."""
    return _select(jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) == j, x)


def merge_rank_select(keys, pb, pnb):
    """Fused beam update over a (rows, C) tile of candidates: merge
    duplicates by key, then rank every candidate by merged score.

    Shared in-kernel body of the per-frame ``beam_merge_topk`` kernel AND
    the persistent multi-frame ``beam_merge_multiframe`` kernel
    (kernels/beam_strip) — one implementation so the two stay bitwise
    interchangeable by construction.

    Each row is one example and its lanes are that example's candidates.
    The (C x C) equality plane of Helix's crossbar merge is walked one
    column j at a time (a ``fori_loop`` whose step compares the whole
    tile against candidate j), so every value stays a 2-D (rows, C) tile:

      canon[i] = no j < i has key[j] == key[i]      (first occurrence)
      m[i]     = max_j (key[j] == key[i] ? p[j] : MASK)
      mass[i]  = m[i] + log sum_j exp((key[j] == key[i] ? p[j] : MASK) - m[i])
      rank[i]  = #{j : score[j] > score[i] or (score[j] == score[i], j < i)}

    The sum runs over j in ascending order, as the oracle's row reduction
    does.  ``rank`` is a permutation of 0..C-1 (ties broken by index,
    matching ``lax.top_k``); ``take_ranked`` reads values out in rank
    order.  Duplicate (non-canonical) lanes are stripped of their pooled
    mass, matching the dense oracle.

    Args: (rows, C) tiles — int32 keys, f32 blank / non-blank log-masses.
    Returns (rank int32, merged_pb, merged_pnb), each (rows, C).
    """
    rows, C = keys.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, C), 1)
    zero_i = jnp.zeros((rows, C), jnp.int32)

    def eq_col(j):
        return keys == lane_col(keys, j)

    def dup_step(j, dup):
        return dup + jnp.where(eq_col(j) & (lane > j), 1, 0)

    canon = jax.lax.fori_loop(0, C, dup_step, zero_i) == 0

    def masked(j, e, vals):
        return jnp.where(e, lane_col(vals, j), MASK)

    def max_step(j, ms):
        e = eq_col(j)
        return (jnp.maximum(ms[0], masked(j, e, pb)),
                jnp.maximum(ms[1], masked(j, e, pnb)))

    floor = jnp.full((rows, C), MASK, jnp.float32)
    m_b, m_nb = jax.lax.fori_loop(0, C, max_step, (floor, floor))

    def sum_step(j, ss):
        e = eq_col(j)
        return (ss[0] + jnp.exp(masked(j, e, pb) - m_b),
                ss[1] + jnp.exp(masked(j, e, pnb) - m_nb))

    zero = jnp.zeros((rows, C), jnp.float32)
    s_b, s_nb = jax.lax.fori_loop(0, C, sum_step, (zero, zero))

    mpb = jnp.where(canon, m_b + jnp.log(s_b), NEG)
    mpnb = jnp.where(canon, m_nb + jnp.log(s_nb), NEG)
    score = jnp.where(canon, jnp.logaddexp(mpb, mpnb), NEG)

    def rank_step(j, rank):
        sj = lane_col(score, j)
        beats = (sj > score) | ((sj == score) & (lane > j))
        return rank + jnp.where(beats, 1, 0)

    rank = jax.lax.fori_loop(0, C, rank_step, zero_i)
    return rank, mpb, mpnb


def take_ranked(rank, vals, W: int):
    """(rows, C) values -> (rows, W) in rank order, exactly: out[:, r] is
    the value of the lane ranked r.  A rank no lane holds (r >= C) reads
    the reduction identity (0 / -inf); callers overwrite those lanes."""
    rows = vals.shape[0]
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (rows, W), 1)
    out = jnp.zeros((rows, W), vals.dtype)
    for r in range(W):
        out = jnp.where(out_lane == r, _select(rank == r, vals), out)
    return out


def _merge_topk_kernel(keys_ref, pb_ref, pnb_ref, idx_ref, opb_ref, opnb_ref):
    """A (bb, C) tile of examples through ``merge_rank_select``."""
    keys = keys_ref[...]
    W = idx_ref.shape[1]
    rank, mpb, mpnb = merge_rank_select(keys, pb_ref[...], pnb_ref[...])
    lane = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    idx_ref[...] = take_ranked(rank, lane, W)
    opb_ref[...] = take_ranked(rank, mpb, W)
    opnb_ref[...] = take_ranked(rank, mpnb, W)


def beam_merge_topk_pallas(keys: jnp.ndarray, pb: jnp.ndarray,
                           pnb: jnp.ndarray, *, W: int,
                           interpret: bool = False):
    """keys (B, C) int32, pb/pnb (B, C) f32, B a multiple of
    ``row_tile(B)`` -> (idx (B, W) int32, pb (B, W) f32, pnb (B, W) f32),
    the W best candidates in rank order."""
    B, C = keys.shape
    bb = row_tile(B)
    assert B % bb == 0, "pad the batch to the row tile before calling"
    in_spec = pl.BlockSpec((bb, C), lambda i: (i, 0))
    out_spec = pl.BlockSpec((bb, W), lambda i: (i, 0))
    return pl.pallas_call(
        _merge_topk_kernel,
        grid=(B // bb,),
        in_specs=[in_spec, in_spec, in_spec],
        out_specs=(out_spec, out_spec, out_spec),
        out_shape=(jax.ShapeDtypeStruct((B, W), jnp.int32),
                   jax.ShapeDtypeStruct((B, W), jnp.float32),
                   jax.ShapeDtypeStruct((B, W), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(keys, pb, pnb)
