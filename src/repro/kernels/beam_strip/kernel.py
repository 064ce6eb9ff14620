"""Pallas TPU kernel: persistent multi-frame CTC beam merge.

The serving decoder launches ``beam_merge_topk`` once per frame — beam
state (hashes, log-masses, last symbol, lengths) round-trips through HBM
between every launch.  This kernel is the decode-side analogue of the
persistent GRU walk (kernels/gru_seq): ONE ``pallas_call`` per strip of
F frames, grid (B/bb, F) with semantics ("parallel", "arbitrary"), where

  * a block is bb examples (a sublane multiple, see
    ``ctc_merge.kernel.row_tile``), one per row; lanes hold beams (W),
    symbols (A) or candidates (C = W * A),
  * the five beam-state arrays live in the OUTPUT refs, whose BlockSpec
    index maps ignore the frame coordinate — Pallas keeps those (bb, W)
    blocks resident in VMEM across the whole strip and writes them back
    once,
  * state is seeded from the input refs under ``@pl.when(f == 0)``,
  * only the (bb, A) log-prob tile streams in and the (bb, W)
    winner-index tile streams out per frame.  The wrapper lays the frame
    axis out first, (F, B, ·), so every block's last two dims are
    (bb, full width) — the TPU's (8, 128)-or-full tiling rule.

Per-frame math is the per-frame decoder's candidate assembly verbatim
(stays ``[0, W)``, extends ``W + w*nsym + j``), built lane by lane from
exact per-beam column reads, followed by the SHARED
``merge_rank_select`` body from kernels/ctc_merge — one merge
implementation, so per-frame and multi-frame stay bitwise
interchangeable by construction.

VMEM per grid step: a handful of (bb, C) tiles — with bb = 128 and
W = 5, A = 5 that is a few tens of KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.beam_strip.ref import _MUL_I32, NEG
from repro.kernels.ctc_merge.kernel import (lane_col, merge_rank_select,
                                            row_tile, take_ranked)


def _strip_kernel(lp_ref, act_ref, keys_in, pb_in, pnb_in, last_in, len_in,
                  idx_ref, keys_ref, pb_ref, pnb_ref, last_ref, len_ref,
                  *, blank: int, L: int, A: int, W: int):
    f = pl.program_id(1)

    @pl.when(f == 0)
    def _init():
        keys_ref[...] = keys_in[...]
        pb_ref[...] = pb_in[...]
        pnb_ref[...] = pnb_in[...]
        last_ref[...] = last_in[...]
        len_ref[...] = len_in[...]

    nsym = A - 1
    C = W * A                      # stays + extends

    lp = lp_ref[0]                 # (bb, A) — this frame's log-probs
    keys = keys_ref[...]           # (bb, W) int32 — persistent across f
    pb = pb_ref[...]
    pnb = pnb_ref[...]
    last = last_ref[...]
    lens = len_ref[...]
    tot = jnp.logaddexp(pb, pnb)   # (bb, W)
    rows = keys.shape[0]

    # --- lane layout of the candidate tile -------------------------------
    # lane w < W stays on beam w; lane W + w*nsym + j extends beam w by
    # sym_ids[j] = j + (j >= blank).  ``sym`` is -1 on stay lanes.
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, C), 1)
    sym = jnp.full((rows, C), -1, jnp.int32)
    for w in range(W):
        jv = lane - (W + w * nsym)
        sym = jnp.where((jv >= 0) & (jv < nsym),
                        jv + jnp.where(jv >= blank, 1, 0), sym)
    lp_cols = [lane_col(lp, a) for a in range(A)]     # (bb, 1) each
    lp_sym = jnp.zeros((rows, C), jnp.float32)
    for a in range(A):
        lp_sym = jnp.where(sym == a, lp_cols[a], lp_sym)

    cand_key = jnp.zeros((rows, C), jnp.int32)
    cand_pb = jnp.full((rows, C), NEG, jnp.float32)   # extends: NEG
    cand_pnb = jnp.zeros((rows, C), jnp.float32)
    cand_last = jnp.zeros((rows, C), jnp.int32)
    cand_len = jnp.zeros((rows, C), jnp.int32)
    for w in range(W):
        k_w, pb_w, pnb_w = (lane_col(x, w) for x in (keys, pb, pnb))
        tot_w, last_w, len_w = (lane_col(x, w) for x in (tot, last, lens))
        stay = lane == w
        ext = (lane >= W + w * nsym) & (lane < W + (w + 1) * nsym)
        # lp at this beam's last symbol (exact one-hot select)
        lp_last = lp_cols[0]
        for a in range(1, A):
            lp_last = jnp.where(jnp.maximum(last_w, 0) == a, lp_cols[a],
                                lp_last)
        # stay: prefix unchanged
        stay_pnb = jnp.where(len_w > 0, pnb_w + lp_last, NEG)
        # extend: append ``sym`` (wrapping i32 hash ≡ the u32 rolling hash)
        ext_pnb = jnp.where(last_w == sym, pb_w, tot_w) + lp_sym
        ext_pnb = jnp.where(len_w < L, ext_pnb, NEG)
        cand_key = jnp.where(stay, k_w, jnp.where(
            ext, k_w * _MUL_I32 + sym + 1, cand_key))
        cand_pb = jnp.where(stay, tot_w + lp_cols[blank], cand_pb)
        cand_pnb = jnp.where(stay, stay_pnb,
                             jnp.where(ext, ext_pnb, cand_pnb))
        cand_last = jnp.where(stay, last_w, jnp.where(ext, sym, cand_last))
        cand_len = jnp.where(stay, len_w, jnp.where(
            ext, jnp.minimum(len_w + 1, L), cand_len))

    # --- shared fused merge + rank, read out the W winners ---------------
    rank, mpb, mpnb = merge_rank_select(cand_key, cand_pb, cand_pnb)

    # padded frames are no-ops: identity idx, state untouched
    live = act_ref[0] > 0                              # (bb, 1)
    iw = jax.lax.broadcasted_iota(jnp.int32, (rows, W), 1)
    idx_ref[0] = jnp.where(live, take_ranked(rank, lane, W), iw)
    keys_ref[...] = jnp.where(live, take_ranked(rank, cand_key, W), keys)
    pb_ref[...] = jnp.where(live, take_ranked(rank, mpb, W), pb)
    pnb_ref[...] = jnp.where(live, take_ranked(rank, mpnb, W), pnb)
    last_ref[...] = jnp.where(live, take_ranked(rank, cand_last, W), last)
    len_ref[...] = jnp.where(live, take_ranked(rank, cand_len, W), lens)


def beam_merge_multiframe_pallas(lp, active, keys, pb, pnb, last, lengths,
                                 *, blank: int, L: int,
                                 interpret: bool = False):
    """lp (B, F, A) f32, active (B, F) i32, state (B, W) each ->
    (idx (B, F, W) i32, keys, pb, pnb, last, lengths) post-strip.

    The batch is padded to the row tile with inactive rows (state passes
    through untouched) and trimmed back."""
    B, F, A = lp.shape
    W = keys.shape[1]
    assert keys.dtype == jnp.int32
    bb = row_tile(B)
    pad = (-B) % bb
    Bp = B + pad
    rows = ((0, pad), (0, 0))
    lp_t = jnp.pad(jnp.swapaxes(lp, 0, 1), ((0, 0), (0, pad), (0, 0)))
    act_t = jnp.pad(jnp.swapaxes(active, 0, 1), ((0, 0), (0, pad)))[..., None]
    state = [jnp.pad(x, rows) for x in (keys, pb, pnb, last, lengths)]

    def frame_spec(n):
        return pl.BlockSpec((1, bb, n), lambda i, f: (f, i, 0))

    state_spec = pl.BlockSpec((bb, W), lambda i, f: (i, 0))
    kernel = functools.partial(_strip_kernel, blank=blank, L=L, A=A, W=W)
    idx, *state = pl.pallas_call(
        kernel,
        grid=(Bp // bb, F),
        in_specs=[frame_spec(A), frame_spec(1)] + [state_spec] * 5,
        out_specs=(frame_spec(W),) + (state_spec,) * 5,
        out_shape=(
            jax.ShapeDtypeStruct((F, Bp, W), jnp.int32),
            jax.ShapeDtypeStruct((Bp, W), jnp.int32),
            jax.ShapeDtypeStruct((Bp, W), jnp.float32),
            jax.ShapeDtypeStruct((Bp, W), jnp.float32),
            jax.ShapeDtypeStruct((Bp, W), jnp.int32),
            jax.ShapeDtypeStruct((Bp, W), jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lp_t, act_t, *state)
    return (jnp.swapaxes(idx, 0, 1)[:B],) + tuple(x[:B] for x in state)
