"""Connectionist Temporal Classification: loss, greedy decode, prefix beam search.

The paper's base-callers (Guppy/Scrappie/Chiron) emit per-frame log-probabilities
over [A, C, G, T, blank]; a CTC decoder maps frames to a read.  Helix's C3
restructures beam search into dense vector ops so it runs on the matrix engine —
here everything is expressed as fixed-shape jnp tensor ops under ``lax.scan`` so
XLA maps it onto the TPU VPU/MXU the same way.

Conventions
-----------
* alphabet indices ``0..A-2`` are symbols, ``blank`` defaults to the LAST index
  (the paper's [A,C,G,T,-] layout with A=5, blank=4).
* all decode outputs are fixed-shape, padded with ``-1`` beyond ``length``.
* ``NEG`` is used instead of ``-inf`` so logsumexp gradients stay NaN-free.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

NEG = -1.0e9  # "log zero" that keeps gradients finite


def _lse2(a, b):
    return jnp.logaddexp(a, b)


def _lse3(a, b, c):
    return jnp.logaddexp(jnp.logaddexp(a, b), c)


# ---------------------------------------------------------------------------
# CTC loss (log-domain forward algorithm)
# ---------------------------------------------------------------------------

def ctc_loss(
    log_probs: jnp.ndarray,
    labels: jnp.ndarray,
    label_length: jnp.ndarray | int | None = None,
    logit_length: jnp.ndarray | int | None = None,
    blank: int = -1,
) -> jnp.ndarray:
    """-ln p(labels | log_probs) for a single example.

    Args:
      log_probs: (T, A) per-frame log-probabilities (already log-softmaxed).
      labels: (L,) int32 label ids, padded arbitrarily beyond ``label_length``.
      label_length: true label length (<= L). Defaults to L.
      logit_length: true frame count (<= T). Defaults to T.
      blank: blank id; negative values index from the end (default: last).

    Returns: scalar loss = -log p(labels | inputs).
    """
    T, A = log_probs.shape
    L = labels.shape[0]
    if blank < 0:
        blank = A + blank
    label_length = jnp.asarray(L if label_length is None else label_length, jnp.int32)
    logit_length = jnp.asarray(T if logit_length is None else logit_length, jnp.int32)

    S = 2 * L + 1
    s_idx = jnp.arange(S)
    # extended label sequence: blank interleaved
    lab_safe = jnp.where(jnp.arange(L) < label_length, labels, 0)
    ext = jnp.where(s_idx % 2 == 0, blank, lab_safe[jnp.minimum((s_idx - 1) // 2, L - 1)])
    # skip transition s-2 -> s allowed for non-blank s whose label differs from s-2
    ext_m2 = jnp.concatenate([jnp.full((2,), -2, ext.dtype), ext[:-2]])
    allow_skip = (s_idx % 2 == 1) & (ext != ext_m2)

    lp0 = log_probs[0]
    alpha0 = jnp.full((S,), NEG)
    alpha0 = alpha0.at[0].set(lp0[blank])
    if L > 0:
        alpha0 = alpha0.at[1].set(jnp.where(label_length > 0, lp0[ext[1]], NEG))

    def step(alpha, lp):
        a1 = jnp.concatenate([jnp.array([NEG]), alpha[:-1]])
        a2 = jnp.concatenate([jnp.array([NEG, NEG]), alpha[:-2]])
        a2 = jnp.where(allow_skip, a2, NEG)
        new = lp[ext] + _lse3(alpha, a1, a2)
        return new, new

    _, alphas = jax.lax.scan(step, alpha0, log_probs[1:])
    alphas = jnp.concatenate([alpha0[None], alphas], axis=0)  # (T, S)
    alpha_final = alphas[jnp.maximum(logit_length - 1, 0)]

    s_end = 2 * label_length  # last blank
    ll_pos = alpha_final[jnp.minimum(s_end, S - 1)]
    ll_pre = jnp.where(label_length > 0,
                       alpha_final[jnp.clip(s_end - 1, 0, S - 1)], NEG)
    return -_lse2(ll_pos, ll_pre)


def ctc_loss_batch(log_probs, labels, label_lengths=None, logit_lengths=None,
                   blank: int = -1):
    """Batched CTC loss, per-example. Shapes: (B,T,A), (B,L), (B,), (B,)."""
    B, T, A = log_probs.shape
    L = labels.shape[1]
    if label_lengths is None:
        label_lengths = jnp.full((B,), L, jnp.int32)
    if logit_lengths is None:
        logit_lengths = jnp.full((B,), T, jnp.int32)
    f = jax.vmap(functools.partial(ctc_loss, blank=blank))
    return f(log_probs, labels, label_lengths, logit_lengths)


# ---------------------------------------------------------------------------
# Greedy (best-path) decode
# ---------------------------------------------------------------------------

def ctc_greedy_decode(log_probs: jnp.ndarray, blank: int = -1,
                      logit_length=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Best-path decode: argmax per frame, collapse repeats, drop blanks.

    Returns (read (T,), length). ``read`` padded with -1.
    """
    T, A = log_probs.shape
    if blank < 0:
        blank = A + blank
    if logit_length is None:
        logit_length = T
    logit_length = jnp.asarray(logit_length, jnp.int32)

    path = jnp.argmax(log_probs, axis=-1)  # (T,)
    prev = jnp.concatenate([jnp.array([-1], path.dtype), path[:-1]])
    valid_t = jnp.arange(T) < logit_length
    keep = (path != blank) & (path != prev) & valid_t
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1  # write index per kept frame
    out = jnp.full((T,), -1, jnp.int32)
    out = out.at[jnp.where(keep, pos, T)].set(path.astype(jnp.int32), mode="drop")
    return out, keep.sum().astype(jnp.int32)


# ---------------------------------------------------------------------------
# CTC prefix beam search (fixed-shape, vectorized; paper Fig. 4d / §4.3)
# ---------------------------------------------------------------------------

def ctc_beam_search(
    log_probs: jnp.ndarray,
    beam_width: int = 10,
    blank: int = -1,
    max_len: int | None = None,
    logit_length=None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Prefix beam search over (T, A) log-probs.

    Maintains per-beam (prefix, p_blank, p_nonblank) and at every frame expands
    each of the W beams with {stay} ∪ {append c : c != blank} — a dense
    (W × A) candidate tensor (the paper computes exactly this product on its
    dot-product array, merging equal prefixes on the bit-lines; we merge with a
    masked logsumexp over an equality matrix).

    Returns (prefixes (W, max_len) padded -1, lengths (W,), scores (W,)),
    sorted by score descending. scores = log p(prefix).
    """
    T, A = log_probs.shape
    if blank < 0:
        blank = A + blank
    if max_len is None:
        max_len = T
    if logit_length is None:
        logit_length = T
    logit_length = jnp.asarray(logit_length, jnp.int32)
    W = beam_width
    nsym = A - 1  # non-blank symbols; ids: all indices != blank
    sym_ids = jnp.array([c for c in range(A) if c != blank], jnp.int32)  # (nsym,)

    # beam state
    prefixes = jnp.full((W, max_len), -1, jnp.int32)
    lengths = jnp.zeros((W,), jnp.int32)
    p_b = jnp.full((W,), NEG).at[0].set(0.0)   # log p(prefix ends in blank)
    p_nb = jnp.full((W,), NEG)                 # log p(prefix ends in non-blank)

    C = W * (1 + nsym)  # candidates per step

    def step(state, inp):
        prefixes, lengths, p_b, p_nb = state
        lp, t = inp
        active = t < logit_length

        last = jnp.where(lengths > 0,
                         prefixes[jnp.arange(W), jnp.maximum(lengths - 1, 0)], -1)
        tot = _lse2(p_b, p_nb)

        # --- stay candidates (prefix unchanged) ------------------------------
        stay_pb = tot + lp[blank]
        stay_pnb = jnp.where(lengths > 0, p_nb + lp[jnp.maximum(last, 0)], NEG)

        # --- extend candidates (append symbol c) -----------------------------
        # (W, nsym): repeat-char extensions may only come through a blank
        lp_sym = lp[sym_ids]                                   # (nsym,)
        is_rep = last[:, None] == sym_ids[None, :]             # (W, nsym)
        ext_pnb = jnp.where(is_rep, p_b[:, None], tot[:, None]) + lp_sym[None, :]
        ext_pb = jnp.full((W, nsym), NEG)
        can_grow = lengths < max_len
        ext_pnb = jnp.where(can_grow[:, None], ext_pnb, NEG)

        # extended prefixes: append c at position `length`
        ext_prefix = jnp.broadcast_to(prefixes[:, None, :], (W, nsym, max_len))
        widx = jnp.minimum(lengths, max_len - 1)
        ext_prefix = ext_prefix.at[jnp.arange(W)[:, None],
                                   jnp.arange(nsym)[None, :],
                                   widx[:, None]].set(
            jnp.broadcast_to(sym_ids[None, :], (W, nsym)))
        ext_len = jnp.minimum(lengths + 1, max_len)

        # --- assemble candidate tensors --------------------------------------
        cand_prefix = jnp.concatenate(
            [prefixes, ext_prefix.reshape(W * nsym, max_len)], axis=0)  # (C, L)
        cand_len = jnp.concatenate([lengths, jnp.repeat(ext_len, nsym)], axis=0)
        cand_pb = jnp.concatenate([stay_pb, ext_pb.reshape(-1)], axis=0)
        cand_pnb = jnp.concatenate([stay_pnb, ext_pnb.reshape(-1)], axis=0)

        # --- merge identical prefixes (masked logsumexp) ----------------------
        eq = (cand_len[:, None] == cand_len[None, :]) & jnp.all(
            cand_prefix[:, None, :] == cand_prefix[None, :, :], axis=-1)  # (C, C)
        canon = ~jnp.any(eq & (jnp.arange(C)[None, :] < jnp.arange(C)[:, None]),
                         axis=1)  # first occurrence wins
        mrg_pb = jax.nn.logsumexp(jnp.where(eq, cand_pb[None, :], NEG), axis=1)
        mrg_pnb = jax.nn.logsumexp(jnp.where(eq, cand_pnb[None, :], NEG), axis=1)
        mrg_pb = jnp.where(canon, mrg_pb, NEG)
        mrg_pnb = jnp.where(canon, mrg_pnb, NEG)

        # --- select top-W -----------------------------------------------------
        score = _lse2(mrg_pb, mrg_pnb)
        _, top = jax.lax.top_k(score, W)
        new_state = (cand_prefix[top], cand_len[top], mrg_pb[top], mrg_pnb[top])
        # frames past logit_length are no-ops
        new_state = jax.tree_util.tree_map(
            lambda n, o: jnp.where(active, n, o), new_state, state)
        return new_state, None

    ts = jnp.arange(T)
    (prefixes, lengths, p_b, p_nb), _ = jax.lax.scan(
        step, (prefixes, lengths, p_b, p_nb), (log_probs, ts))

    score = _lse2(p_b, p_nb)
    order = jnp.argsort(-score)
    return prefixes[order], lengths[order], score[order]


def ctc_beam_search_batch(log_probs, beam_width=10, blank=-1, max_len=None,
                          logit_lengths=None):
    B, T, A = log_probs.shape
    if logit_lengths is None:
        logit_lengths = jnp.full((B,), T, jnp.int32)

    def one(lp, ll):
        return ctc_beam_search(lp, beam_width=beam_width, blank=blank,
                               max_len=max_len, logit_length=ll)

    return jax.vmap(one)(log_probs, logit_lengths)


# ---------------------------------------------------------------------------
# hash-merge CTC prefix beam search (the serving decoder)
# ---------------------------------------------------------------------------
#
# The dense decoder above materializes an O(C^2 * L) prefix-equality tensor
# per frame (C = W * A candidates) — beam width and read length blow up
# quadratically, and only the logsumexp tail is accelerated.  The serving
# decoder instead identifies every candidate by a 32-bit ROLLING PREFIX
# HASH:
#
#     h(empty) = 0;   h(prefix + c) = h(prefix) * M + (c + 1)   (mod 2^32)
#
# with M odd, so duplicate detection is single-word integer compares and
# the whole per-frame beam update — merge duplicate candidates, pool their
# log-mass, pick the top W — is ONE fused ``beam_merge_topk`` op from
# ``repro.kernels.registry`` (ref / interpret / Pallas backends).
#
# Invariants the hash state maintains (see ARCHITECTURE.md):
#   * after every frame the W live beams carry distinct prefixes, so the
#     only duplicates among the W*(1+nsym) candidates are structural:
#     extend(beam_i, c) colliding with stay(beam_j) where P_j = P_i + c —
#     exactly what the key-equality merge pools;
#   * hash identity == prefix identity up to 32-bit collisions
#     (probability ~ C^2 * T / 2^33 per read — negligible, and the dense
#     decoder stays available as the exact oracle);
#   * dead lanes (score ~ NEG) may carry stale prefixes; their mass
#     underflows to zero in every merge, so they never influence a live
#     beam.

_HASH_MUL = jnp.uint32(2654435761)  # Knuth's multiplicative constant (odd)


def prefix_hash_extend(h: jnp.ndarray, sym: jnp.ndarray) -> jnp.ndarray:
    """Rolling prefix hash update: h' = h * M + (sym + 1) (mod 2^32)."""
    return h * _HASH_MUL + (sym.astype(jnp.uint32) + jnp.uint32(1))


def ctc_beam_search_hash_batch(log_probs, beam_width: int = 10,
                               blank: int = -1, max_len: int | None = None,
                               logit_lengths=None, backend=None,
                               strip_frames: int | None = None
                               ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                          jnp.ndarray]:
    """Batched hash-merge prefix beam search over (B, T, A) log-probs.

    Natively batched (no vmap): the whole pool advances one frame per
    fused merge/top-k call, which is what the serving engine batches over
    slots.  ``logit_lengths`` (B,) masks padded tail frames per example —
    frames at/after an example's length leave its beam state untouched.

    ``backend`` is a registry backend name or ``repro.kernels.registry
    .Backend`` ("auto"/"pallas"/"interpret"/"ref") for the fused op.

    ``strip_frames`` > 1 switches the per-frame ``beam_merge_topk`` loop
    to the persistent ``beam_merge_multiframe`` kernel: beam state stays
    resident in VMEM across strips of that many frames (one launch per
    strip instead of one per frame), and prefixes are rebuilt from the
    kernel's per-frame winner indices by an index-only replay scan.  The
    result is bitwise identical to the per-frame path (``None``/``1``),
    which remains the differential oracle.

    Returns (prefixes (B, W, max_len) padded -1, lengths (B, W),
    scores (B, W)), each example sorted by score descending.
    """
    from repro.kernels import registry as _registry

    B, T, A = log_probs.shape
    if blank < 0:
        blank = A + blank
    if max_len is None:
        max_len = T
    if logit_lengths is None:
        logit_lengths = jnp.full((B,), T, jnp.int32)
    logit_lengths = jnp.asarray(logit_lengths, jnp.int32)
    W = beam_width
    nsym = A - 1
    sym_ids = jnp.array([c for c in range(A) if c != blank], jnp.int32)
    L = max_len

    mode = backend.mode if isinstance(backend, _registry.Backend) else backend
    if strip_frames is not None and strip_frames > 1:
        return _hash_beam_strips(log_probs, logit_lengths, mode,
                                 W=W, blank=blank, L=L,
                                 F=int(strip_frames))
    merge_topk = _registry.get_op("beam_merge_topk", mode)

    prefixes = jnp.full((B, W, L), -1, jnp.int32)
    lengths = jnp.zeros((B, W), jnp.int32)
    hashes = jnp.zeros((B, W), jnp.uint32)
    p_b = jnp.full((B, W), NEG).at[:, 0].set(0.0)
    p_nb = jnp.full((B, W), NEG)

    def step(state, inp):
        prefixes, lengths, hashes, p_b, p_nb = state
        lp, t = inp                                    # lp (B, A)
        active = t < logit_lengths                     # (B,)

        last = jnp.where(
            lengths > 0,
            jnp.take_along_axis(
                prefixes, jnp.maximum(lengths - 1, 0)[:, :, None],
                axis=2)[:, :, 0],
            -1)                                        # (B, W)
        tot = _lse2(p_b, p_nb)

        # --- stay candidates (prefix unchanged) ------------------------------
        stay_pb = tot + lp[:, blank][:, None]
        stay_pnb = jnp.where(
            lengths > 0,
            p_nb + jnp.take_along_axis(lp, jnp.maximum(last, 0), axis=1),
            NEG)

        # --- extend candidates (append symbol c) -----------------------------
        lp_sym = lp[:, sym_ids]                        # (B, nsym)
        is_rep = last[:, :, None] == sym_ids[None, None, :]
        ext_pnb = (jnp.where(is_rep, p_b[:, :, None], tot[:, :, None])
                   + lp_sym[:, None, :])               # (B, W, nsym)
        can_grow = lengths < L
        ext_pnb = jnp.where(can_grow[:, :, None], ext_pnb, NEG)
        ext_hash = prefix_hash_extend(hashes[:, :, None],
                                      sym_ids[None, None, :])

        ext_prefix = jnp.broadcast_to(prefixes[:, :, None, :],
                                      (B, W, nsym, L))
        widx = jnp.minimum(lengths, L - 1)
        ext_prefix = ext_prefix.at[
            jnp.arange(B)[:, None, None],
            jnp.arange(W)[None, :, None],
            jnp.arange(nsym)[None, None, :],
            widx[:, :, None]].set(
            jnp.broadcast_to(sym_ids[None, None, :], (B, W, nsym)))
        ext_len = jnp.minimum(lengths + 1, L)

        # --- assemble candidates: stays first, then extends ------------------
        cand_prefix = jnp.concatenate(
            [prefixes, ext_prefix.reshape(B, W * nsym, L)], axis=1)
        cand_len = jnp.concatenate(
            [lengths, jnp.repeat(ext_len, nsym, axis=1)], axis=1)
        cand_hash = jnp.concatenate(
            [hashes, ext_hash.reshape(B, W * nsym)], axis=1)
        cand_pb = jnp.concatenate(
            [stay_pb, jnp.full((B, W * nsym), NEG)], axis=1)
        cand_pnb = jnp.concatenate(
            [stay_pnb, ext_pnb.reshape(B, W * nsym)], axis=1)

        # --- fused hash merge + top-W ----------------------------------------
        idx, mpb, mpnb = merge_topk(cand_hash, cand_pb, cand_pnb, W=W)

        new_state = (
            jnp.take_along_axis(cand_prefix, idx[:, :, None], axis=1),
            jnp.take_along_axis(cand_len, idx, axis=1),
            jnp.take_along_axis(cand_hash, idx, axis=1),
            mpb, mpnb)
        new_state = jax.tree_util.tree_map(
            lambda n, o: jnp.where(
                active.reshape((B,) + (1,) * (n.ndim - 1)), n, o),
            new_state, state)
        return new_state, None

    lps = jnp.swapaxes(log_probs, 0, 1)                # (T, B, A)
    ts = jnp.arange(T)
    (prefixes, lengths, hashes, p_b, p_nb), _ = jax.lax.scan(
        step, (prefixes, lengths, hashes, p_b, p_nb), (lps, ts))

    score = _lse2(p_b, p_nb)
    order = jnp.argsort(-score, axis=1)
    return (jnp.take_along_axis(prefixes, order[:, :, None], axis=1),
            jnp.take_along_axis(lengths, order, axis=1),
            jnp.take_along_axis(score, order, axis=1))


def _hash_beam_strips(log_probs, logit_lengths, mode, *, W: int, blank: int,
                      L: int, F: int):
    """Strip-mode body of ``ctc_beam_search_hash_batch``.

    One ``beam_merge_multiframe`` launch advances the narrow beam state
    (hashes / log-masses / last symbol / lengths) through F frames with
    the state resident in VMEM; prefix CONTENT — too wide to keep
    resident — is rebuilt afterwards by replaying the per-frame winner
    indices, an index-only gather/scatter scan with no float math, so the
    final (prefixes, lengths, scores) are bitwise the per-frame path's.

    The frame axis is zero-padded up to a multiple of F; padded frames
    are inactive for every example (``active`` masks on the TRUE lengths)
    and the kernel emits identity indices for them, which makes the
    replay a natural no-op there too.
    """
    from repro.kernels import registry as _registry

    B, T, A = log_probs.shape
    nsym = A - 1
    sym_ids = jnp.array([c for c in range(A) if c != blank], jnp.int32)
    strip_op = _registry.get_op("beam_merge_multiframe", mode)

    S = -(-T // F)
    Tp = S * F
    lps = jnp.pad(log_probs.astype(jnp.float32),
                  ((0, 0), (0, Tp - T), (0, 0)))
    active = (jnp.arange(Tp)[None, :]
              < logit_lengths[:, None]).astype(jnp.int32)     # (B, Tp)

    prefixes = jnp.full((B, W, L), -1, jnp.int32)
    lengths = jnp.zeros((B, W), jnp.int32)
    keys = jnp.zeros((B, W), jnp.int32)   # uint32 hash bit patterns
    last = jnp.full((B, W), -1, jnp.int32)
    p_b = jnp.full((B, W), NEG).at[:, 0].set(0.0)
    p_nb = jnp.full((B, W), NEG)

    bi = jnp.arange(B)[:, None]
    wi = jnp.arange(W)[None, :]

    def replay(st, idx_f):
        """One frame of prefix reconstruction from winner indices.

        idx < W is a stay of beam ``idx``; idx >= W is beam
        ``(idx-W)//nsym`` extended by symbol ``sym_ids[(idx-W)%nsym]`` —
        the per-frame decoder's candidate layout.
        """
        prefixes, lengths = st
        is_ext = idx_f >= W                                   # (B, W)
        src = jnp.where(is_ext, (idx_f - W) // nsym, idx_f)
        sym = jnp.take(sym_ids, jnp.where(is_ext, (idx_f - W) % nsym, 0))
        prev_prefix = jnp.take_along_axis(prefixes, src[:, :, None], axis=1)
        prev_len = jnp.take_along_axis(lengths, src, axis=1)
        widx = jnp.minimum(prev_len, L - 1)
        cur = prev_prefix[bi, wi, widx]
        newp = prev_prefix.at[bi, wi, widx].set(
            jnp.where(is_ext, sym, cur))
        newl = jnp.where(is_ext, jnp.minimum(prev_len + 1, L), prev_len)
        return (newp, newl), None

    def strip_step(state, inp):
        prefixes, lengths, keys, last, p_b, p_nb = state
        lp_strip, act_strip = inp                 # (B, F, A), (B, F)
        idx, keys, p_b, p_nb, last, _lens = strip_op(
            lp_strip, act_strip, keys, p_b, p_nb, last, lengths,
            blank=blank, L=L)
        # lengths from the replay are provably the kernel's ``_lens``
        with jax.named_scope("ctc_replay"):
            (prefixes, lengths), _ = jax.lax.scan(
                replay, (prefixes, lengths), jnp.moveaxis(idx, 1, 0))
        return (prefixes, lengths, keys, last, p_b, p_nb), None

    xs = (jnp.moveaxis(lps.reshape(B, S, F, A), 1, 0),
          jnp.moveaxis(active.reshape(B, S, F), 1, 0))
    (prefixes, lengths, keys, last, p_b, p_nb), _ = jax.lax.scan(
        strip_step, (prefixes, lengths, keys, last, p_b, p_nb), xs)

    score = _lse2(p_b, p_nb)
    order = jnp.argsort(-score, axis=1)
    return (jnp.take_along_axis(prefixes, order[:, :, None], axis=1),
            jnp.take_along_axis(lengths, order, axis=1),
            jnp.take_along_axis(score, order, axis=1))


def ctc_beam_search_hash(log_probs, beam_width: int = 10, blank: int = -1,
                         max_len: int | None = None, logit_length=None,
                         backend=None, strip_frames: int | None = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Hash-merge beam search over a single (T, A) example.

    Same contract as ``ctc_beam_search`` (the dense-merge oracle), decoded
    on the fused ``beam_merge_topk`` registry op (or the persistent
    ``beam_merge_multiframe`` strips when ``strip_frames`` > 1).
    """
    ll = None if logit_length is None else jnp.asarray(
        logit_length, jnp.int32).reshape(1)
    prefixes, lengths, scores = ctc_beam_search_hash_batch(
        log_probs[None], beam_width=beam_width, blank=blank,
        max_len=max_len, logit_lengths=ll, backend=backend,
        strip_frames=strip_frames)
    return prefixes[0], lengths[0], scores[0]
