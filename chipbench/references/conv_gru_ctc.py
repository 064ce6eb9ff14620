"""Plain reference of the quantized Conv -> GRU -> FC -> CTC basecaller.

The family of ``guppy`` and ``scrappie`` (Helix, arXiv 2008.03107,
Table 3), written from its description in straightforward ``jax.numpy``
and numpy.  It imports nothing of the program and takes nothing the
program made: the benchmark hands it the float weights it drew from the
seed and the raw windows it cut from its own signal.

Numerics, as the configuration file states them (``quant``,
``precision``), with ``b = bits``, ``q = 2**(b-1) - 1``:

* ``fq(x, axes)``: scale ``s = max(max|x| over axes, 1e-8) / q``,
  ``round_half_even(x / s)`` clipped to ``[-q, q]``, times ``s``.
* conv: its input ``fq`` over the whole window (one scale per
  window), weights ``fq`` per output channel, "SAME" strided conv, bias,
  ReLU.
* each projection (a GRU layer's input projection, the FC head): input
  ``fq`` per row (per frame), weights ``fq`` per output column, bias.
* GRU (gates z, r, n; ``U`` is ``fq`` per column, the hidden state stays
  float32)::

      g = h @ U + x W + b;  z = sigmoid(g_z);  r = sigmoid(g_r)
      n = tanh((x W + b)_n + (r * h) @ U_n);  h' = z * h + (1 - z) * n

  Layers alternate direction (odd layers walk time backwards).
* log-softmax over [A, C, G, T, blank].
* decode: CTC prefix beam search, beam ``W``, reads of at most ``L``
  bases, frames at or past a window's valid length ignored; a read's
  score is ``log(p_blank + p_nonblank)`` of its prefix.
* vote: consecutive window reads are aligned by their longest common
  substring (first maximum in row-major order; no match appends), the
  offsets chained and clamped at 0, and each position takes the
  majority base (lowest id on ties); uncovered positions are dropped.

Products run at the configuration's ``precision``: ``conv`` for the
conv stack (``default``: one bfloat16 pass on a TPU, as the program's
XLA conv runs it) and ``matmul`` for the projections and the GRU's
recurrent products (``highest``: float32).  The control lowers one of
them or the bit width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1.0e9
BLANK = 4
N_SYM = 4


def out_frames(cfg: dict, samples):
    """Frames the conv stack emits for ``samples`` input samples."""
    t = np.asarray(samples)
    for c in cfg["conv"]:
        t = -(-t // c["stride"])
    return t


def _fq(x, bits, axes):
    q = (1 << (bits - 1)) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True), 1e-8) / q
    return jnp.clip(jnp.round(x / s), -q, q) * s


def _proj(x, w, b, bits, prec):
    """Row-quantized input times column-quantized weight, plus bias."""
    xq = _fq(x, bits, (x.ndim - 1,))
    wq = _fq(w, bits, (0,))
    return jnp.einsum("...f,fo->...o", xq, wq, precision=prec) + b


@functools.partial(jax.jit, static_argnames=("cfg_key", "bits", "prec",
                                             "conv_prec"))
def _forward(params, windows, cfg_key, bits, prec, conv_prec):
    cfg = dict(cfg_key)
    x = windows                                         # (B, T, C)
    for p, c in zip(params["conv"], cfg["conv"]):
        x = _fq(x, bits, (1, 2))
        w = _fq(p["w"], bits, (0, 1))
        x = jax.lax.conv_general_dilated(
            x, w, window_strides=(c[2],), padding="SAME",
            dimension_numbers=("NWC", "WIO", "NWC"), precision=conv_prec)
        x = jax.nn.relu(x + p["b"])
    H = cfg["rnn_hidden"]
    for i, p in enumerate(params["rnn"]):
        xp = _proj(x, p["w"], 0.0, bits, prec)          # (B, T, 3H)
        u = _fq(p["u"], bits, (0,))
        b = p["b"]
        reverse = cfg["rnn_direction"] == "alt" and i % 2 == 1

        def step(h, xp_t, u=u, b=b):
            g = jnp.dot(h, u, precision=prec) + xp_t + b
            z = jax.nn.sigmoid(g[:, :H])
            r = jax.nn.sigmoid(g[:, H:2 * H])
            n = jnp.tanh(xp_t[:, 2 * H:] + b[2 * H:]
                         + jnp.dot(r * h, u[:, 2 * H:], precision=prec))
            h = z * h + (1.0 - z) * n
            return h, h

        h0 = jnp.zeros((x.shape[0], H), jnp.float32)
        _, ys = jax.lax.scan(step, h0, jnp.swapaxes(xp, 0, 1),
                             reverse=reverse)
        x = jnp.swapaxes(ys, 0, 1)
    logits = _proj(x, params["fc"]["w"], params["fc"]["b"], bits, prec)
    return jax.nn.log_softmax(logits, axis=-1)


def _cfg_key(cfg: dict):
    conv = tuple((c["kernel"], c["channels"], c["stride"]) for c in cfg["conv"])
    return (("conv", conv), ("rnn_hidden", cfg["rnn_hidden"]),
            ("rnn_direction", cfg["rnn_direction"]))


def forward(params, windows, cfg: dict, bits: int, precision: dict):
    """(B, window, C) float32 windows -> (B, frames, 5) log-probs;
    ``precision`` maps ``conv`` and ``matmul`` to a JAX precision."""
    return _forward(params, jnp.asarray(windows, jnp.float32),
                    _cfg_key(cfg), int(bits), precision["matmul"],
                    precision["conv"])


@functools.partial(jax.jit, static_argnames=("W", "L"))
def _beam(lps, lens, W, L):
    """Dense-merge prefix beam search (no hashing), one window per row."""
    def one(lp, n):
        T = lp.shape[0]
        C = W * (1 + N_SYM)
        pre = jnp.full((W, L), -1, jnp.int32)
        ln = jnp.zeros((W,), jnp.int32)
        pb = jnp.full((W,), NEG).at[0].set(0.0)
        pnb = jnp.full((W,), NEG)
        syms = jnp.arange(N_SYM, dtype=jnp.int32)

        def step(st, inp):
            pre, ln, pb, pnb = st
            row, t = inp
            last = jnp.where(ln > 0, pre[jnp.arange(W), jnp.maximum(ln - 1, 0)],
                             -1)
            tot = jnp.logaddexp(pb, pnb)
            s_pb = tot + row[BLANK]
            s_pnb = jnp.where(ln > 0, pnb + row[jnp.maximum(last, 0)], NEG)
            rep = last[:, None] == syms[None, :]
            e_pnb = jnp.where(rep, pb[:, None], tot[:, None]) + row[:N_SYM]
            e_pnb = jnp.where((ln < L)[:, None], e_pnb, NEG)
            at = jnp.minimum(ln, L - 1)
            e_pre = jnp.broadcast_to(pre[:, None, :], (W, N_SYM, L))
            e_pre = e_pre.at[jnp.arange(W)[:, None], syms[None, :],
                             at[:, None]].set(
                jnp.broadcast_to(syms[None, :], (W, N_SYM)))
            c_pre = jnp.concatenate([pre, e_pre.reshape(-1, L)])
            c_len = jnp.concatenate(
                [ln, jnp.repeat(jnp.minimum(ln + 1, L), N_SYM)])
            c_pb = jnp.concatenate([s_pb, jnp.full((W * N_SYM,), NEG)])
            c_pnb = jnp.concatenate([s_pnb, e_pnb.reshape(-1)])
            same = ((c_len[:, None] == c_len[None, :])
                    & jnp.all(c_pre[:, None, :] == c_pre[None, :, :], -1))
            first = ~jnp.any(same & (jnp.arange(C)[None, :]
                                     < jnp.arange(C)[:, None]), axis=1)
            m_pb = jax.nn.logsumexp(jnp.where(same, c_pb[None, :], NEG), 1)
            m_pnb = jax.nn.logsumexp(jnp.where(same, c_pnb[None, :], NEG), 1)
            m_pb = jnp.where(first, m_pb, NEG)
            m_pnb = jnp.where(first, m_pnb, NEG)
            _, top = jax.lax.top_k(jnp.logaddexp(m_pb, m_pnb), W)
            new = (c_pre[top], c_len[top], m_pb[top], m_pnb[top])
            on = t < n
            return jax.tree_util.tree_map(
                lambda a, o: jnp.where(on, a, o), new, st), None

        (pre, ln, pb, pnb), _ = jax.lax.scan(
            step, (pre, ln, pb, pnb), (lp, jnp.arange(T)))
        score = jnp.logaddexp(pb, pnb)
        k = jnp.argmax(score)
        return pre[k], ln[k], score[k]

    return jax.vmap(one)(lps, lens)


def beam_search(lps, frames, W: int, L: int):
    """Top beam per window: (reads (B, L) padded -1, lengths, scores)."""
    return _beam(lps, jnp.asarray(frames, jnp.int32), W, L)


@jax.jit
def _ctc_loglik(lps, frames, labels, label_len):
    """log p(labels | lps) by the CTC forward algorithm, one row each."""
    def one(lp, n, lab, m):
        S = 2 * lab.shape[0] + 1
        ext = jnp.full((S,), BLANK, jnp.int32).at[1::2].set(
            jnp.maximum(lab, 0))
        skip = jnp.zeros((S,), bool).at[3::2].set(lab[1:] != lab[:-1])
        valid = jnp.arange(S) < 2 * m + 1
        a0 = jnp.full((S,), NEG).at[0].set(lp[0, BLANK])
        a0 = a0.at[1].set(jnp.where(m > 0, lp[0, ext[1]], NEG))

        def step(a, inp):
            row, t = inp
            a1 = jnp.concatenate([jnp.full((1,), NEG), a[:-1]])
            a2 = jnp.concatenate([jnp.full((2,), NEG), a[:-2]])
            a2 = jnp.where(skip, a2, NEG)
            new = jnp.logaddexp(jnp.logaddexp(a, a1), a2) + row[ext]
            new = jnp.where(valid, new, NEG)
            return jnp.where(t < n, new, a), None

        a, _ = jax.lax.scan(step, a0, (lp[1:], jnp.arange(1, lp.shape[0])))
        end = 2 * m
        tail = jnp.where(m > 0, a[jnp.maximum(end - 1, 0)], NEG)
        ll = jnp.logaddexp(a[end], tail)
        return jnp.where(n > 0, ll, jnp.where(m == 0, 0.0, NEG))

    return jax.vmap(one)(lps, frames, labels, label_len)


def ctc_loglik(lps, frames, reads, lengths):
    """log p(read | log-probs) per window (reads padded with -1)."""
    return _ctc_loglik(lps, jnp.asarray(frames, jnp.int32),
                       jnp.asarray(reads, jnp.int32),
                       jnp.asarray(lengths, jnp.int32))


# ---------------------------------------------------------------------------
# vote, in numpy
# ---------------------------------------------------------------------------

def _pair_offsets(reads: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """Relative offset of each read against the one before it, all pairs
    at once: the longest common substring, first maximum in row-major
    order, ``s1 - s2``; ``l1`` when nothing matches."""
    a, b = reads[:-1], reads[1:]
    la, lb = lengths[:-1], lengths[1:]
    P, L = a.shape
    va = np.arange(L)[None, :] < la[:, None]
    vb = np.arange(L)[None, :] < lb[:, None]
    best = np.zeros((P,), np.int64)
    at = np.zeros((P,), np.int64)         # flat index of the first maximum
    prev = np.zeros((P, L), np.int64)
    for i in range(L):
        eq = (a[:, i: i + 1] == b) & va[:, i: i + 1] & vb
        shifted = np.concatenate([np.zeros((P, 1), np.int64), prev[:, :-1]],
                                 axis=1)
        cur = np.where(eq, shifted + 1, 0)
        rmax = cur.max(axis=1)
        better = rmax > best
        at = np.where(better, i * L + cur.argmax(axis=1), at)
        best = np.maximum(best, rmax)
        prev = cur
    i_end, j_end = at // L, at % L
    rel = (i_end - best + 1) - (j_end - best + 1)
    return np.where(best > 0, rel, la)


def vote(reads: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """Consensus bases of one read's window reads, in window order."""
    reads = np.asarray(reads, np.int64)
    lengths = np.asarray(lengths, np.int64)
    R, L = reads.shape
    if R == 0:
        return np.zeros((0,), np.int64)
    if R == 1:
        return reads[0, : lengths[0]]
    rel = _pair_offsets(reads, lengths)
    offs = np.zeros((R,), np.int64)
    for k in range(1, R):
        offs[k] = max(offs[k - 1] + rel[k - 1], 0)
    span = L * R
    pos = offs[:, None] + np.arange(L)[None, :]
    ok = (np.arange(L)[None, :] < lengths[:, None]) & (pos < span)
    counts = np.zeros((span, N_SYM), np.int64)
    np.add.at(counts, (pos[ok], np.clip(reads, 0, N_SYM - 1)[ok]), 1)
    covered = counts.sum(axis=1) > 0
    return counts.argmax(axis=1)[covered]
