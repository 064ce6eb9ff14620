"""CTC beam-merge public wrappers — dispatch via ``repro.kernels.registry``.

Two ops live here:

  masked_logsumexp  — the dense-equality merge (the PR-1 kernel; now the
                      oracle path's accelerated tail)
  beam_merge_topk   — the fused hash-merge + top-W selection that the
                      vectorized hash beam decoder (``core.ctc``) runs
                      every frame: candidate identity is an int32 rolling
                      prefix hash, so duplicate detection is single-word
                      compares instead of length-L prefix compares
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.ctc_merge.kernel import (beam_merge_topk_pallas,
                                            ctc_merge_pallas, row_tile)
from repro.kernels.ctc_merge.ref import beam_merge_topk_ref, ctc_merge_ref

NEG = -1.0e9


def _impl_pallas(eq, scores, *, bi: int = 128,
                 interpret: bool = False) -> jnp.ndarray:
    """Pad C to the tile size with inert (self-connected, NEG) lanes."""
    B, C, _ = eq.shape
    pad = (-C) % bi
    if pad:
        Cp = C + pad
        eye = jnp.eye(Cp, dtype=eq.dtype)
        eq_p = jnp.zeros((B, Cp, Cp), eq.dtype).at[:, :C, :C].set(eq)
        eq_p = jnp.maximum(eq_p, eye[None])
        s_p = jnp.full((B, Cp), NEG, scores.dtype).at[:, :C].set(scores)
    else:
        eq_p, s_p = eq, scores
    out = ctc_merge_pallas(eq_p.astype(jnp.int8), s_p.astype(jnp.float32),
                           bi=bi, interpret=interpret)
    return out[:, :C]


def _impl_ref(eq, scores, **_tiles) -> jnp.ndarray:
    return ctc_merge_ref(eq, scores.astype(jnp.float32))


def _example():
    """Ragged candidate count vs bi=128 (cf. tests/test_registry.py)."""
    B, C = 3, 45
    eq = jnp.maximum(jnp.zeros((B, C, C), jnp.int8),
                     jnp.eye(C, dtype=jnp.int8)[None])  # self-connected
    return ((eq, jnp.zeros((B, C), jnp.float32)), {})


registry.register_op("masked_logsumexp", ref=_impl_ref, pallas=_impl_pallas,
                     example=_example, batch_axes=((0, 0), 0))


@functools.partial(jax.jit, static_argnames=("bi", "backend"))
def _dispatch(eq, scores, *, bi, backend):
    return registry.get_op("masked_logsumexp", backend)(eq, scores, bi=bi)


def masked_logsumexp(eq: jnp.ndarray, scores: jnp.ndarray, *, bi: int = 128,
                     backend: str | None = None) -> jnp.ndarray:
    """Batched masked logsumexp: (B, C, C) mask x (B, C) scores -> (B, C).

    Rows must be self-connected (eq[b,i,i]=1) so no row is empty.
    Backend resolves before the jit boundary (see quant_matmul.ops)."""
    return _dispatch(eq, scores, bi=bi,
                     backend=registry.resolve_backend(backend))


# ---------------------------------------------------------------------------
# fused hash-merge + top-k
# ---------------------------------------------------------------------------

def _topk_impl_pallas(keys, pb, pnb, *, W: int, interpret: bool = False):
    """Pad the batch to the row tile, run the fused kernel, trim back.

    Padded rows are independent examples whose outputs are dropped; the
    candidate axis is never padded (each block spans all C lanes).
    """
    B, C = keys.shape
    keys = jax.lax.bitcast_convert_type(keys.astype(jnp.uint32), jnp.int32) \
        if keys.dtype == jnp.uint32 else keys.astype(jnp.int32)
    pad = (-B) % row_tile(B)
    rows = ((0, pad), (0, 0))
    idx, opb, opnb = beam_merge_topk_pallas(
        jnp.pad(keys, rows), jnp.pad(pb.astype(jnp.float32), rows),
        jnp.pad(pnb.astype(jnp.float32), rows), W=W, interpret=interpret)
    idx, opb, opnb = idx[:B], opb[:B], opnb[:B]
    if W > C:   # ranks >= C hold no candidate
        is_pad = jnp.arange(W) >= C
        idx = jnp.where(is_pad[None], C - 1, idx)
        opb = jnp.where(is_pad[None], NEG, opb)
        opnb = jnp.where(is_pad[None], NEG, opnb)
    return idx, opb, opnb


def _topk_impl_ref(keys, pb, pnb, *, W: int, **_tiles):
    if keys.dtype == jnp.uint32:
        keys = jax.lax.bitcast_convert_type(keys, jnp.int32)
    return beam_merge_topk_ref(keys.astype(jnp.int32),
                               pb.astype(jnp.float32),
                               pnb.astype(jnp.float32), W=W)


def _topk_example():
    """Ragged batch vs the 8-row tile, ragged candidate count."""
    B, C = 2, 45
    keys = jnp.arange(B * C, dtype=jnp.int32).reshape(B, C) % 12
    return ((keys, jnp.zeros((B, C), jnp.float32),
             jnp.zeros((B, C), jnp.float32)), {"W": 7})


registry.register_op("beam_merge_topk", ref=_topk_impl_ref,
                     pallas=_topk_impl_pallas, example=_topk_example,
                     batch_axes=((0, 0, 0), (0, 0, 0)))


@functools.partial(jax.jit, static_argnames=("W", "backend"))
def _topk_dispatch(keys, pb, pnb, *, W, backend):
    return registry.get_op("beam_merge_topk", backend)(keys, pb, pnb, W=W)


def beam_merge_topk(keys: jnp.ndarray, pb: jnp.ndarray, pnb: jnp.ndarray,
                    W: int, *, backend: str | None = None):
    """Merge duplicate beam candidates by integer key and keep the top W.

    (B, C) keys/pb/pnb -> (idx (B, W) int32, pb (B, W), pnb (B, W)):
    per-key pooled log-masses on the first (canonical) occurrence, ranked
    by total score descending with ties broken by lower index.  W > C pads
    with (C-1, NEG, NEG) lanes.  Backend resolves before the jit boundary
    (see quant_matmul.ops)."""
    return _topk_dispatch(keys, pb, pnb, W=W,
                          backend=registry.resolve_backend(backend))


__all__ = ["masked_logsumexp", "ctc_merge_ref", "beam_merge_topk",
           "beam_merge_topk_ref"]
