"""Persistent multi-frame beam merge — dispatch via the registry.

``beam_merge_multiframe`` advances the hash beam decoder's state through
a strip of F frames in one launch instead of F ``beam_merge_topk``
launches.  The state the op carries (hashes, log-masses, last symbol,
lengths) is everything EXCEPT prefix content — callers replay the
returned per-frame winner indices to rebuild prefixes (see
``core.ctc.ctc_beam_search_hash_batch``'s ``strip_frames`` path).

The kernel wrapper pads the batch to its row tile with inactive rows;
the candidate axis is never padded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.beam_strip.kernel import beam_merge_multiframe_pallas
from repro.kernels.beam_strip.ref import beam_merge_multiframe_ref


def _impl_pallas(lp, active, keys, pb, pnb, last, lengths, *, blank: int,
                 L: int, interpret: bool = False):
    return beam_merge_multiframe_pallas(
        lp.astype(jnp.float32), active.astype(jnp.int32),
        keys.astype(jnp.int32), pb.astype(jnp.float32),
        pnb.astype(jnp.float32), last.astype(jnp.int32),
        lengths.astype(jnp.int32), blank=blank, L=L, interpret=interpret)


def _impl_ref(lp, active, keys, pb, pnb, last, lengths, *, blank: int,
              L: int, **_tiles):
    return beam_merge_multiframe_ref(
        lp.astype(jnp.float32), active.astype(jnp.int32),
        keys.astype(jnp.int32), pb.astype(jnp.float32),
        pnb.astype(jnp.float32), last.astype(jnp.int32),
        lengths.astype(jnp.int32), blank=blank, L=L)


def _example():
    """Ragged strip (one padded frame) at the paper's A=5 alphabet."""
    B, F, A, W, L = 2, 3, 5, 4, 11
    NEG = -1.0e9
    lp = jnp.zeros((B, F, A), jnp.float32) - jnp.log(float(A))
    active = jnp.array([[1, 1, 1], [1, 1, 0]], jnp.int32)
    keys = jnp.zeros((B, W), jnp.int32)
    pb = jnp.full((B, W), NEG, jnp.float32).at[:, 0].set(0.0)
    pnb = jnp.full((B, W), NEG, jnp.float32)
    last = jnp.full((B, W), -1, jnp.int32)
    lengths = jnp.zeros((B, W), jnp.int32)
    return ((lp, active, keys, pb, pnb, last, lengths),
            {"blank": A - 1, "L": L})


registry.register_op("beam_merge_multiframe", ref=_impl_ref,
                     pallas=_impl_pallas, example=_example,
                     batch_axes=((0,) * 7, (0,) * 6))


@functools.partial(jax.jit, static_argnames=("blank", "L", "backend"))
def _dispatch(lp, active, keys, pb, pnb, last, lengths, *, blank, L,
              backend):
    return registry.get_op("beam_merge_multiframe", backend)(
        lp, active, keys, pb, pnb, last, lengths, blank=blank, L=L)


def beam_merge_multiframe(lp, active, keys, pb, pnb, last, lengths, *,
                          blank: int, L: int, backend: str | None = None):
    """Advance hash beam state through F frames in one persistent launch.

    lp (B, F, A), active (B, F), state arrays (B, W) -> (idx (B, F, W),
    keys, pb, pnb, last, lengths).  ``idx`` uses the per-frame decoder's
    candidate layout (stays [0, W), extends W + w*nsym + j); padded
    frames (active == 0) emit the identity and leave state untouched.
    Backend resolves before the jit boundary (see quant_matmul.ops)."""
    return _dispatch(lp, active, keys, pb, pnb, last, lengths, blank=blank,
                     L=L, backend=registry.resolve_backend(backend))


__all__ = ["beam_merge_multiframe", "beam_merge_multiframe_ref"]
