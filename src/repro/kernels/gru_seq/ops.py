"""Persistent GRU sequence public wrapper — dispatch via the registry.

One launch per layer/direction instead of one per timestep: the batch is
padded to the tile size ONCE and the whole recurrent walk runs inside a
single ``pallas_call`` (see kernel.py).  Zero-padded batch rows are inert
— every per-row op (the h·U matmul rows included) is independent of the
other rows, so the real rows are bitwise what the per-step path computes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.gru_seq.kernel import gru_seq_pallas
from repro.kernels.gru_seq.ref import gru_seq_ref


def _impl_pallas(x_proj, h0, u, b, *, bb: int = 128,
                 interpret: bool = False) -> jnp.ndarray:
    """Pad batch to the tile size and run the persistent kernel."""
    B = h0.shape[0]
    pad = (-B) % bb
    if pad:
        x_proj = jnp.pad(x_proj, ((0, 0), (0, pad), (0, 0)))
        h0 = jnp.pad(h0, ((0, pad), (0, 0)))
    out = gru_seq_pallas(x_proj, h0, u, b.reshape(1, -1), bb=bb,
                         interpret=interpret)
    return out[:, :B]


def _impl_ref(x_proj, h0, u, b, **_tiles) -> jnp.ndarray:
    return gru_seq_ref(x_proj, h0, u, b)


def _example():
    """Ragged batch vs bb=128, odd T (cf. tests/test_registry.py)."""
    T, B, H = 7, 23, 48
    return ((jnp.zeros((T, B, 3 * H), jnp.float32),
             jnp.zeros((B, H), jnp.float32),
             jnp.zeros((H, 3 * H), jnp.float32),
             jnp.zeros((3 * H,), jnp.float32)), {})


registry.register_op("gru_seq", ref=_impl_ref, pallas=_impl_pallas,
                     example=_example, batch_axes=((1, 0, None, None), 1))


@functools.partial(jax.jit, static_argnames=("bb", "backend"))
def _dispatch(x_proj, h0, u, b, *, bb, backend):
    return registry.get_op("gru_seq", backend)(x_proj, h0, u, b, bb=bb)


def gru_seq(x_proj: jnp.ndarray, h0: jnp.ndarray, u: jnp.ndarray,
            b: jnp.ndarray, *, bb: int = 128,
            backend: str | None = None) -> jnp.ndarray:
    """Whole-layer GRU walk: x_proj (T, B, 3H), h0 (B, H) -> ys (T, B, H).

    Backend resolves before the jit boundary (see quant_matmul.ops)."""
    return _dispatch(x_proj, h0, u, b, bb=bb,
                     backend=registry.resolve_backend(backend))


__all__ = ["gru_seq", "gru_seq_ref"]
