"""Fused GRU cell public wrapper — dispatch via ``repro.kernels.registry``."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.gru_cell.kernel import gru_cell_pallas
from repro.kernels.gru_cell.ref import gru_cell_ref


def _impl_pallas(x_proj, h, u, b, *, bb: int = 128,
                 interpret: bool = False) -> jnp.ndarray:
    """Pad batch to the tile size and run the fused kernel."""
    B = h.shape[0]
    pad = (-B) % bb
    if pad:
        x_proj = jnp.pad(x_proj, ((0, pad), (0, 0)))
        h = jnp.pad(h, ((0, pad), (0, 0)))
    out = gru_cell_pallas(x_proj, h, u, b.reshape(1, -1), bb=bb,
                          interpret=interpret)
    return out[:B]


def _impl_ref(x_proj, h, u, b, **_tiles) -> jnp.ndarray:
    return gru_cell_ref(x_proj, h, u, b.reshape(1, -1))


def _example():
    """Ragged batch vs bb=128 (cf. tests/test_registry.py)."""
    B, H = 23, 48
    return ((jnp.zeros((B, 3 * H), jnp.float32),
             jnp.zeros((B, H), jnp.float32),
             jnp.zeros((H, 3 * H), jnp.float32),
             jnp.zeros((3 * H,), jnp.float32)), {})


registry.register_op("gru_cell", ref=_impl_ref, pallas=_impl_pallas,
                     example=_example, batch_axes=((0, 0, None, None), 0))


@functools.partial(jax.jit, static_argnames=("bb", "backend"))
def _dispatch(x_proj, h, u, b, *, bb, backend):
    return registry.get_op("gru_cell", backend)(x_proj, h, u, b, bb=bb)


def gru_cell(x_proj: jnp.ndarray, h: jnp.ndarray, u: jnp.ndarray,
             b: jnp.ndarray, *, bb: int = 128,
             backend: str | None = None) -> jnp.ndarray:
    """Fused GRU step (x_proj (B, 3H), h (B, H), u (H, 3H), b (3H,)).

    Backend resolves before the jit boundary (see quant_matmul.ops)."""
    return _dispatch(x_proj, h, u, b, bb=bb,
                     backend=registry.resolve_backend(backend))


__all__ = ["gru_cell", "gru_cell_ref"]
