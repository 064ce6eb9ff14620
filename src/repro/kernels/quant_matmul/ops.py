"""quant_matmul public wrapper — dispatch via ``repro.kernels.registry``.

The Pallas path pads ragged shapes to MXU tiles; the ref path is the
int32-accumulate oracle.  Backend selection (pallas / interpret / ref /
auto) lives in the registry, not here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import quant as quant_lib
from repro.kernels import registry
from repro.kernels.quant_matmul.kernel import quant_matmul_pallas
from repro.kernels.quant_matmul.ref import quant_matmul_ref


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _impl_pallas(xq, wq, x_scale, w_scale, *, bm: int = 128, bn: int = 128,
                 bk: int = 128, interpret: bool = False) -> jnp.ndarray:
    """Pad ragged shapes to MXU tiles and run the Pallas kernel."""
    M, K = xq.shape
    N = wq.shape[1]
    xq_p = _pad_to(_pad_to(xq, bm, 0), bk, 1)
    wq_p = _pad_to(_pad_to(wq, bk, 0), bn, 1)
    sw_p = _pad_to(w_scale.reshape(1, -1), bn, 1)
    out = quant_matmul_pallas(xq_p, wq_p, x_scale.reshape(1, 1), sw_p,
                              bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:M, :N]


def _impl_ref(xq, wq, x_scale, w_scale, **_tiles) -> jnp.ndarray:
    return quant_matmul_ref(xq, wq, x_scale.reshape(1, 1),
                            w_scale.reshape(1, -1))


def _example():
    """Ragged-vs-MXU-tile shapes (cf. tests/test_registry.py)."""
    return ((jnp.zeros((37, 100), jnp.int8), jnp.zeros((100, 51), jnp.int8),
             jnp.ones((1, 1), jnp.float32), jnp.ones((1, 51), jnp.float32)),
            {})


registry.register_op("quant_matmul", ref=_impl_ref, pallas=_impl_pallas,
                     example=_example,
                     batch_axes=((0, None, None, None), 0))


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "backend"))
def _dispatch(xq, wq, x_scale, w_scale, *, bm, bn, bk, backend):
    return registry.get_op("quant_matmul", backend)(
        xq, wq, x_scale, w_scale, bm=bm, bn=bn, bk=bk)


def quant_matmul(xq: jnp.ndarray, wq: jnp.ndarray, x_scale: jnp.ndarray,
                 w_scale: jnp.ndarray, *, bm: int = 128, bn: int = 128,
                 bk: int = 128,
                 backend: str | None = None) -> jnp.ndarray:
    """Quantized matmul over int8 codes; pads ragged shapes to MXU tiles.

    The backend resolves BEFORE the jit boundary so
    ``registry.set_default_backend`` takes effect on the next call rather
    than being pinned by a stale trace.
    """
    return _dispatch(xq, wq, x_scale, w_scale, bm=bm, bn=bn, bk=bk,
                     backend=registry.resolve_backend(backend))


def qmm_from_float(x: jnp.ndarray, w: jnp.ndarray, bits: int = 5,
                   backend: str | None = None) -> jnp.ndarray:
    """Quantize fp inputs on the fly and run the integer kernel."""
    xq, sx = quant_lib.pack_act(x, bits)
    wq, sw = quant_lib.pack_weight(w, bits)
    return quant_matmul(xq, wq, sx.reshape(1, 1), sw.reshape(1, -1),
                        backend=backend)


def qmm_packed(x: jnp.ndarray, wq: jnp.ndarray, sw: jnp.ndarray,
               *, bits_a: int = 5,
               backend: str | None = None) -> jnp.ndarray:
    """Integer matmul against a PRE-PACKED weight — no float detour.

    ``(wq int8, sw fp32)`` is the quantize-once serving artifact
    (``core.quant.pack_weight`` at pack time); only the activation is
    quantized here, with per-row scales so the result is batch-composition
    invariant (see ``core.quant.pack_act_rows``).  The trace therefore
    contains zero weight-quantization ops.
    """
    lead, F = x.shape[:-1], x.shape[-1]
    xq, sx = quant_lib.pack_act_rows(x.reshape(-1, F), bits_a)
    one = jnp.ones((1, 1), jnp.float32)
    # resolved here, inside the caller's trace, so the kernel maps over
    # the ambient mesh's dp shards (see kernels.registry)
    qmm = registry.get_op("quant_matmul", backend)
    y = qmm(xq, wq, one, sw.reshape(1, -1)) * sx
    return y.reshape(lead + (wq.shape[-1],))


__all__ = ["quant_matmul", "qmm_from_float", "qmm_packed",
           "quant_matmul_ref"]
