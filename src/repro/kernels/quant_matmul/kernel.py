"""Pallas TPU kernel: low-bit quantized matmul with fused dequant epilogue.

TPU rendition of the paper's NVM dot-product engine (§2.4/§4.2): the
128x128 crossbar holding 2-bit-cell weights maps onto a 128x128 MXU tile
holding int8-container codes (a 5-bit weight occupies the [-15,15] sub-grid,
see core/quant.py).  The bit-serial input DAC pipeline becomes the int8 MXU
datapath; the CMOS/SOT-MRAM ADC stage becomes the fp32 dequant epilogue
(per-channel weight scale x per-tensor activation scale), fused so the int32
accumulator never round-trips to HBM.

Memory plan per grid step (defaults bm=bn=bk=128):
  x tile  (bm, bk) int8   16 KiB   VMEM
  w tile  (bk, bn) int8   16 KiB   VMEM (stationary across m by grid order)
  acc     (bm, bn) int32  64 KiB   VMEM scratch, lives across the k loop
  out     (bm, bn) f32    64 KiB   written once at k == K-1
MXU dims are multiples of 128 by construction; ops.py pads ragged shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _qmm_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * sx_ref[0, 0] * sw_ref[...])


def quant_matmul_pallas(xq: jnp.ndarray, wq: jnp.ndarray,
                        x_scale: jnp.ndarray, w_scale: jnp.ndarray,
                        *, bm: int = 128, bn: int = 128, bk: int = 128,
                        interpret: bool = False) -> jnp.ndarray:
    """(M,K) int8 @ (K,N) int8 -> (M,N) f32. Shapes must be block multiples.

    x_scale: (1, 1) f32 per-tensor; w_scale: (1, N) f32 per-channel.
    """
    M, K = xq.shape
    K2, N = wq.shape
    assert K == K2, (K, K2)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)

    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        _qmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, 1), lambda m, n, k: (0, 0)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xq, wq, x_scale, w_scale)
