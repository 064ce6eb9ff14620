"""Pallas TPU kernel: binary comparator array (paper §4.3, Fig. 20).

Helix stores every length-K substring of read R1 in the rows of a SOT-MRAM
array (each base as a 3-bit, 2-cell-per-bit code) and drives the bit-lines
with a substring of R2; a source-line current flags any mismatching bit.

Digital identity: with bit-planes a, b ∈ {0,1},
    xor(a, b) = a + b - 2ab
so the mismatch-bit count between substring i of R1 and substring j of R2 is

    C[i, j] = rowsum_a[i] + rowsum_b[j] - 2 * (A_bits @ B_bitsᵀ)[i, j]

i.e. ONE int8 MXU matmul plus a rank-1 epilogue — the comparator array *is*
a dot-product engine, which is exactly the paper's point.  C[i,j]==0 marks
an exact window match (zero source-line current).

Tiling: grid (N1/bm, N2/bn, D/bk) over the bit dimension D = K*3; the int32
accumulator lives in VMEM scratch; rowsums arrive as (bm,1)/(1,bn) tiles and
fuse in the last-k epilogue.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _cmp_kernel(a_ref, b_ref, ra_ref, rb_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # A (bm, bk) @ B^T (bk, bn): B arrives pre-transposed as (bk, bn)
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        o_ref[...] = ra_ref[...] + rb_ref[...] - 2 * acc_ref[...]


def vote_cmp_pallas(a_bits: jnp.ndarray, bT_bits: jnp.ndarray,
                    rowsum_a: jnp.ndarray, rowsum_b: jnp.ndarray,
                    *, bm: int = 128, bn: int = 128, bk: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """a_bits (N1, D) int8, bT_bits (D, N2) int8, rowsums (N1,1)/(1,N2) int32
    -> mismatch-bit counts (N1, N2) int32."""
    N1, D = a_bits.shape
    D2, N2 = bT_bits.shape
    assert D == D2
    assert N1 % bm == 0 and N2 % bn == 0 and D % bk == 0

    grid = (N1 // bm, N2 // bn, D // bk)
    return pl.pallas_call(
        _cmp_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((bm, 1), lambda m, n, k: (m, 0)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((N1, N2), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_bits, bT_bits, rowsum_a, rowsum_b)
