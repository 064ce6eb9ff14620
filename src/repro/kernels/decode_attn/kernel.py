"""Pallas TPU kernel: single-token decode attention over a KV cache.

The serving hot-spot: one query row per sequence against a (L, Kv, D)
cache. Bandwidth-bound (roofline §Perf: decode cells are memory-dominant),
so the kernel's job is to stream the cache HBM->VMEM exactly once with an
online softmax — no (L,) score round-trip to HBM, no f32 cache copy.

Grid (B, L/bl): for a fixed batch row the L-blocks arrive sequentially and
the running (m, l, acc) online-softmax state lives in VMEM scratch; the
output block writes once at the last L-block.  Per grid step:

  q     (1, Kv*G, D)   bf16/f32   VMEM (stationary across the L loop)
  k, v  (1, bl, Kv, D)            VMEM (streamed)
  state m,l (Kv*G,1), acc (Kv*G, D) f32 scratch
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


M_INIT = -0.5e9
MASK_NEG = -1.0e9


def _decode_kernel(nv_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, bl: int, kv_heads: int, groups: int):
    b = pl.program_id(0)
    li = pl.program_id(1)

    @pl.when(li == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, M_INIT)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                   # (Kv*G, D)
    k = k_ref[0].astype(jnp.float32)                   # (bl, Kv, D)
    v = v_ref[0].astype(jnp.float32)
    D = q.shape[-1]
    qh = q.reshape(kv_heads, groups, D) * (D ** -0.5)

    s = jnp.einsum("hgd,lhd->hgl", qh, k)              # (Kv, G, bl)
    pos = li * bl + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bl), 2)
    s = jnp.where(pos < nv_ref[b, 0], s, MASK_NEG)
    s = s.reshape(kv_heads * groups, bl)

    m_old = m_ref[...]                                 # (Kv*G, 1)
    m_new = jnp.maximum(m_old, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)                             # (Kv*G, bl)
    corr = jnp.exp(m_old - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
    pv = jnp.einsum("hgl,lhd->hgd", p.reshape(kv_heads, groups, bl),
                    v).reshape(kv_heads * groups, D)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new

    @pl.when(li == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


def _paged_decode_kernel(bt_ref, nv_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                         l_ref, acc_ref, *, bs: int, kv_heads: int,
                         groups: int):
    """Same online softmax as ``_decode_kernel``, but the (1, bs, Kv, D)
    K/V block arriving each grid step was fetched THROUGH the block table
    (scalar-prefetch index map, see ``paged_decode_attn_pallas``) — the
    kernel body only re-derives which token positions the block covers
    (``j * bs + iota``) for the validity mask."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, M_INIT)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                   # (Kv*G, D)
    k = k_ref[0].astype(jnp.float32)                   # (bs, Kv, D)
    v = v_ref[0].astype(jnp.float32)
    D = q.shape[-1]
    qh = q.reshape(kv_heads, groups, D) * (D ** -0.5)

    s = jnp.einsum("hgd,lhd->hgl", qh, k)              # (Kv, G, bs)
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
    s = jnp.where(pos < nv_ref[b, 0], s, MASK_NEG)
    s = s.reshape(kv_heads * groups, bs)

    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_old - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
    pv = jnp.einsum("hgl,lhd->hgd", p.reshape(kv_heads, groups, bs),
                    v).reshape(kv_heads * groups, D)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


def paged_decode_attn_pallas(q: jnp.ndarray, k_arena: jnp.ndarray,
                             v_arena: jnp.ndarray,
                             block_tables: jnp.ndarray,
                             n_valid: jnp.ndarray, *, groups: int,
                             interpret: bool = False) -> jnp.ndarray:
    """q (B, Kv*G, D); arenas (N, bs, Kv, D) pooled KV blocks;
    block_tables (B, nb) int32; n_valid (B, 1) int32.

    Returns (B, Kv*G, D).  Grid (B, nb): the block table rides in as a
    SCALAR-PREFETCH operand so the K/V BlockSpec index map can address
    arena row ``block_tables[b, j]`` at grid step (b, j) — the kernel
    streams exactly the blocks each lane owns, never materializing the
    (B, nb*bs, Kv, D) gather the jnp reference builds.
    """
    import functools

    B, H, D = q.shape
    N, bs, Kv, _ = k_arena.shape
    nb = block_tables.shape[1]
    assert H == Kv * groups

    kern = functools.partial(_paged_decode_kernel, bs=bs, kv_heads=Kv,
                             groups=groups)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, j, bt, nv: (b, 0, 0)),
            pl.BlockSpec((1, bs, Kv, D),
                         lambda b, j, bt, nv: (bt[b, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, Kv, D),
                         lambda b, j, bt, nv: (bt[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, j, bt, nv: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, D), jnp.float32)],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, n_valid, q, k_arena, v_arena)


def decode_attn_pallas(q: jnp.ndarray, k_cache: jnp.ndarray,
                       v_cache: jnp.ndarray, n_valid: jnp.ndarray,
                       *, groups: int, bl: int = 256,
                       interpret: bool = False) -> jnp.ndarray:
    """q (B, Kv*G, D); caches (B, L, Kv, D); n_valid (B, 1) int32.

    Returns (B, Kv*G, D). L must be a multiple of bl.
    """
    B, H, D = q.shape
    L, Kv = k_cache.shape[1], k_cache.shape[2]
    assert H == Kv * groups and L % bl == 0

    import functools
    kern = functools.partial(_decode_kernel, bl=bl, kv_heads=Kv,
                             groups=groups)
    # n_valid rides as a scalar-prefetch (SMEM) operand: a (1, 1) VMEM
    # block of a (B, 1) array breaks the TPU's (8, 128)-or-full rule
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, L // bl),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, l, nv: (b, 0, 0)),
            pl.BlockSpec((1, bl, Kv, D), lambda b, l, nv: (b, l, 0, 0)),
            pl.BlockSpec((1, bl, Kv, D), lambda b, l, nv: (b, l, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, l, nv: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, D), jnp.float32)],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(n_valid, q, k_cache, v_cache)
