"""Pallas TPU kernel: persistent GRU sequence (whole-layer recurrent scan).

The per-step ``gru_cell`` kernel already keeps U resident within one step,
but the scan around it still launches one kernel per timestep — hidden
state and the recurrent weights round-trip through HBM T times per layer.
This kernel is the jax_pallas analogue of Helix's in-situ PIM dataflow:
ONE ``pallas_call`` whose grid walks timesteps, with

  * U and b fetched once per batch tile (their BlockSpec index maps
    ignore the time coordinate, so Pallas keeps the blocks resident in
    VMEM across the whole walk — "weights stationary in the crossbar"),
  * the hidden state h living in a VMEM scratch buffer that persists
    across grid iterations (initialized from h0 at t == 0),
  * only x_proj streaming in and ys streaming out, one (bb, ·) tile per
    step.

Grid: (B/bb, T) with semantics ("parallel", "arbitrary") — batch tiles
are independent; the time axis is a sequential walk (t is the minor grid
dimension, so each batch tile sees t = 0..T-1 in order and re-initializes
its scratch at t == 0).

Per-step math is IDENTICAL to ``gru_cell.kernel._gru_kernel`` — the
differential tests pin the fused walk bitwise against the per-step scan.

VMEM residency per tile: U (H, 3H) + b + h scratch (bb, H) + one x_proj
tile (bb, 3H) + one output tile (bb, H).  At the paper's H = 96 and
bb = 128 that is ~0.4 MiB — far inside the 16 MiB per-core budget
(``repro.analysis`` pass 2 checks this estimate on the registered
example shapes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST    # as in gru_cell.kernel, which says why


def _gru_seq_kernel(xp_ref, h0_ref, u_ref, b_ref, bn_ref, o_ref, h_scratch):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        h_scratch[...] = h0_ref[...]

    h = h_scratch[...]                  # (bb, H) — persistent across t
    u = u_ref[...]                      # (H, 3H) — stationary
    xp = xp_ref[0]                      # (bb, 3H) — this step's tile
    b = b_ref[...]                      # (1, 3H)
    H = h.shape[-1]
    # the n-gate bias arrives as its own (1, H) block: a lane-offset slice
    # b[:, 2H:] broadcast to (bb, H) has a layout Mosaic refuses

    gates = jnp.dot(h, u, precision=HIGHEST,
                    preferred_element_type=jnp.float32) + xp + b
    z = jax.nn.sigmoid(gates[:, :H])
    r = jax.nn.sigmoid(gates[:, H:2 * H])
    n_in = xp[:, 2 * H:] + bn_ref[...]
    n_h = jnp.dot(r * h, u[:, 2 * H:], precision=HIGHEST,
                  preferred_element_type=jnp.float32)
    n = jnp.tanh(n_in + n_h)
    hn = z * h + (1.0 - z) * n
    h_scratch[...] = hn
    o_ref[0] = hn


def gru_seq_pallas(x_proj: jnp.ndarray, h0: jnp.ndarray, u: jnp.ndarray,
                   b: jnp.ndarray, *, bb: int = 128,
                   interpret: bool = False) -> jnp.ndarray:
    """x_proj (T, B, 3H), h0 (B, H), u (H, 3H), b (1, 3H) -> ys (T, B, H)."""
    T, B, _ = x_proj.shape
    H = h0.shape[-1]
    assert x_proj.shape == (T, B, 3 * H)
    assert B % bb == 0

    grid = (B // bb, T)
    return pl.pallas_call(
        _gru_seq_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bb, 3 * H), lambda i, t: (t, i, 0)),
            pl.BlockSpec((bb, H), lambda i, t: (i, 0)),
            pl.BlockSpec((H, 3 * H), lambda i, t: (0, 0)),   # stationary
            pl.BlockSpec((1, 3 * H), lambda i, t: (0, 0)),
            pl.BlockSpec((1, H), lambda i, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bb, H), lambda i, t: (t, i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, B, H), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x_proj, h0, u, b, b[:, 2 * H:])
