"""Pallas TPU kernel: fused GRU cell (the base-caller's compute hot-spot).

Guppy/Scrappie spend >90 % of DNN FLOPs in the GRU stack (Table 3); the
recurrent h·U product is the part that cannot be hoisted out of the time
loop.  This kernel fuses, per time step:

    gates = h @ U + x_proj + b          (MXU)
    z, r  = σ(gates[:, :H]), σ(gates[:, H:2H])
    n     = tanh(x_projₙ + bₙ + (r ⊙ h) @ Uₙ)   (second MXU product)
    h'    = z ⊙ h + (1-z) ⊙ n

so h, U, and the gate intermediates stay in VMEM for the whole step —
on the PIM this is "weights stationary in the crossbar"; on TPU it is
U resident in VMEM across the batch grid (BlockSpec index ignores the
batch coordinate).

Grid: (B/bb,). U is (H, 3H): with H≤512 that is ≤3 MiB fp32 — well within
a v5e core's 16 MiB VMEM next to the (bb, 3H) activation tiles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# f32 at full precision on the MXU, here and in the oracle
# (gru_cell.ref): a default-precision f32 dot on a TPU rounds its
# inputs to bf16, and the kernel and its oracle must agree on the chip
HIGHEST = jax.lax.Precision.HIGHEST


def _gru_kernel(xp_ref, h_ref, u_ref, b_ref, bn_ref, o_ref):
    h = h_ref[...]                      # (bb, H)
    u = u_ref[...]                      # (H, 3H)
    xp = xp_ref[...]                    # (bb, 3H)
    b = b_ref[...]                      # (1, 3H)
    H = h.shape[-1]
    # n-gate bias is its own (1, H) block (see gru_seq.kernel)

    gates = jnp.dot(h, u, precision=HIGHEST,
                    preferred_element_type=jnp.float32) + xp + b
    z = jax.nn.sigmoid(gates[:, :H])
    r = jax.nn.sigmoid(gates[:, H:2 * H])
    n_in = xp[:, 2 * H:] + bn_ref[...]
    n_h = jnp.dot(r * h, u[:, 2 * H:], precision=HIGHEST,
                  preferred_element_type=jnp.float32)
    n = jnp.tanh(n_in + n_h)
    o_ref[...] = z * h + (1.0 - z) * n


def gru_cell_pallas(x_proj: jnp.ndarray, h: jnp.ndarray, u: jnp.ndarray,
                    b: jnp.ndarray, *, bb: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """x_proj (B, 3H), h (B, H), u (H, 3H), b (1, 3H) -> h' (B, H)."""
    B, H = h.shape
    assert x_proj.shape == (B, 3 * H)
    assert B % bb == 0

    grid = (B // bb,)
    return pl.pallas_call(
        _gru_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, 3 * H), lambda i: (i, 0)),
            pl.BlockSpec((bb, H), lambda i: (i, 0)),
            pl.BlockSpec((H, 3 * H), lambda i: (0, 0)),   # stationary
            pl.BlockSpec((1, 3 * H), lambda i: (0, 0)),
            pl.BlockSpec((1, H), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x_proj, h, u, b, b[:, 2 * H:])
