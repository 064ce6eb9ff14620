"""Pure-jnp oracle for the fused GRU cell — mirrors models.basecaller."""
import jax
import jax.numpy as jnp

# full f32 precision, as in the kernels (see gru_cell.kernel)
HIGHEST = jax.lax.Precision.HIGHEST


def gru_cell_ref(x_proj, h, u, b):
    H = h.shape[-1]
    gates = jnp.dot(h, u, precision=HIGHEST) + x_proj + b
    z = jax.nn.sigmoid(gates[..., :H])
    r = jax.nn.sigmoid(gates[..., H:2 * H])
    n_in = x_proj[..., 2 * H:] + b[..., 2 * H:]
    n_h = jnp.dot(r * h, u[:, 2 * H:], precision=HIGHEST)
    n = jnp.tanh(n_in + n_h)
    return z * h + (1.0 - z) * n
