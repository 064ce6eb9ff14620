"""Production mesh factory.

Single pod: v5e 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2 pods    = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is pure data-parallel; its gradient all-reduce is the only
traffic that crosses the (slow) inter-pod DCI, and it is int8-compressible
(dist/collectives.py).

Defined as a FUNCTION so importing this module never touches jax device
state (device count is locked on first backend init — dryrun.py sets
XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax

from repro.dist.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Degenerate mesh over the locally available devices (tests/examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))
