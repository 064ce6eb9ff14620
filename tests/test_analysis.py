"""Loop-aware HLO cost model: validated against unrolled lowerings.

The core claim (EXPERIMENTS.md §Roofline methodology): XLA cost_analysis
counts while bodies once; our reconstruction multiplies by parsed trip
counts and must agree with an UNROLLED lowering of the same computation.
Runs in a subprocess so the multi-device XLA_FLAGS never leak into the
test process.
"""
import json
import subprocess
import sys

import pytest

PROBE = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, functools
from repro.launch.analysis import loop_aware_cost

def model(x, ws, use_scan, L):
    if use_scan:
        def body(x, w):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, ws)
        return x
    for i in range(L):
        x = jnp.tanh(x @ ws[i])
    return x

out = {}
for L in (2, 8):
    xs = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    wss = jax.ShapeDtypeStruct((L, 256, 256), jnp.float32)
    for use_scan in (True, False):
        c = jax.jit(functools.partial(model, use_scan=use_scan, L=L)
                    ).lower(xs, wss).compile()
        la = loop_aware_cost(c.as_text(), 4)
        rep = c.cost_analysis()
        if isinstance(rep, (list, tuple)):   # older jax: list of one dict
            rep = rep[0]
        out[f"{L}_{use_scan}"] = {"la_flops": la[0], "la_bytes": la[1],
                                  "xla_flops": float(rep["flops"])}

def nested(x):
    def outer(c, _):
        def inner(ci, _):
            return jnp.tanh(ci @ ci.T) @ ci, None
        c, _ = jax.lax.scan(inner, c, None, length=3)
        return c, None
    x, _ = jax.lax.scan(outer, x, None, length=5)
    return x
c = jax.jit(nested).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32)
                          ).compile()
out["nested"] = {"la_flops": loop_aware_cost(c.as_text(), 4)[0]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def probe():
    r = subprocess.run([sys.executable, "-c", PROBE], capture_output=True,
                       text=True, env={**__import__("os").environ,
                                        "PYTHONPATH": "src",
                                        "JAX_PLATFORMS": "cpu"},
                       timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_scan_flops_match_unrolled(probe):
    for L in (2, 8):
        scan = probe[f"{L}_True"]["la_flops"]
        unrolled = probe[f"{L}_False"]["la_flops"]
        assert abs(scan - unrolled) / unrolled < 0.02, (L, scan, unrolled)


def test_xla_reported_flops_do_not_scale_with_trip_count(probe):
    """The motivating defect: XLA's own numbers are L-independent for scan."""
    assert probe["2_True"]["xla_flops"] == probe["8_True"]["xla_flops"]
    assert probe["8_False"]["xla_flops"] > 3 * probe["8_True"]["xla_flops"]


def test_scan_bytes_close_to_unrolled(probe):
    for L in (8,):
        scan = probe[f"{L}_True"]["la_bytes"]
        unrolled = probe[f"{L}_False"]["la_bytes"]
        assert abs(scan - unrolled) / unrolled < 0.25, (scan, unrolled)


def test_nested_loop_multiplication(probe):
    want = 5 * 3 * 2 * (2 * 64 ** 3)
    assert abs(probe["nested"]["la_flops"] - want) / want < 0.02
