"""Elastic scaling: checkpoints move between DIFFERENT device meshes.

Runs in subprocesses (8 fake host devices) so the multi-device XLA_FLAGS
never leak into the main test process: save params sharded on a (4,2)
mesh, restore onto (2,4) and (8,1) meshes, verify bitwise equality —
the restart-with-a-different-pod-count path of train/checkpoint.py.
"""
import json
import os
import subprocess
import sys

import pytest

PROBE = r"""
import os, json, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train import checkpoint as ckpt_lib
from repro.dist import sharding as shd

def mesh(shape):
    return shd.make_mesh(shape, ("data", "model"))

tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
        "b": jnp.linspace(0, 1, 8)}
m1 = mesh((4, 2))
sh1 = {"w": NamedSharding(m1, P("data", "model")),
       "b": NamedSharding(m1, P("model"))}
placed = {k: jax.device_put(v, sh1[k]) for k, v in tree.items()}
d = tempfile.mkdtemp()
ckpt_lib.save(d, 5, placed)

out = {"ok": True}
for shape, spec_w in (((2, 4), P("model", "data")), ((8, 1), P("data", None))):
    m2 = mesh(shape)
    sh2 = {"w": NamedSharding(m2, spec_w), "b": NamedSharding(m2, P())}
    restored, step = ckpt_lib.restore(d, tree, sharding_tree=sh2)
    assert step == 5
    for k in tree:
        np.testing.assert_array_equal(np.asarray(restored[k]),
                                      np.asarray(tree[k]))
        assert restored[k].sharding == sh2[k], (shape, k)
    out[f"mesh{shape}"] = "ok"
print(json.dumps(out))
"""


def test_checkpoint_elastic_across_meshes():
    r = subprocess.run([sys.executable, "-c", PROBE], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": "src",
                                        "JAX_PLATFORMS": "cpu"},
                       timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["mesh(2, 4)"] == "ok" \
        and out["mesh(8, 1)"] == "ok"
