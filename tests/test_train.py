"""Training substrate: optimizer, checkpoint, fault tolerance, compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import collectives
from repro.train import checkpoint as ckpt_lib
from repro.train import fault as fault_lib
from repro.train.optimizer import AdamW, warmup_cosine, global_norm
from repro.train.trainer import Trainer, TrainerConfig, make_train_step

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def _quadratic_problem(seed=0):
    key = jax.random.PRNGKey(seed)
    target = jax.random.normal(key, (8, 4))
    params = {"w": jnp.zeros((8, 4))}

    def loss_fn(p, batch=None):
        return jnp.mean((p["w"] - target) ** 2), {}

    return params, loss_fn, target


@pytest.mark.parametrize("state_bits", [32, 8])
def test_adamw_converges(state_bits):
    params, loss_fn, target = _quadratic_problem()
    opt = AdamW(lr=0.05, state_bits=state_bits, clip_norm=None)
    state = opt.init(params)
    grad_fn = jax.jit(jax.grad(lambda p: loss_fn(p)[0]))
    for _ in range(300):
        params, state = opt.update(grad_fn(params), state, params)
    err = float(jnp.mean((params["w"] - target) ** 2))
    assert err < 1e-2, err


def test_adamw_8bit_tracks_fp32():
    params, loss_fn, _ = _quadratic_problem(1)
    grad_fn = jax.jit(jax.grad(lambda p: loss_fn(p)[0]))
    trajs = {}
    for bits in (32, 8):
        p = jax.tree_util.tree_map(jnp.copy, params)
        opt = AdamW(lr=0.05, state_bits=bits, clip_norm=None)
        s = opt.init(p)
        for _ in range(50):
            p, s = opt.update(grad_fn(p), s, p)
        trajs[bits] = p["w"]
    diff = float(jnp.abs(trajs[32] - trajs[8]).max())
    scale = float(jnp.abs(trajs[32]).max())
    assert diff < 0.1 * max(scale, 1.0), diff


def test_grad_clipping():
    opt = AdamW(lr=0.0, clip_norm=1.0)  # lr 0: only test the clip path runs
    params = {"w": jnp.ones((4,))}
    state = opt.init(params)
    big = {"w": jnp.full((4,), 1e6)}
    newp, _ = opt.update(big, state, params)
    np.testing.assert_allclose(np.asarray(newp["w"]), 1.0)


def test_warmup_cosine_shape():
    sched = warmup_cosine(1.0, 10, 100)
    assert float(sched(0)) == 0.0
    np.testing.assert_allclose(float(sched(10)), 1.0, rtol=1e-5)
    assert float(sched(100)) < float(sched(50)) < float(sched(10))
    np.testing.assert_allclose(float(sched(100)), 0.1, atol=1e-6)


def test_global_norm():
    t = {"a": jnp.ones((3,)), "b": jnp.full((4,), 2.0)}
    np.testing.assert_allclose(float(global_norm(t)),
                               np.sqrt(3 + 16), rtol=1e-6)


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones((4,), jnp.int32)}}
    ckpt_lib.save(str(tmp_path), 7, tree)
    template = jax.tree_util.tree_map(jnp.zeros_like, tree)
    out, step = ckpt_lib.restore(str(tmp_path), template)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(out["b"]["c"]),
                                  np.asarray(tree["b"]["c"]))


def test_checkpoint_keep_and_latest(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    for s in (1, 2, 3, 4, 5):
        ckpt_lib.save(str(tmp_path), s, tree, keep=2)
    assert ckpt_lib.all_steps(str(tmp_path)) == [4, 5]
    assert ckpt_lib.latest_step(str(tmp_path)) == 5


def test_checkpoint_async(tmp_path):
    tree = {"x": jnp.full((128,), 3.0)}
    t = ckpt_lib.save_async(str(tmp_path), 1, tree)
    t.join(10)
    out, step = ckpt_lib.restore(str(tmp_path), tree)
    np.testing.assert_allclose(np.asarray(out["x"]), 3.0)


def test_checkpoint_elastic_resharding(tmp_path):
    """Host-global arrays restore onto a different device layout."""
    tree = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    ckpt_lib.save(str(tmp_path), 0, tree)
    # "new cluster": single-device sharding spec (degenerate but exercises
    # the device_put path with an explicit Sharding object)
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist.sharding import make_mesh
    mesh = make_mesh((1,), ("data",))
    sh = NamedSharding(mesh, P("data", None))
    out, _ = ckpt_lib.restore(str(tmp_path), tree, sharding_tree=sh)
    assert out["w"].sharding == sh
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(tree["w"]))


def test_checkpoint_shape_mismatch_raises(tmp_path):
    ckpt_lib.save(str(tmp_path), 0, {"w": jnp.zeros((2, 2))})
    with pytest.raises(ValueError):
        ckpt_lib.restore(str(tmp_path), {"w": jnp.zeros((3, 3))})


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

def test_heartbeat_and_quorum():
    clock = [0.0]
    hb = fault_lib.Heartbeat(timeout_s=10, clock=lambda: clock[0])
    hb.beat("w0"); hb.beat("w1")
    assert hb.quorum(2)
    clock[0] = 15.0
    hb.beat("w0")
    assert hb.alive("w0") and not hb.alive("w1")
    assert hb.dead_workers() == ["w1"]
    assert not hb.quorum(2)


def test_straggler_detector():
    det = fault_lib.StragglerDetector(z_threshold=3.0)
    flags = [det.observe(1.0 + 0.01 * (i % 3)) for i in range(30)]
    assert not any(flags)
    assert det.observe(10.0)   # 10x step time => straggler
    assert not det.observe(1.0)


def test_trainer_crash_restart_is_deterministic(tmp_path):
    """A run with an injected crash equals an uninterrupted run, bit-for-bit
    (per-step data + checkpoints => full replay determinism)."""
    def make(run_dir, fail):
        params, loss_fn, target = _quadratic_problem(3)

        def data_fn(step):
            return {"step": jnp.asarray(step)}

        def loss(p, batch):
            return jnp.mean((p["w"] - target) ** 2), {}

        tr = Trainer(loss, data_fn, params, AdamW(lr=0.05, clip_norm=None),
                     TrainerConfig(steps=20, ckpt_every=5, log_every=0,
                                   ckpt_dir=str(run_dir), ckpt_async=False))
        if fail:
            tr.fault_injector = fault_lib.FaultInjector(fail_at=[12])
        return tr

    clean = make(tmp_path / "clean", fail=False)
    clean.run(max_restarts=0)

    faulty = make(tmp_path / "faulty", fail=True)
    faulty.run(max_restarts=2)

    np.testing.assert_array_equal(np.asarray(clean.params["w"]),
                                  np.asarray(faulty.params["w"]))


def test_run_resilient_gives_up_after_max_restarts():
    calls = []

    def run_from(start):
        calls.append(start)
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError):
        fault_lib.run_resilient(run_from, lambda: 0, max_restarts=2)
    assert len(calls) == 3  # initial + 2 restarts


# ---------------------------------------------------------------------------
# grad accumulation & compression
# ---------------------------------------------------------------------------

def test_grad_accum_matches_full_batch():
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (8, 2))
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    y = jax.random.normal(jax.random.PRNGKey(2), (16, 2))

    def loss(p, batch):
        pred = batch["x"] @ p["w"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    opt = AdamW(lr=1e-2, clip_norm=None)
    outs = {}
    for accum in (1, 4):
        params = {"w": w}
        state = opt.init(params)
        step = make_train_step(loss, opt, grad_accum=accum, donate=False)
        params, state, _ = step(params, state, {"x": x, "y": y})
        outs[accum] = params["w"]
    np.testing.assert_allclose(np.asarray(outs[1]), np.asarray(outs[4]),
                               rtol=2e-4, atol=2e-5)


def test_compress_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,))
    codes, scale = collectives.compress(x)
    assert codes.dtype == jnp.int8
    y = collectives.decompress(codes, scale, x.shape)
    blocks_max = float(jnp.abs(x).max())
    assert float(jnp.abs(x - y).max()) <= blocks_max / 127 + 1e-6


def test_error_feedback_preserves_sum():
    """Σ_t wire_t + residual_T == Σ_t grad_t (exact bookkeeping)."""
    key = jax.random.PRNGKey(1)
    res = jnp.zeros((300,))
    total_wire = jnp.zeros((300,))
    total_grad = jnp.zeros((300,))
    for t in range(20):
        g = jax.random.normal(jax.random.fold_in(key, t), (300,))
        wire, res = collectives.ef_compress(g, res)
        total_wire += wire
        total_grad += g
    np.testing.assert_allclose(np.asarray(total_wire + res),
                               np.asarray(total_grad), rtol=1e-4, atol=1e-4)


def test_compression_ratio():
    r = collectives.compression_ratio((1024, 1024))
    assert 3.5 < r < 4.0  # ~4x vs fp32 with per-block scale overhead
