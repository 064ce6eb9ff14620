"""Multi-device sharded basecalling: the dp-over-windows serving path.

The tentpole invariant: under a 4-way host-device mesh
(``conftest`` forces ``--xla_force_host_platform_device_count=4``) every
pipeline/engine surface must produce BITWISE identical output to the
single-device path — dp sharding splits the window batch, replicates the
serving artifact, and all-gathers per-window reads before the shared
stitch/vote, none of which may perturb a single bit.  Plus the
``dist.sharding`` degradation contract: no mesh -> no-op, indivisible
batch -> a clear ValueError, never an XLA shape crash.
"""
from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.quant import QuantConfig  # noqa: E402
from repro.dist import sharding as shd  # noqa: E402
from repro.pipeline import BasecallPipeline  # noqa: E402
from repro.serve import BasecallRequest, Server  # noqa: E402
from repro.serve.basecall_engine import BasecallEngine  # noqa: E402


@pytest.fixture(scope="module")
def tiny_pipe():
    pipe = BasecallPipeline.from_preset(
        "guppy", scale="tiny",
        quant=QuantConfig(enabled=True, bits_w=5, bits_a=5),
        backend="ref", beam_width=3)
    pipe.init_params(jax.random.PRNGKey(0))
    return pipe


def _assert_same_result(a, b):
    assert a.length == b.length
    assert np.array_equal(a.read, b.read)
    assert np.array_equal(a.window_reads, b.window_reads)
    assert np.array_equal(a.window_lengths, b.window_lengths)


# ---------------------------------------------------------------------------
# pipeline parity: 1 device vs 4 devices, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_windows", [1.0, 3.0, 5.3])
def test_basecall_parity_1dev_vs_4dev(tiny_pipe, host_mesh4, n_windows):
    """basecall under the mesh ≡ basecall without it, including batches
    that are not multiples of the device count."""
    rng = np.random.default_rng(int(n_windows * 10))
    sig = rng.standard_normal(
        int(tiny_pipe.mcfg.input_len * n_windows)).astype(np.float32)
    single = tiny_pipe.basecall(sig)
    with shd.use_mesh(host_mesh4):
        sharded = tiny_pipe.basecall(sig)
    _assert_same_result(single, sharded)


def test_basecall_ragged_last_batch(tiny_pipe, host_mesh4):
    """A window count that leaves a ragged final device batch (the padded
    lanes carry logit_length 0 and must not contribute reads)."""
    # batch_windows=8 rounds to 8 under dp=4; 10 windows => final batch of 2
    rng = np.random.default_rng(7)
    hop = tiny_pipe.chunk.hop
    n_samples = tiny_pipe.mcfg.input_len + 9 * hop - hop // 2
    sig = rng.standard_normal(n_samples).astype(np.float32)
    single = tiny_pipe.basecall(sig)
    assert single.window_reads.shape[0] % 4 != 0  # genuinely ragged
    with shd.use_mesh(host_mesh4):
        sharded = tiny_pipe.basecall(sig)
    _assert_same_result(single, sharded)


def test_basecall_iter_pins_creation_mesh(tiny_pipe, host_mesh4):
    """The mesh is captured when ``basecall_iter`` is CALLED: a generator
    created under a mesh shards every batch even when consumed entirely
    outside the ``use_mesh`` block, and one created outside stays
    single-device even when consumed inside — placement and decode trace
    never mix meshes."""
    rng = np.random.default_rng(13)
    sig = rng.standard_normal(
        int(tiny_pipe.mcfg.input_len * 12.5)).astype(np.float32)
    want = [(r.copy(), l.copy()) for r, l in tiny_pipe.basecall_iter(sig)]

    with shd.use_mesh(host_mesh4):
        sharded_it = tiny_pipe.basecall_iter(sig)
    got = list(sharded_it)             # consumed with no ambient mesh
    assert len(got) == len(want) > 1
    for (gr, gl), (wr, wl) in zip(got, want):
        assert np.array_equal(gr, wr)
        assert np.array_equal(gl, wl)

    plain_it = tiny_pipe.basecall_iter(sig)
    with shd.use_mesh(host_mesh4):     # consumed inside a mesh block
        got = list(plain_it)
    for (gr, gl), (wr, wl) in zip(got, want):
        assert np.array_equal(gr, wr)
        assert np.array_equal(gl, wl)


def test_basecall_empty_signal_under_mesh(tiny_pipe, host_mesh4):
    with shd.use_mesh(host_mesh4):
        res = tiny_pipe.basecall(np.zeros((0,), np.float32))
    assert res.length == 0
    assert res.window_reads.shape[0] == 0


def test_basecall_windows_parity(tiny_pipe, host_mesh4):
    rng = np.random.default_rng(3)
    margin = tiny_pipe.scfg.margin
    batch = rng.standard_normal(
        (4, tiny_pipe.mcfg.input_len + 2 * margin, 1)).astype(np.float32)
    single = [np.asarray(t) for t in tiny_pipe.basecall_windows(batch)]
    with shd.use_mesh(host_mesh4):
        sharded = [np.asarray(t) for t in tiny_pipe.basecall_windows(batch)]
    for s, m in zip(single, sharded):
        assert np.array_equal(s, m)


def test_golden_read_parity_under_mesh(golden_pipeline, golden_read,
                                       host_mesh4):
    """The golden genome -> signal -> basecall round-trip is bitwise
    identical under the 4-way mesh (the acceptance-criteria pin)."""
    pipe, params, _ = golden_pipeline
    _, sig = golden_read
    single = pipe.basecall(sig, params)
    with shd.use_mesh(host_mesh4):
        sharded = pipe.basecall(sig, params)
    _assert_same_result(single, sharded)


# ---------------------------------------------------------------------------
# dist.sharding degradation contract (the bugfix satellite)
# ---------------------------------------------------------------------------

def test_constrain_no_mesh_is_noop():
    x = np.arange(6.0).reshape(3, 2)
    y = shd.constrain(x, ("dp", None))
    assert y is x
    assert shd.replicate(x) is x
    assert shd.dp_size() == 1


def test_constrain_indivisible_skips_by_default(host_mesh4):
    """Non-strict constrain on an indivisible dim degrades to identity
    (never hands GSPMD an uneven shard)."""
    x = jax.numpy.ones((3, 2))
    with shd.use_mesh(host_mesh4):
        y = shd.constrain(x, ("dp", None))
    assert y is x


def test_constrain_indivisible_strict_raises(host_mesh4):
    x = jax.numpy.ones((3, 2))
    with shd.use_mesh(host_mesh4):
        with pytest.raises(ValueError, match="cannot shard dim of size 3"):
            shd.constrain(x, ("dp", None), strict=True)


def test_basecall_windows_indivisible_raises(tiny_pipe, host_mesh4):
    """The pipeline surfaces the divisibility failure as a clear error at
    the API boundary, not an XLA shape crash."""
    rng = np.random.default_rng(5)
    margin = tiny_pipe.scfg.margin
    batch = rng.standard_normal(
        (3, tiny_pipe.mcfg.input_len + 2 * margin, 1)).astype(np.float32)
    with shd.use_mesh(host_mesh4):
        with pytest.raises(ValueError, match="does not divide the mesh"):
            tiny_pipe.basecall_windows(batch)


def test_training_path_bakes_no_mesh(tiny_pipe, host_mesh4):
    """The training forward (backend=None) must carry ZERO sharding
    constraints even under an ambient mesh: the trainer's jits are not
    mesh-keyed, so a baked mesh would silently outlive its use_mesh
    block (regression for the serving-only constrain scoping)."""
    from repro.analysis import jaxpr_tools as jt
    from repro.models import basecaller as bc

    sig = jax.numpy.zeros((4, tiny_pipe.mcfg.input_len, 1))  # 4 % dp == 0

    def count_constraints(backend):
        with shd.use_mesh(host_mesh4):
            closed = jax.make_jaxpr(
                lambda p, s: bc.apply_basecaller(p, s, tiny_pipe.mcfg,
                                                 backend=backend)
            )(tiny_pipe.params, sig)
        return jt.count_primitive(closed, "sharding_constraint")

    assert count_constraints(None) == 0          # training: mesh-free
    assert count_constraints(tiny_pipe.backend) > 0   # serving: constrained


def test_place_params_caches_by_mesh_value(tiny_pipe, host_mesh4):
    """A mesh built per call (as the docs snippets do) must hit the
    placement cache, not re-transfer the serving artifact every call.
    (jax interns equal Mesh objects, but the cache keys by VALUE so it
    stays a hit even if that implementation detail changes.)"""
    packed = tiny_pipe.serving_params()
    placed1 = tiny_pipe._place_params(packed, host_mesh4)
    clone = shd.make_mesh((4,), ("data",))
    assert clone == host_mesh4
    placed2 = tiny_pipe._place_params(packed, clone)
    assert placed2 is placed1
    assert len(tiny_pipe._placed_cache) == 1


def test_replicated_sharding_tree(tiny_pipe, host_mesh4):
    """The serving artifact placement: every leaf fully replicated."""
    packed = tiny_pipe.serving_params()
    tree = shd.replicated_sharding_tree(packed, host_mesh4)
    for s in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, jax.sharding.NamedSharding)):
        assert all(ax is None for ax in s.spec)  # replicated on every dim


# ---------------------------------------------------------------------------
# serving stack scale-out
# ---------------------------------------------------------------------------

def test_engine_capacity_scales_with_mesh(tiny_pipe, host_mesh4):
    with shd.use_mesh(host_mesh4):
        eng = BasecallEngine(tiny_pipe, batch_slots=2)
    assert eng.dp == 4
    assert eng.B == 8
    eng1 = BasecallEngine(tiny_pipe, batch_slots=2)
    assert eng1.dp == 1 and eng1.B == 2


def test_server_engine_parity_under_mesh(tiny_pipe, host_mesh4):
    """Server.submit over a mesh-scaled engine ≡ pipe.basecall, and
    metrics() reports one occupancy entry per dp device."""
    rng = np.random.default_rng(11)
    sigs = [rng.standard_normal(
        int(tiny_pipe.mcfg.input_len * k)).astype(np.float32)
        for k in (1.4, 2.7, 0.6)]
    expected = [tiny_pipe.basecall(s) for s in sigs]
    with shd.use_mesh(host_mesh4):
        eng = BasecallEngine(tiny_pipe, batch_slots=2)
        srv = Server(eng)
        futs = [srv.submit(BasecallRequest(signal=s)) for s in sigs]
        results = [f.result() for f in futs]
    for got, want in zip(results, expected):
        assert got.ok
        _assert_same_result(got.value, want)
    m = srv.metrics()
    assert m.devices == 4
    assert len(m.occupancy_per_device) == 4
    assert all(0.0 <= o <= 1.0 for o in m.occupancy_per_device)
    # the pool-wide mean is the mean of the per-device means (equal groups)
    assert np.isclose(m.occupancy, np.mean(m.occupancy_per_device))


def test_lm_engine_capacity_scales_with_mesh(host_mesh4):
    from repro.models import lm as lm_lib
    from repro.serve.engine import ServingEngine

    cfg = lm_lib.LMConfig(n_layers=1, d_model=16, n_heads=2, n_kv_heads=2,
                          d_ff=32, vocab_size=32, remat=False)
    params = lm_lib.init_lm(jax.random.PRNGKey(0), cfg)
    with shd.use_mesh(host_mesh4):
        eng = ServingEngine(params, cfg, batch_slots=2, max_len=16)
    assert eng.dp == 4 and eng.B == 8
    assert eng.cache["pos"].shape[0] == 8
