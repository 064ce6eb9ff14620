"""Kernel backend registry: ref ≡ interpret parity sweep + dispatch rules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import registry

jax.config.update("jax_platform_name", "cpu")

RAGGED = 0  # marker: every shape below is deliberately non-tile-multiple


def _quant_matmul_args(rng):
    M, K, N = 37, 100, 51                       # ragged vs 128 tiles
    xq = jnp.asarray(rng.integers(-15, 16, (M, K)), jnp.int8)
    wq = jnp.asarray(rng.integers(-15, 16, (K, N)), jnp.int8)
    sx = jnp.asarray([[0.021]], jnp.float32)
    sw = jnp.asarray(rng.random((1, N)).astype(np.float32) * 0.05 + 1e-3)
    return (xq, wq, sx, sw), {}


def _gru_cell_args(rng):
    B, H = 23, 48                                # ragged vs bb=128
    xp = jnp.asarray(rng.standard_normal((B, 3 * H)).astype(np.float32))
    h = jnp.asarray(rng.standard_normal((B, H)).astype(np.float32))
    u = jnp.asarray(rng.standard_normal((H, 3 * H)).astype(np.float32) * 0.1)
    b = jnp.asarray(rng.standard_normal((3 * H,)).astype(np.float32) * 0.1)
    return (xp, h, u, b), {}


def _masked_logsumexp_args(rng):
    B, C = 3, 45                                 # ragged vs bi=128
    eq = rng.integers(0, 2, (B, C, C))
    eq |= np.eye(C, dtype=eq.dtype)[None]        # rows self-connected
    scores = rng.standard_normal((B, C)).astype(np.float32)
    return (jnp.asarray(eq, jnp.int8), jnp.asarray(scores)), {}


def _decode_attn_args(rng):
    B, L, Kv, G, D = 2, 75, 2, 3, 16             # ragged vs bl=256
    q = jnp.asarray(rng.standard_normal((B, Kv * G, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, L, Kv, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, L, Kv, D)).astype(np.float32))
    n_valid = jnp.asarray([31, 75], jnp.int32)
    return (q, k, v, n_valid), {"groups": G}


def _paged_decode_attn_args(rng):
    B, N, bs, Kv, G, D = 2, 16, 8, 2, 3, 16      # non-contiguous tables
    q = jnp.asarray(rng.standard_normal((B, Kv * G, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((N, bs, Kv, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((N, bs, Kv, D)).astype(np.float32))
    tables = jnp.asarray([[3, 7, 1], [12, 0, 5]], jnp.int32)
    n_valid = jnp.asarray([5, 20], jnp.int32)    # ragged vs nb*bs = 24
    return (q, k, v, tables, n_valid), {"groups": G}


def _mismatch_bits_args(rng):
    r1 = jnp.asarray(rng.integers(0, 4, (41,)), jnp.int32)
    r2 = jnp.asarray(rng.integers(0, 4, (29,)), jnp.int32)
    return (r1, r2), {"K": 5}


def _beam_merge_topk_args(rng):
    B, C = 2, 45                                 # ragged vs the 128 lane tile
    keys = jnp.asarray(rng.integers(0, 12, (B, C)), jnp.int32)  # duplicates
    pb = jnp.asarray(rng.standard_normal((B, C)).astype(np.float32) * 4)
    pnb = jnp.asarray(rng.standard_normal((B, C)).astype(np.float32) * 4)
    return (keys, pb, pnb), {"W": 7}


def _gru_seq_args(rng):
    T, B, H = 7, 23, 48                          # ragged vs bb=128, odd T
    xp = jnp.asarray(rng.standard_normal((T, B, 3 * H)).astype(np.float32))
    h0 = jnp.asarray(rng.standard_normal((B, H)).astype(np.float32))
    u = jnp.asarray(rng.standard_normal((H, 3 * H)).astype(np.float32) * 0.1)
    b = jnp.asarray(rng.standard_normal((3 * H,)).astype(np.float32) * 0.1)
    return (xp, h0, u, b), {}


def _beam_merge_multiframe_args(rng):
    B, F, A, W, L = 2, 3, 5, 4, 11               # one padded (ragged) frame
    NEG = -1.0e9
    lp = jnp.asarray(np.log(
        rng.dirichlet(np.ones(A), (B, F))).astype(np.float32))
    active = jnp.asarray([[1, 1, 1], [1, 1, 0]], jnp.int32)
    keys = jnp.zeros((B, W), jnp.int32)
    pb = jnp.full((B, W), NEG, jnp.float32).at[:, 0].set(0.0)
    pnb = jnp.full((B, W), NEG, jnp.float32)
    last = jnp.full((B, W), -1, jnp.int32)
    lengths = jnp.zeros((B, W), jnp.int32)
    return ((lp, active, keys, pb, pnb, last, lengths),
            {"blank": A - 1, "L": L})


_CASES = {
    "quant_matmul": _quant_matmul_args,
    "gru_cell": _gru_cell_args,
    "gru_seq": _gru_seq_args,
    "masked_logsumexp": _masked_logsumexp_args,
    "beam_merge_topk": _beam_merge_topk_args,
    "beam_merge_multiframe": _beam_merge_multiframe_args,
    "decode_attn": _decode_attn_args,
    "paged_decode_attn": _paged_decode_attn_args,
    "mismatch_bits": _mismatch_bits_args,
}


def test_registry_knows_all_registered_ops():
    assert set(registry.list_ops()) == set(_CASES)


@pytest.mark.parametrize("name", sorted(_CASES))
def test_ref_matches_interpret_on_ragged_shapes(name):
    """get_op(name, "ref") ≡ get_op(name, "interpret"): the padding done by
    the Pallas wrapper must be invisible on non-tile-multiple shapes."""
    rng = np.random.default_rng(hash(name) % 2**31)
    args, kw = _CASES[name](rng)
    ref = registry.get_op(name, "ref")(*args, **kw)
    interp = registry.get_op(name, "interpret")(*args, **kw)
    ref_leaves = jax.tree_util.tree_leaves(ref)
    interp_leaves = jax.tree_util.tree_leaves(interp)
    assert len(ref_leaves) == len(interp_leaves), name
    for r, i in zip(ref_leaves, interp_leaves):
        assert r.shape == i.shape, name
        np.testing.assert_allclose(np.asarray(r), np.asarray(i),
                                   rtol=1e-5, atol=1e-5)


def test_unknown_op_suggests_nearest():
    with pytest.raises(KeyError, match="quant_matmul"):
        registry.get_op("quant_matmui")


def test_unknown_backend_rejected():
    with pytest.raises(ValueError):
        registry.get_op("gru_cell", "cuda")
    with pytest.raises(ValueError):
        registry.Backend("cuda")


def test_backend_auto_resolves_off_tpu_to_interpret():
    """On the CPU test platform "auto" runs the kernel bodies in
    interpret mode."""
    assert jax.default_backend() == "cpu"
    assert registry.Backend("auto").resolved == "interpret"
    assert registry.resolve_backend(None) == "interpret"
    assert registry.resolve_backend("ref") == "ref"


def test_pallas_backend_off_tpu_raises_without_interpreting(monkeypatch):
    """backend="pallas" on the CPU is an error naming the TPU, raised
    before any kernel runs — never a quiet fall back to interpret mode."""
    from repro.kernels.gru_cell import ops as gru_ops

    entry = registry._REGISTRY["gru_cell"]     # registered by the import
    calls = []

    def spy_pallas(*a, interpret=False, **kw):
        calls.append(interpret)
        return entry.pallas(*a, interpret=interpret, **kw)

    monkeypatch.setitem(registry._REGISTRY, "gru_cell",
                        registry.OpEntry("gru_cell", entry.ref, spy_pallas,
                                         entry.example))
    (xp, h, u, b), _ = _gru_cell_args(np.random.default_rng(3))
    for resolve in (lambda: registry.resolve_backend("pallas"),
                    lambda: registry.Backend("pallas").resolved,
                    lambda: registry.get_op("gru_cell", "pallas"),
                    lambda: gru_ops.gru_cell(xp, h, u, b, backend="pallas")):
        with pytest.raises(RuntimeError, match="needs a TPU"):
            resolve()
    assert calls == []
    # the process default cannot smuggle it in either
    monkeypatch.setattr(registry, "_default_backend", "pallas")
    with pytest.raises(RuntimeError, match="needs a TPU"):
        registry.resolve_backend(None)


def test_kernel_runs_per_dp_shard_under_mesh(host_mesh4):
    """Under a dp mesh a kernel with declared batch axes runs inside a
    shard_map (XLA cannot partition a Mosaic kernel), bitwise equal to the
    unsharded call; a batch the devices do not divide is refused."""
    from repro.dist import sharding as shd

    rng = np.random.default_rng(5)
    T, B, H = 3, 8, 16
    xp = jnp.asarray(rng.standard_normal((T, B, 3 * H)), jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((B, H)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((H, 3 * H)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.standard_normal((3 * H,)) * 0.1, jnp.float32)
    op = registry.get_op("gru_seq", "interpret")

    def trace():   # a fresh function: jit caches ignore the ambient mesh
        return str(jax.make_jaxpr(lambda *a: op(*a))(xp, h0, u, b))

    want = op(xp, h0, u, b)
    assert "shard_map" not in trace()
    with shd.use_mesh(host_mesh4):
        assert "shard_map" in trace()
        got = jax.jit(lambda *a: op(*a))(xp, h0, u, b)
        with pytest.raises(ValueError, match="does not divide"):
            op(xp[:, :6], h0[:6], u, b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_auto_backend_refuses_platforms_other_than_cpu_and_tpu(monkeypatch):
    """interpret is the CPU test platform's mapping only; on a TPU "auto"
    and "pallas" both mean the compiled kernels."""
    monkeypatch.setattr(registry, "_platform", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no kernel path"):
        registry.resolve_backend("auto")
    assert registry.resolve_backend("interpret") == "interpret"
    monkeypatch.setattr(registry, "_platform", lambda: "tpu")
    assert registry.resolve_backend("auto") == "pallas"
    assert registry.resolve_backend("pallas") == "pallas"


def test_default_backend_rebinding():
    registry.set_default_backend("ref")
    try:
        assert registry.resolve_backend(None) == "ref"
        assert registry.resolve_backend("auto") == "ref"
        assert registry.resolve_backend("interpret") == "interpret"
    finally:
        registry.set_default_backend("auto")


def test_public_wrappers_resolve_exclusively_through_registry():
    """Re-registering an op must intercept the public ops.py wrapper —
    proof there is no residual per-op dispatch path."""
    from repro.kernels.gru_cell import ops as gru_ops

    entry = registry._REGISTRY["gru_cell"]
    seen = []

    def fake_ref(x_proj, h, u, b, **kw):
        seen.append(x_proj.shape)
        return entry.ref(x_proj, h, u, b, **kw)

    registry.register_op("gru_cell", ref=fake_ref, pallas=entry.pallas)
    try:
        rng = np.random.default_rng(0)
        # unique shape so the wrapper's jit cache cannot serve a stale trace
        (xp, h, u, b), _ = _gru_cell_args(rng)
        xp, h = xp[:11], h[:11]
        out = gru_ops.gru_cell(xp, h, u, b, backend="ref")
        assert seen, "wrapper did not route through the registry"
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(entry.ref(xp, h, u, b)),
            rtol=1e-5, atol=1e-6)
    finally:
        registry.register_op("gru_cell", ref=entry.ref, pallas=entry.pallas)


def test_old_auto_interpret_helpers_are_gone():
    """The five copy-pasted per-op ``_auto_interpret`` dispatchers are gone;
    backend choice lives in the registry alone."""
    import repro.kernels.ctc_merge.ops as m1
    import repro.kernels.decode_attn.ops as m2
    import repro.kernels.gru_cell.ops as m3
    import repro.kernels.quant_matmul.ops as m4
    import repro.kernels.vote_cmp.ops as m5
    for mod in (m1, m2, m3, m4, m5):
        assert not hasattr(mod, "_auto_interpret"), mod.__name__


def test_default_backend_takes_effect_after_prior_trace():
    """Rebinding the default must not be defeated by a stale jit cache:
    the wrapper resolves the backend BEFORE its jit boundary."""
    from repro.kernels.gru_cell import ops as gru_ops

    rng = np.random.default_rng(7)
    (xp, h, u, b), _ = _gru_cell_args(rng)
    _ = gru_ops.gru_cell(xp, h, u, b)          # traces under the default

    entry = registry._REGISTRY["gru_cell"]
    calls = []

    def spy_ref(x_proj, hh, uu, bb_, **kw):
        calls.append("ref")
        return entry.ref(x_proj, hh, uu, bb_, **kw)

    registry.register_op("gru_cell", ref=spy_ref, pallas=entry.pallas)
    registry.set_default_backend("ref")
    try:
        _ = gru_ops.gru_cell(xp, h, u, b)      # SAME shapes as before
        assert calls == ["ref"], "stale trace served instead of new default"
    finally:
        registry.set_default_backend("auto")
        registry.register_op("gru_cell", ref=entry.ref, pallas=entry.pallas)
