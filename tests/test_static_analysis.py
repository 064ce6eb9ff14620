"""repro.analysis: clean on the repo, and every rule fires on its mutant.

Three layers:

1. Positive controls — the shipped traces/kernels/tree produce ZERO
   findings (the CI gate ``python -m repro.analysis --strict`` relies on
   this staying true).
2. Negative paths — each violation class is planted (unpacked params in
   a serving trace, dropped constrain, stray pallas_call, indivisible
   block shape, ...) and the matching rule must catch it with an
   actionable message.
3. The registry first-use backend validation satellite (bad
   ``REPRO_DEFAULT_BACKEND`` must NOT crash import, must raise a listed
   ValueError at first resolve).
"""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.analysis import jaxpr_tools as jt  # noqa: E402
from repro.analysis import kernel_checks as kc  # noqa: E402
from repro.analysis import repolint  # noqa: E402
from repro.analysis import trace_invariants as ti  # noqa: E402
from repro.analysis.findings import ERROR, WARNING, Finding, errors  # noqa: E402
from repro.core.quant import QuantConfig  # noqa: E402
from repro.kernels import registry  # noqa: E402
from repro.kernels.registry import Backend  # noqa: E402
from repro.models import basecaller as bc  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
QUANT = QuantConfig(enabled=True, bits_w=5, bits_a=5)


def _env(**extra):
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}
    env.update(extra)
    return env


# ---------------------------------------------------------------------------
# Pass 1: trace invariants
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def meshed_cases(host_mesh4):
    """The guppy serving traces under the 4-way mesh (built once)."""
    return ti.build_pipeline_cases("guppy", host_mesh4)


def test_repo_serving_traces_clean(meshed_cases):
    """Every trace rule is silent on the shipped serving traces."""
    cases = ti.build_pipeline_cases("guppy", None) + list(meshed_cases)
    cases.append(ti.build_lm_engine_case(None))
    cases.append(ti.build_paged_lm_engine_case(None))
    for case in cases:
        for name, rule in ti.TRACE_RULES.items():
            assert rule(case) == [], (case.name, name)


def test_weight_quant_rule_fires_on_unpacked_serving_trace():
    """Mutant: serving the FLOAT checkpoint re-quantizes weights in-trace."""
    cfg = bc.tiny_preset("guppy").with_quant(QUANT)
    params = bc.init_basecaller(jax.random.PRNGKey(0), cfg)
    sig = jnp.zeros((2, cfg.input_len, 1), jnp.float32)
    closed = jax.make_jaxpr(
        lambda p, s: bc.apply_basecaller(p, s, cfg, backend=Backend("ref"))
    )(params, sig)
    case = ti.TraceCase("mutant.unpacked", closed,
                        len(jax.tree_util.tree_leaves(params)))
    fs = ti.rule_weight_quant(case)
    assert len(fs) == 1
    assert "weight-quantization" in fs[0].message
    assert "quantize-once" in fs[0].message            # actionable fix


def test_stage_coverage_rule_fires_on_dropped_constrain(host_mesh4):
    """Mutant: a declared boundary whose constrain was dropped — modeled
    by the training forward (no constrains at all) traced under the mesh
    with the serving boundaries declared."""
    cfg = bc.tiny_preset("guppy").with_quant(QUANT)
    params = bc.init_basecaller(jax.random.PRNGKey(0), cfg)
    sig = jnp.zeros((4, cfg.input_len, 1), jnp.float32)
    from repro.dist import sharding as shd
    with shd.use_mesh(host_mesh4):
        closed = jax.make_jaxpr(
            lambda p, s: bc.apply_basecaller(p, s, cfg, backend=None)
        )(params, sig)
    case = ti.TraceCase("mutant.dropped_constrain", closed, 0,
                        boundaries=bc.serving_stage_boundaries(cfg),
                        meshed=True)
    fs = ti.rule_sharding(case)
    assert len(fs) == 1 and fs[0].rule == "trace-stage-coverage"
    assert "signal_in" in fs[0].message                # names the boundary
    assert "shd.constrain" in fs[0].message            # actionable fix


def test_stage_coverage_rule_fires_on_partial_drop(meshed_cases):
    """A single extra declared-but-unrealized boundary is reported."""
    good = meshed_cases[0]
    assert ti.rule_sharding(good) == []
    bad = dataclasses.replace(good,
                              boundaries=good.boundaries + ("attn0",))
    fs = ti.rule_sharding(bad)
    assert len(fs) == 1 and "attn0" in fs[0].message


def test_mesh_bake_rule_fires_on_meshed_trace_marked_unmeshed(meshed_cases):
    """Mutant: sharding constraints baked where no mesh is expected."""
    baked = dataclasses.replace(meshed_cases[0], meshed=False)
    fs = ti.rule_sharding(baked)
    assert len(fs) == 1 and fs[0].rule == "trace-mesh-bake"
    assert "use_mesh" in fs[0].message


def test_dequant_rule_fires_outside_scope_only():
    """int8 codes -> float is flagged everywhere EXCEPT under the
    declared dequant scope."""
    codes = jnp.zeros((4, 4), jnp.int8)

    leaky = jax.make_jaxpr(lambda q: q.astype(jnp.float32) * 0.1)(codes)
    assert len(jt.unsanctioned_dequant_eqns(leaky)) == 1

    def sanctioned(q):
        from repro.core.quant import DEQUANT_SCOPE
        with jax.named_scope(DEQUANT_SCOPE):
            return q.astype(jnp.float32) * 0.1

    assert jt.unsanctioned_dequant_eqns(
        jax.make_jaxpr(sanctioned)(codes)) == []

    # widening int8 -> int32 keeps carrying the taint through arithmetic
    def widened(q):
        return (q.astype(jnp.int32) @ q.astype(jnp.int32).T
                ).astype(jnp.float32)

    assert len(jt.unsanctioned_dequant_eqns(
        jax.make_jaxpr(widened)(codes))) == 1

    # packing a float INTO codes is not dequantization
    packed = jax.make_jaxpr(
        lambda x: jnp.round(x * 10).astype(jnp.int8))(jnp.zeros((4,)))
    assert jt.unsanctioned_dequant_eqns(packed) == []


def test_f64_and_host_transfer_rules_fire():
    jax.config.update("jax_enable_x64", True)
    try:
        closed = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2)(jnp.zeros((2,)))
    finally:
        jax.config.update("jax_enable_x64", False)
    assert len(jt.f64_eqns(closed)) >= 1

    def cb(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    closed = jax.make_jaxpr(cb)(jnp.zeros((2,)))
    assert len(jt.host_transfer_eqns(closed)) == 1


def test_retrace_guard_clean_on_repo():
    assert ti.retrace_findings(None) == []


def test_walker_counts_through_higher_order_prims():
    """count_primitive recurses into scan/cond/pjit sub-jaxprs."""

    def fn(x):
        def body(c, _):
            return jnp.sin(c), None
        y, _ = jax.lax.scan(body, x, None, length=3)
        return jax.lax.cond(y.sum() > 0,
                            lambda v: jnp.sin(v), lambda v: v, y)

    closed = jax.make_jaxpr(jax.jit(fn))(jnp.zeros((2,)))
    assert jt.count_primitive(closed, "sin") == 2      # scan body + branch
    counts = jt.primitive_counts(closed)
    assert counts["scan"] == 1 and counts["cond"] == 1


# ---------------------------------------------------------------------------
# Pass 2: kernel checks
# ---------------------------------------------------------------------------

def test_kernel_checks_clean_on_registry():
    assert kc.run() == []


def _bad_blockspec_trace():
    from jax.experimental import pallas as pl

    def k(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def bad(x):
        return pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct((10, 8), jnp.float32),
            grid=(2,),
            in_specs=[pl.BlockSpec((3, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((3, 8), lambda i: (i, 0)),
            interpret=True)(x)

    return jax.make_jaxpr(bad)(jnp.zeros((10, 8), jnp.float32))


def test_block_divisibility_rule_fires():
    """Mutant: a (3, 8) block over a (10, 8) operand."""
    eqns = kc.pallas_call_eqns(_bad_blockspec_trace())
    assert len(eqns) == 1
    fs = [f for f in kc.check_pallas_eqn(eqns[0], "mutant")
          if f.rule == "kernel-block-div"]
    assert fs and "10 % 3" in fs[0].message
    assert "pad the operand" in fs[0].message          # actionable fix


def test_tpu_tiling_rule_fires_on_unit_row_strip_block():
    """Mutant: the old beam-strip layout — a (1, 1, A) log-prob block over
    (B, F, A) and (1, W) state blocks over (B, W).  Every block divides
    its operand, yet the TPU compiler refuses it: the last two dims are
    neither (8, 128)-aligned nor the operand's own."""
    from jax.experimental import pallas as pl

    B, F, A, W = 4, 8, 5, 5

    def k(lp_ref, st_ref, o_ref):
        o_ref[...] = st_ref[...] + lp_ref[0][:, :W]

    def old_strip(lp, st):
        return pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct((B, W), jnp.float32),
            grid=(B, F),
            in_specs=[pl.BlockSpec((1, 1, A), lambda b, f: (b, f, 0)),
                      pl.BlockSpec((1, W), lambda b, f: (b, 0))],
            out_specs=pl.BlockSpec((1, W), lambda b, f: (b, 0)),
            interpret=True)(lp, st)

    closed = jax.make_jaxpr(old_strip)(jnp.zeros((B, F, A), jnp.float32),
                                       jnp.zeros((B, W), jnp.float32))
    fs = kc.check_pallas_eqn(kc.pallas_call_eqns(closed)[0], "mutant")
    assert not [f for f in fs if f.rule == "kernel-block-div"]
    tiling = [f for f in fs if f.rule == "kernel-tpu-tiling"]
    assert len(tiling) == 3                    # lp, state in, state out
    assert "(1, 1, 5)" in tiling[0].message and "(8, 128)" in tiling[0].message


def test_vmem_budget_rule_fires():
    eqns = kc.pallas_call_eqns(_bad_blockspec_trace())
    fs = [f for f in kc.check_pallas_eqn(eqns[0], "mutant", budget=4)
          if f.rule == "kernel-vmem"]
    assert fs and "budget" in fs[0].message


def test_signature_parity_rule_fires():
    def ref_impl(a, b):
        return a + b

    def pallas_impl(a, c, *, interpret=False):
        return a + c

    fs = kc.check_signature_parity("mutant", ref_impl, pallas_impl)
    assert len(fs) == 1 and "positional args" in fs[0].message

    def pallas_no_interp(a, b):
        return a + b

    fs = kc.check_signature_parity("mutant", ref_impl, pallas_no_interp)
    assert len(fs) == 1 and "interpret" in fs[0].message


def test_missing_example_flagged():
    entry = registry._REGISTRY["gru_cell"] if "gru_cell" in \
        registry._REGISTRY else registry._ensure("gru_cell")
    registry._REGISTRY["tmp_op"] = dataclasses.replace(
        entry, name="tmp_op", example=None)
    try:
        fs = kc.run(ops=("tmp_op",))
        assert len(fs) == 1 and fs[0].rule == "kernel-example"
        assert "register_op" in fs[0].message
    finally:
        del registry._REGISTRY["tmp_op"]


def test_example_survives_reregistration():
    """Tests that swap impls (spies) must not lose the example factory."""
    entry = registry._ensure("gru_cell")
    assert entry.example is not None
    registry.register_op("gru_cell", ref=entry.ref, pallas=entry.pallas)
    try:
        assert registry._REGISTRY["gru_cell"].example is entry.example
    finally:
        registry._REGISTRY["gru_cell"] = entry


# ---------------------------------------------------------------------------
# Pass 3: repo lint (planted trees under tmp_path)
# ---------------------------------------------------------------------------

def _plant(tmp_path: Path, files: dict) -> Path:
    for rel, body in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(body))
    return tmp_path


def test_lint_clean_on_repo():
    assert repolint.run(REPO) == []


def test_stray_pallas_call_flagged(tmp_path):
    root = _plant(tmp_path, {"src/repro/rogue.py": """
        from jax.experimental import pallas as pl

        def f(x):
            return pl.pallas_call(lambda i, o: None, out_shape=x)(x)
        """})
    fs = repolint.run(root)
    assert [f.rule for f in fs] == ["lint-pallas-call"]
    assert fs[0].subject == "src/repro/rogue.py:5"
    assert "registry.get_op" in fs[0].message          # actionable fix


def test_kernel_internal_import_flagged(tmp_path):
    root = _plant(tmp_path, {"src/repro/rogue.py": """
        import repro.kernels.gru_cell.ref
        from repro.kernels.quant_matmul import kernel
        from repro.kernels.registry import get_op        # allowed
        from repro.kernels.quant_matmul.ops import qmm_packed  # allowed
        """})
    fs = repolint.run(root)
    assert sorted(f.subject for f in fs) == ["src/repro/rogue.py:2",
                                             "src/repro/rogue.py:3"]
    assert all(f.rule == "lint-kernel-import" for f in fs)


def test_interpret_kwarg_flagged_and_suppressible(tmp_path):
    root = _plant(tmp_path, {"src/repro/rogue.py": """
        def f(op, x):
            return op(x, interpret=True)

        def g(op, x):
            return op(x, interpret=True)  # repro: allow[lint-interpret-kwarg]
        """})
    fs = repolint.run(root)
    assert [f.subject for f in fs] == ["src/repro/rogue.py:3"]
    assert fs[0].rule == "lint-interpret-kwarg"


def test_public_wrapper_interpret_param_flagged(tmp_path):
    root = _plant(tmp_path, {"src/repro/kernels/myop/ops.py": """
        __all__ = ["myop"]

        def myop(x, *, interpret=False):
            return x

        def _impl_pallas(x, *, interpret=False):   # private: allowed
            return x
        """})
    fs = repolint.run(root)
    rules = [f.rule for f in fs]
    assert "lint-wrapper-interpret" in rules
    wrapper = [f for f in fs if f.rule == "lint-wrapper-interpret"]
    assert len(wrapper) == 1 and "myop()" in wrapper[0].message


def test_registry_completeness_flags_missing_pieces(tmp_path):
    root = _plant(tmp_path, {
        "src/repro/kernels/newop/ops.py": """
            from repro.kernels import registry
            registry.register_op("newop", ref=None, pallas=None)
            """,
        "tests/test_other.py": "def test_nothing():\n    pass\n",
    })
    fs = repolint.run(root)
    rules = sorted(f.rule for f in fs)
    assert rules == ["lint-registry-complete"] * 3     # ref.py, kernel.py,
    msgs = " ".join(f.message for f in fs)             # test coverage
    assert "ref.py" in msgs and "kernel.py" in msgs and "tests/" in msgs


# ---------------------------------------------------------------------------
# registry backend validation (the bugfix satellite)
# ---------------------------------------------------------------------------

def test_bad_env_backend_errors_at_first_use_not_import():
    """REPRO_DEFAULT_BACKEND=cuda: importing the registry (and the kernel
    modules registering into it) must succeed; the FIRST backend resolve
    raises one ValueError naming the env var and the valid backends."""
    probe = textwrap.dedent("""
        import repro.kernels.registry as r
        import repro.kernels.gru_cell.ops          # registration is fine
        try:
            r.get_op("gru_cell")
            print("NO_ERROR")
        except ValueError as e:
            msg = str(e)
            assert "REPRO_DEFAULT_BACKEND" in msg, msg
            assert "'cuda'" in msg, msg
            assert "interpret" in msg, msg          # lists BACKENDS
            print("FIRST_USE_OK")
        # an explicit backend never touches the env default
        r.get_op("gru_cell", "ref")
        print("EXPLICIT_OK")
    """)
    r = subprocess.run(
        [sys.executable, "-c", probe], capture_output=True, text=True,
        cwd=REPO, env=_env(REPRO_DEFAULT_BACKEND="cuda"), timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "FIRST_USE_OK" in r.stdout
    assert "EXPLICIT_OK" in r.stdout


def test_good_env_backend_still_honored():
    probe = ("import repro.kernels.registry as r; "
             "print(r.resolve_backend(None), r.resolve_backend('auto'))")
    r = subprocess.run(
        [sys.executable, "-c", probe], capture_output=True, text=True,
        cwd=REPO, env=_env(REPRO_DEFAULT_BACKEND="ref"), timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == ["ref", "ref"]


def test_set_default_backend_invalid_lists_backends():
    with pytest.raises(ValueError, match="interpret"):
        registry.set_default_backend("cuda")
    with pytest.raises(ValueError, match="interpret"):
        registry.resolve_backend("cuda")


# ---------------------------------------------------------------------------
# findings plumbing + CLI
# ---------------------------------------------------------------------------

def test_findings_severity_and_disable():
    fs = [Finding("a-rule", "s", "m", ERROR),
          Finding("b-rule", "s", "m", WARNING)]
    assert errors(fs) == [fs[0]]
    assert errors(fs, strict=True) == fs
    from repro.analysis.findings import drop_disabled
    assert drop_disabled(fs, ["a-rule"]) == [fs[1]]


def test_cli_list_rules_and_bad_pass():
    from repro.analysis import cli
    assert cli.main(["--list-rules"]) == 0
    assert cli.main(["--passes", "nope"]) == 2


def test_cli_lint_pass_subprocess_clean_and_fails_on_mutant(tmp_path):
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--passes", "lint",
         "--strict"],
        capture_output=True, text=True, cwd=REPO, env=_env(), timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    assert "OK" in r.stdout

    _plant(tmp_path, {"src/repro/rogue.py": """
        def f(op, x):
            return op(x, interpret=True)
        """})
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--passes", "lint",
         "--root", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO, env=_env(), timeout=300)
    assert r.returncode == 1, r.stdout + r.stderr[-2000:]
    assert "lint-interpret-kwarg" in r.stdout
    # --disable waives exactly that rule
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--passes", "lint",
         "--root", str(tmp_path), "--disable", "lint-interpret-kwarg"],
        capture_output=True, text=True, cwd=REPO, env=_env(), timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
