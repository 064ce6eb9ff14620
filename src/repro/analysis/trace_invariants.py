"""Pass 1 — invariants over the jitted serving traces.

Builds the repo's serving traces (pipeline decode/fused, both engines'
step — each with and without an ambient mesh) via ``jax.make_jaxpr`` and
runs every trace rule on each.  Rules read *declared intent*:

* packed parameters are the trace's leading invars (``n_param_leaves``),
* sharding stage boundaries are declared by ``stage:<name>`` scopes
  (``BasecallPipeline.decode_stage_boundaries`` /
  ``models.basecaller.serving_stage_boundaries``),
* sanctioned dequant sites carry the ``repro.core.quant.DEQUANT_SCOPE``
  named scope.

All traces use the "ref" backend: the reference path exposes the full
dataflow to the walker, whereas interpret mode hides arithmetic inside
``pallas_call`` kernel bodies that dataflow analysis deliberately skips
(kernel bodies get their own pass).

Rule catalog (see docs/analysis.md):
  trace-weight-quant    no weight-quantization reachable from packed params
  trace-dequant         int8/int16 -> float only under the dequant scope
  trace-f64             no float64 anywhere in a serving trace
  trace-host-transfer   no host callbacks / device transfers in traces
  trace-stage-coverage  every declared boundary constrained under a mesh
  trace-mesh-bake       zero sharding constraints in a mesh-free trace
  trace-retrace         same-aval second call hits the jit cache
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp

from repro.analysis import jaxpr_tools as jt
from repro.analysis.findings import Finding


@dataclasses.dataclass
class TraceCase:
    """One serving trace plus its declared intent."""
    name: str
    closed: "jex_core.ClosedJaxpr"
    n_param_leaves: int
    boundaries: Tuple[str, ...] = ()
    meshed: bool = False


def _mesh_ctx(mesh):
    from repro.dist import sharding as shd
    if mesh is None:
        return contextlib.nullcontext()
    return shd.use_mesh(mesh)


def default_mesh():
    """A 1-D data mesh over all local devices (None when single-device)."""
    devs = jax.devices()
    if len(devs) < 2:
        return None
    from repro.dist.sharding import make_mesh
    return make_mesh((len(devs),), ("data",))


# ---------------------------------------------------------------------------
# trace-case builders
# ---------------------------------------------------------------------------

def _tiny_pipe(preset: str):
    from repro.core.quant import QuantConfig
    from repro.pipeline import BasecallPipeline

    pipe = BasecallPipeline.from_preset(
        preset, scale="tiny",
        quant=QuantConfig(enabled=True, bits_w=5, bits_a=5),
        backend="ref", beam_width=3, packed=True)
    pipe.init_params(jax.random.PRNGKey(0))
    return pipe


def _tag(preset: str, mesh) -> str:
    return f"[{preset}{'/mesh' if mesh is not None else ''}]"


def build_pipeline_cases(preset: str = "guppy",
                         mesh=None) -> List[TraceCase]:
    """The pipeline's two jitted serving surfaces (decode + fused)."""
    pipe = _tiny_pipe(preset)
    packed = pipe.serving_params()
    n = len(jax.tree_util.tree_leaves(packed))
    B = 4  # divisible by every host-device mesh CI uses
    windows = jnp.zeros((B, pipe.mcfg.input_len, 1), jnp.float32)
    lengths = jnp.full((B,), pipe.mcfg.input_len, jnp.int32)
    batch = jnp.zeros((B, pipe.mcfg.input_len + 2 * pipe.scfg.margin, 1),
                      jnp.float32)
    with _mesh_ctx(mesh):
        decode = jax.make_jaxpr(pipe._build_decode_windows())(
            packed, windows, lengths)
        fused = jax.make_jaxpr(pipe._build_windows_fused())(packed, batch)
    meshed = mesh is not None
    return [
        TraceCase(f"pipeline.decode_windows{_tag(preset, mesh)}", decode, n,
                  pipe.decode_stage_boundaries(), meshed),
        TraceCase(f"pipeline.windows_fused{_tag(preset, mesh)}", fused, n,
                  pipe.fused_stage_boundaries(), meshed),
    ]


def build_basecall_engine_case(mesh=None) -> TraceCase:
    """BasecallEngine.step's decode trace at engine capacity (B*dp)."""
    from repro.serve.basecall_engine import BasecallEngine

    pipe = _tiny_pipe("guppy")
    with _mesh_ctx(mesh):
        eng = BasecallEngine(pipe, batch_slots=2)
        packed = pipe.serving_params()
        windows = jnp.zeros((eng.B, pipe.mcfg.input_len, 1), jnp.float32)
        lengths = jnp.full((eng.B,), pipe.mcfg.input_len, jnp.int32)
        closed = jax.make_jaxpr(pipe._build_decode_windows())(
            packed, windows, lengths)
    n = len(jax.tree_util.tree_leaves(packed))
    return TraceCase(f"basecall_engine.step{_tag('guppy', mesh)}", closed, n,
                     pipe.decode_stage_boundaries(), mesh is not None)


def _tiny_lm_cfg():
    from repro.core.quant import QuantConfig
    from repro.models import lm as lm_lib

    return lm_lib.LMConfig(
        n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
        vocab_size=64, quant=QuantConfig(enabled=True, bits_w=5, bits_a=5),
        remat=False)


def build_lm_engine_case(mesh=None) -> TraceCase:
    """ServingEngine's jitted decode step over the packed LM artifact.

    Under an ambient mesh the (B,) step batch dp-shards lane-major
    (``models.decode.lm_stage_boundaries`` declares the constrained
    stages), mirroring the basecall engine's step."""
    from repro.models import decode as decode_lib
    from repro.models import lm as lm_lib
    from repro.serve.engine import ServingEngine

    cfg = _tiny_lm_cfg()
    params = lm_lib.init_lm(jax.random.PRNGKey(0), cfg)
    with _mesh_ctx(mesh):
        eng = ServingEngine(params, cfg, batch_slots=2, max_len=16)
        tokens = jnp.zeros((eng.B,), jnp.int32)
        active = jnp.ones((eng.B,), bool)
        closed = jax.make_jaxpr(eng._decode)(
            eng.params, eng.cache, tokens, active)
    n = len(jax.tree_util.tree_leaves(eng.params))
    return TraceCase(f"serving_engine.step{_tag('lm', mesh)}", closed, n,
                     decode_lib.lm_stage_boundaries(), mesh is not None)


def build_paged_lm_engine_case(mesh=None) -> TraceCase:
    """ServingEngine's decode step on the PAGED KV layout (block-table
    gathers through the pooled arena; same declared stage boundaries as
    the dense step)."""
    from repro.models import decode as decode_lib
    from repro.models import lm as lm_lib
    from repro.serve.engine import ServingEngine

    cfg = _tiny_lm_cfg()
    params = lm_lib.init_lm(jax.random.PRNGKey(0), cfg)
    with _mesh_ctx(mesh):
        eng = ServingEngine(params, cfg, batch_slots=2, max_len=16,
                            kv_layout="paged", kv_block=4)
        tokens = jnp.zeros((eng.B,), jnp.int32)
        active = jnp.ones((eng.B,), bool)
        closed = jax.make_jaxpr(eng._decode)(
            eng.params, eng.cache, tokens, active, eng._ship_tables())
    n = len(jax.tree_util.tree_leaves(eng.params))
    return TraceCase(f"serving_engine.step{_tag('lm-paged', mesh)}", closed,
                     n, decode_lib.lm_stage_boundaries(), mesh is not None)


def build_cases(presets: Sequence[str] = ("guppy", "chiron"),
                mesh=None) -> List[TraceCase]:
    """Every serving trace the rules run on, unmeshed + meshed."""
    cases: List[TraceCase] = []
    for preset in presets:
        cases += build_pipeline_cases(preset, None)
    cases.append(build_basecall_engine_case(None))
    cases.append(build_lm_engine_case(None))
    cases.append(build_paged_lm_engine_case(None))
    if mesh is not None:
        cases += build_pipeline_cases(presets[0], mesh)
        cases.append(build_basecall_engine_case(mesh))
        cases.append(build_lm_engine_case(mesh))
        cases.append(build_paged_lm_engine_case(mesh))
    return cases


# ---------------------------------------------------------------------------
# trace rules
# ---------------------------------------------------------------------------

def rule_weight_quant(case: TraceCase) -> List[Finding]:
    eqns = jt.weight_quant_eqns(case.closed, case.n_param_leaves)
    if not eqns:
        return []
    return [Finding(
        "trace-weight-quant", case.name,
        f"{len(eqns)} weight-quantization op(s) reachable from the serving "
        f"params (first: {jt.describe_eqn(eqns[0])}); serve the "
        "quantize-once packed artifact instead of re-deriving codes "
        "in-trace (docs/analysis.md#trace-weight-quant)")]


def rule_dequant(case: TraceCase) -> List[Finding]:
    eqns = jt.unsanctioned_dequant_eqns(case.closed)
    if not eqns:
        return []
    return [Finding(
        "trace-dequant", case.name,
        f"{len(eqns)} int8/int16->float convert(s) outside the declared "
        f"dequant boundary (first: {jt.describe_eqn(eqns[0])}); wrap the "
        "sanctioned site in jax.named_scope(quant.DEQUANT_SCOPE) or stop "
        "dequantizing codes there (docs/analysis.md#trace-dequant)")]


def rule_f64(case: TraceCase) -> List[Finding]:
    eqns = jt.f64_eqns(case.closed)
    if not eqns:
        return []
    return [Finding(
        "trace-f64", case.name,
        f"{len(eqns)} float64-producing op(s) in a serving trace (first: "
        f"{jt.describe_eqn(eqns[0])}); serving numerics are fp32/int8 "
        "only")]


def rule_host_transfer(case: TraceCase) -> List[Finding]:
    eqns = jt.host_transfer_eqns(case.closed)
    if not eqns:
        return []
    return [Finding(
        "trace-host-transfer", case.name,
        f"host callback / device transfer inside the trace: "
        f"{sorted({e.primitive.name for e in eqns})}; serving steps must "
        "stay on-device end to end")]


def rule_sharding(case: TraceCase) -> List[Finding]:
    if not case.meshed:
        n = jt.count_primitive(case.closed, "sharding_constraint")
        if n:
            return [Finding(
                "trace-mesh-bake", case.name,
                f"{n} sharding_constraint op(s) in a MESH-FREE trace: an "
                "ambient mesh was baked at trace time and would outlive "
                "its use_mesh block (docs/analysis.md#trace-mesh-bake)")]
        return []
    realized = jt.stage_boundary_names(case.closed)
    missing = [b for b in case.boundaries if not realized.get(b)]
    if missing:
        return [Finding(
            "trace-stage-coverage", case.name,
            f"declared stage boundaries carry no sharding constraint "
            f"under the mesh: {missing}; add shd.constrain under "
            "jax.named_scope('stage:<name>') at each, or update the "
            "boundary declaration (docs/analysis.md#trace-stage-coverage)")]
    return []


TRACE_RULES: Dict[str, Callable[[TraceCase], List[Finding]]] = {
    "trace-weight-quant": rule_weight_quant,
    "trace-dequant": rule_dequant,
    "trace-f64": rule_f64,
    "trace-host-transfer": rule_host_transfer,
    "trace-sharding": rule_sharding,  # emits stage-coverage / mesh-bake
}


# ---------------------------------------------------------------------------
# retrace guard (the one rule that must EXECUTE the jitted fns)
# ---------------------------------------------------------------------------

def retrace_findings(mesh=None) -> List[Finding]:
    """Same-aval second calls must hit the jit cache (no silent retrace)."""
    found: List[Finding] = []

    pipe = _tiny_pipe("guppy")
    packed = pipe.serving_params()
    windows = jnp.zeros((4, pipe.mcfg.input_len, 1), jnp.float32)
    lengths = jnp.full((4,), pipe.mcfg.input_len, jnp.int32)
    fn = pipe._build_decode_windows()
    with _mesh_ctx(mesh):
        fn(packed, windows, lengths)
        fn(packed, windows, lengths)
    n = fn._cache_size()
    if n != 1:
        found.append(Finding(
            "trace-retrace", f"pipeline.decode_windows{_tag('guppy', mesh)}",
            f"two same-aval calls compiled {n} jit entries (expected 1): "
            "an unhashable/unstable static argument or weak-type flap is "
            "forcing retraces"))

    from repro.core.quant import QuantConfig
    from repro.models import lm as lm_lib
    from repro.serve.engine import ServingEngine

    cfg = lm_lib.LMConfig(
        n_layers=1, d_model=16, n_heads=2, n_kv_heads=2, d_ff=32,
        vocab_size=32, quant=QuantConfig(enabled=True, bits_w=5, bits_a=5),
        remat=False)
    params = lm_lib.init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, batch_slots=2, max_len=8)
    tokens = jnp.zeros((eng.B,), jnp.int32)
    active = jnp.ones((eng.B,), bool)
    # _decode donates the cache: thread the returned cache into call 2
    _, cache = eng._decode(eng.params, eng.cache, tokens, active)
    eng._decode(eng.params, cache, tokens, active)
    n = eng._decode._cache_size()
    if n != 1:
        found.append(Finding(
            "trace-retrace", "serving_engine.step[lm]",
            f"two same-aval calls compiled {n} jit entries (expected 1)"))

    # paged layout: block tables ship with a FIXED (B, max_blocks) shape
    # precisely so lane growth never retraces — guard that here
    eng_p = ServingEngine(params, cfg, batch_slots=2, max_len=8,
                          kv_layout="paged", kv_block=4)
    bt = eng_p._ship_tables()
    _, cache = eng_p._decode(eng_p.params, eng_p.cache, tokens, active, bt)
    eng_p._decode(eng_p.params, cache, tokens, active, bt)
    n = eng_p._decode._cache_size()
    if n != 1:
        found.append(Finding(
            "trace-retrace", "serving_engine.step[lm-paged]",
            f"two same-aval calls compiled {n} jit entries (expected 1)"))

    found += _multitenant_retrace(mesh)
    return found


def _multitenant_retrace(mesh=None) -> List[Finding]:
    """PER-MODEL retrace guard for the multi-tenant engine: every hosted
    tenant's group sub-batch has a fixed shape, so two engine steps must
    leave each model's decode with exactly one jit entry — a tenant whose
    lane batch flaps avals would retrace on every step of a fleet."""
    import numpy as np

    from repro.serve.api import BasecallRequest
    from repro.serve.multitenant import MultiModelBasecallEngine
    from repro.serve.registry import ModelRegistry

    found: List[Finding] = []
    reg = ModelRegistry()
    pipes = {}
    for mid, preset in (("small", "guppy"), ("large", "chiron")):
        pipes[mid] = _tiny_pipe(preset)
        reg.register_basecaller(mid, pipes[mid])
    with _mesh_ctx(mesh):
        eng = MultiModelBasecallEngine(reg, tuple(pipes), batch_slots=2)
    for rid, (mid, pipe) in enumerate(pipes.items()):
        sig = np.zeros((2 * pipe.mcfg.input_len,), np.float32)
        eng.submit(eng.make_request(rid, BasecallRequest(signal=sig,
                                                         model=mid)))
    eng.admit()
    eng.step()
    eng.step()
    for mid, pipe in pipes.items():
        fn = pipe._decode_windows.cache.get(eng.mesh)
        n = -1 if fn is None else fn._cache_size()
        if n != 1:
            where = "never ran" if fn is None else f"compiled {n} jit entries"
            found.append(Finding(
                "trace-retrace",
                f"multitenant.step[{mid}{'/mesh' if mesh else ''}]",
                f"two same-aval engine steps for hosted model {mid!r} "
                f"{where} (expected exactly 1): the tenant's group "
                "sub-batch must keep a fixed shape across steps"))
    return found


# ---------------------------------------------------------------------------
# pass entry point
# ---------------------------------------------------------------------------

def run(presets: Sequence[str] = ("guppy", "chiron"), mesh=None,
        disable: Sequence[str] = (),
        with_retrace: bool = True) -> List[Finding]:
    """Run every trace rule over every serving trace case."""
    findings: List[Finding] = []
    for case in build_cases(presets, mesh):
        for rule_name, rule in TRACE_RULES.items():
            if rule_name in disable:
                continue
            findings += rule(case)
    if with_retrace and "trace-retrace" not in disable:
        findings += retrace_findings(mesh)
    # rule fns may emit sub-rule names (stage-coverage/mesh-bake); apply
    # disable to those too
    return [f for f in findings if f.rule not in disable]
