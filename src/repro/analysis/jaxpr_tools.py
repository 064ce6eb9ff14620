"""Shared jaxpr-walking library: THE one implementation in the repo.

Everything here operates on ``jax.extend.core.ClosedJaxpr`` / ``Jaxpr``
objects produced by ``jax.make_jaxpr``; nothing executes.  The walkers
recurse into higher-order primitives (``pjit``, ``scan``, ``cond``,
``while``) via the ``ClosedJaxpr`` values found in ``eqn.params``.  Raw
Pallas kernel jaxprs (``pallas_call``'s ``jaxpr`` param) operate on
*refs* whose invars do not align positionally with the call's operands,
so dataflow walkers deliberately stop at the ``pallas_call`` boundary —
kernel bodies get their own pass (``repro.analysis.kernel_checks``).

Two taint engines live here:

* **weight taint** (`weight_quant_eqns`): seeds taint from the packed
  serving-parameter invars and flags quantization arithmetic
  ({round, clamp, reduce_max}, or converts to int8/int16) reachable from
  them.  This is the "quantize-once" invariant from PR 3: packing is a
  host-side artifact step, so a serving trace re-deriving codes from
  weights is a regression.
* **code taint** (`unsanctioned_dequant_eqns`): seeds taint from int8 /
  int16 "code" values and flags integer→float converts fed by them that
  are not under a ``jax.named_scope`` whose name contains the declared
  dequant scope (``repro.core.quant.DEQUANT_SCOPE``).  This pins WHERE
  codes are allowed to materialize as floats: the two reference
  dequant-matmul epilogues, nowhere else.

Both engines use the same sub-jaxpr operand alignment: jax's
higher-order primitives pass operands to the sub-jaxpr as a suffix of
``eqn.invars`` (scan prepends consts/carry, pjit is 1:1), so sub-invar
``i`` maps to ``eqn.invars[i + (len(eqn.invars) - len(sub.invars))]``.
Taint flows out when the sub-jaxpr's outvars align 1:1 with the
equation's outvars (true for pjit/scan/cond on every traced path here).
"""
from __future__ import annotations

import re
from typing import Any, Dict, Iterator, List, Optional, Sequence, Set

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np

from repro.core.quant import DEQUANT_SCOPE

#: Primitives that implement fake-quant rounding/clipping/range-finding.
#: Identical to the set the PR-3 packed tests enforced.
QUANT_PRIMS = frozenset({"round", "clamp", "reduce_max"})

#: Integer dtypes that carry quantized codes in this codebase.
CODE_DTYPES = (jnp.int8.dtype, jnp.int16.dtype)

#: Primitives that move data across the host boundary or between
#: devices outside the partitioner's control.  None may appear inside a
#: serving trace.
HOST_TRANSFER_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "host_callback_call", "infeed", "outfeed",
    "device_put",
})

_STAGE_RE = re.compile(r"stage:([A-Za-z0-9_]+)")


# ---------------------------------------------------------------------------
# structural helpers
# ---------------------------------------------------------------------------

def as_jaxpr(obj: Any) -> "jex_core.Jaxpr":
    """Accept a ClosedJaxpr or Jaxpr and return the raw Jaxpr."""
    return obj.jaxpr if isinstance(obj, jex_core.ClosedJaxpr) else obj


def sub_closed_jaxprs(eqn: Any) -> List["jex_core.ClosedJaxpr"]:
    """Sub-jaxprs of a higher-order equation (pjit/scan/cond/while...).

    Only ``ClosedJaxpr`` params count: ``pallas_call`` stores a raw
    ``Jaxpr`` over refs whose invars do not align with the operands, so
    it is intentionally excluded from dataflow recursion.
    """
    subs: List[jex_core.ClosedJaxpr] = []
    for val in eqn.params.values():
        items = val if isinstance(val, (list, tuple)) else (val,)
        for item in items:
            if isinstance(item, jex_core.ClosedJaxpr):
                subs.append(item)
    return subs


def iter_eqns(jaxpr: Any, *, into_kernels: bool = False) -> Iterator[Any]:
    """Yield every equation, recursing through sub-jaxprs.

    With ``into_kernels=True`` also descends into raw Pallas kernel
    jaxprs — safe for per-equation predicates (dtype scans, primitive
    counts) though not for dataflow.
    """
    for eqn in as_jaxpr(jaxpr).eqns:
        yield eqn
        for sub in sub_closed_jaxprs(eqn):
            yield from iter_eqns(sub.jaxpr, into_kernels=into_kernels)
        if into_kernels:
            for val in eqn.params.values():
                if isinstance(val, jex_core.Jaxpr):
                    yield from iter_eqns(val, into_kernels=True)


def count_primitive(jaxpr: Any, name: str) -> int:
    """Number of equations (recursively) whose primitive is ``name``."""
    return sum(1 for e in iter_eqns(jaxpr) if e.primitive.name == name)


def primitive_counts(jaxpr: Any) -> Dict[str, int]:
    """Histogram of primitive names over the whole (recursive) trace."""
    counts: Dict[str, int] = {}
    for e in iter_eqns(jaxpr):
        counts[e.primitive.name] = counts.get(e.primitive.name, 0) + 1
    return counts


def name_stack_of(eqn: Any) -> str:
    """The ``jax.named_scope`` stack recorded on an equation ('' if none)."""
    si = getattr(eqn, "source_info", None)
    return str(getattr(si, "name_stack", "") or "")


def stage_boundary_names(jaxpr: Any) -> Dict[str, int]:
    """Declared stage boundaries realized in a trace.

    Returns ``{stage_name: count}`` over all ``sharding_constraint``
    equations whose name stack contains a ``stage:<name>`` scope — the
    mechanism model/pipeline code uses to declare where a sharding
    boundary is *intended* (see ``docs/analysis.md``).
    """
    names: Dict[str, int] = {}
    for e in iter_eqns(jaxpr):
        if e.primitive.name != "sharding_constraint":
            continue
        for m in _STAGE_RE.finditer(name_stack_of(e)):
            names[m.group(1)] = names.get(m.group(1), 0) + 1
    return names


def _var_dtype(v: Any) -> Optional[Any]:
    aval = getattr(v, "aval", None)
    return getattr(aval, "dtype", None)


def _nonliteral(vs: Sequence[Any]) -> List[Any]:
    return [v for v in vs if not isinstance(v, jex_core.Literal)]


# ---------------------------------------------------------------------------
# weight taint: no re-quantization reachable from packed params
# ---------------------------------------------------------------------------

def is_quant_eqn(eqn: Any) -> bool:
    """Quantization arithmetic: fake-quant rounding/clipping/range ops,
    or a convert to a code dtype (int8/int16)."""
    name = eqn.primitive.name
    if name in QUANT_PRIMS:
        return True
    if name == "convert_element_type":
        return eqn.params.get("new_dtype") in CODE_DTYPES
    return False


def _align_sub_taint(eqn: Any, sub: "jex_core.ClosedJaxpr",
                     tainted: Set[Any]) -> Set[Any]:
    """Map taint from eqn operands onto sub-jaxpr invars (suffix-aligned)."""
    sub_taint: Set[Any] = set()
    offset = len(eqn.invars) - len(sub.jaxpr.invars)
    for i, sv in enumerate(sub.jaxpr.invars):
        j = i + offset
        if 0 <= j < len(eqn.invars):
            ov = eqn.invars[j]
            if not isinstance(ov, jex_core.Literal) and ov in tainted:
                sub_taint.add(sv)
    return sub_taint


def _outvar_taint(jaxpr: "jex_core.Jaxpr",
                  tainted: Set[Any]) -> List[bool]:
    """One extra linear weight-only pass, then report outvar taint."""
    tainted = set(tainted)
    for eqn in jaxpr.eqns:
        invars = _nonliteral(eqn.invars)
        if invars and all(v in tainted for v in invars):
            for ov in eqn.outvars:
                tainted.add(ov)
    return [not isinstance(v, jex_core.Literal) and v in tainted
            for v in jaxpr.outvars]


def collect_weight_quant(jaxpr: "jex_core.Jaxpr",
                         tainted: Set[Any]) -> List[Any]:
    """Equations doing quantization arithmetic on *weight-only* values.

    A value is weight-only when every non-literal input deriving it is
    weight-only (mixing in an activation clears the taint — activation
    packing legitimately keeps its round/clamp ops).  Mutates
    ``tainted``; returns the offending equations (empty ⇒ the
    quantize-once invariant holds).
    """
    found: List[Any] = []
    for eqn in jaxpr.eqns:
        invars = _nonliteral(eqn.invars)
        all_w = bool(invars) and all(v in tainted for v in invars)
        for sub in sub_closed_jaxprs(eqn):
            sub_taint = _align_sub_taint(eqn, sub, tainted)
            found.extend(collect_weight_quant(sub.jaxpr, sub_taint))
            if len(sub.jaxpr.outvars) == len(eqn.outvars):
                for ov, t in zip(eqn.outvars,
                                 _outvar_taint(sub.jaxpr, sub_taint)):
                    if t:
                        tainted.add(ov)
        if all_w:
            if is_quant_eqn(eqn):
                found.append(eqn)
            for ov in eqn.outvars:
                tainted.add(ov)
    return found


def weight_quant_eqns(closed: "jex_core.ClosedJaxpr",
                      n_param_leaves: int) -> List[Any]:
    """Quantization equations reachable from the first ``n_param_leaves``
    invars of a trace — the flattened parameter pytree when parameters
    are the callable's first argument (the convention of every serving
    entry point here).  Empty ⇒ the quantize-once invariant holds."""
    tainted: Set[Any] = set(closed.jaxpr.invars[:n_param_leaves])
    return collect_weight_quant(closed.jaxpr, tainted)


# ---------------------------------------------------------------------------
# code taint: int8/int16 -> float only inside the declared dequant scope
# ---------------------------------------------------------------------------

def _dequant_walk(jaxpr: "jex_core.Jaxpr", tainted: Set[Any],
                  scope: str) -> List[Any]:
    found: List[Any] = []
    for v in jaxpr.invars:
        dt = _var_dtype(v)
        if dt is not None and dt in CODE_DTYPES:
            tainted.add(v)
    for eqn in jaxpr.eqns:
        in_tainted = any(v in tainted for v in _nonliteral(eqn.invars))
        for sub in sub_closed_jaxprs(eqn):
            sub_taint = _align_sub_taint(eqn, sub, tainted)
            found.extend(_dequant_walk(sub.jaxpr, sub_taint, scope))
            if len(sub.jaxpr.outvars) == len(eqn.outvars):
                for ov, t in zip(eqn.outvars,
                                 _outvar_taint(sub.jaxpr, sub_taint)):
                    if t:
                        tainted.add(ov)
        if eqn.primitive.name == "convert_element_type":
            out_dt = _var_dtype(eqn.outvars[0])
            if out_dt in CODE_DTYPES:
                # producing codes (activation packing) is fine and
                # taints the result
                tainted.add(eqn.outvars[0])
            elif in_tainted and out_dt is not None:
                if jnp.issubdtype(out_dt, jnp.floating):
                    if scope not in name_stack_of(eqn):
                        found.append(eqn)
                    # sanctioned or not, the float result exits taint
                elif jnp.issubdtype(out_dt, jnp.integer):
                    # int8 -> int32 widening keeps carrying codes
                    tainted.add(eqn.outvars[0])
        elif in_tainted:
            for ov in eqn.outvars:
                dt = _var_dtype(ov)
                if (dt is not None and jnp.issubdtype(dt, jnp.integer)
                        and not jnp.issubdtype(dt, jnp.bool_)):
                    tainted.add(ov)
    return found


def unsanctioned_dequant_eqns(closed: Any, *,
                              scope: str = DEQUANT_SCOPE) -> List[Any]:
    """Integer→float converts fed by int8/int16 code values that are NOT
    under a ``named_scope`` containing ``scope``.  Taint propagates only
    through integer-dtype results (comparisons etc. drop it), so the
    declared dequant epilogue is the taint's only sanctioned float exit.
    """
    return _dequant_walk(as_jaxpr(closed), set(), scope)


# ---------------------------------------------------------------------------
# simple per-equation scans
# ---------------------------------------------------------------------------

def f64_eqns(jaxpr: Any) -> List[Any]:
    """Equations producing float64 anywhere in the trace (kernels too)."""
    f64 = np.dtype("float64")
    found = []
    for e in iter_eqns(jaxpr, into_kernels=True):
        for v in e.outvars:
            dt = _var_dtype(v)
            if dt is not None and dt == f64:
                found.append(e)
                break
    return found


def host_transfer_eqns(jaxpr: Any) -> List[Any]:
    """Host-callback / transfer primitives anywhere in the trace."""
    return [e for e in iter_eqns(jaxpr, into_kernels=True)
            if e.primitive.name in HOST_TRANSFER_PRIMS]


def kernel_launch_count(jaxpr: Any) -> int:
    """Static count of Pallas kernel launches one execution performs.

    Walks the trace multiplying each ``pallas_call`` by the trip counts
    of the ``scan`` loops enclosing it (``eqn.params["length"]``) — the
    number the persistent kernels exist to shrink: a per-step op under a
    T-step scan counts T launches, the fused walk counts 1.  ``cond``
    branches count as the worst case (max over branches); ``while`` trip
    counts are unknowable statically and count as 1 iteration (none of
    the serving traces here put kernels under ``while``).
    """
    total = 0
    for eqn in as_jaxpr(jaxpr).eqns:
        if eqn.primitive.name == "pallas_call":
            total += 1
            continue
        mult = 1
        if eqn.primitive.name == "scan":
            mult = int(eqn.params.get("length", 1))
        subs = sub_closed_jaxprs(eqn)
        if not subs:
            continue
        inner = (max(kernel_launch_count(s) for s in subs)
                 if eqn.primitive.name == "cond"
                 else sum(kernel_launch_count(s) for s in subs))
        total += mult * inner
    return total


def describe_eqn(eqn: Any) -> str:
    """Short human string for findings: primitive + dtypes + scope."""
    outs = ", ".join(str(_var_dtype(v)) for v in eqn.outvars)
    stack = name_stack_of(eqn)
    loc = f" in scope '{stack}'" if stack else ""
    return f"{eqn.primitive.name} -> ({outs}){loc}"
