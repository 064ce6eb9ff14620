"""Pass 2 — static validation of every registered Pallas kernel.

For each op in ``repro.kernels.registry`` the pass traces the Pallas
wrapper (interpret mode — tracing only, nothing executes) on the op's
declared ``example`` shapes and inspects the resulting ``pallas_call``
equations:

  kernel-signature   ref and pallas impls take the same positional args,
                     and pallas accepts the ``interpret`` keyword
  kernel-example     every op declares an ``example=`` factory (the
                     shapes this pass traces with)
  kernel-trace       the pallas impl actually lowers to >=1 pallas_call
  kernel-block-div   every BlockSpec block shape divides its (padded)
                     operand shape — the wrapper's padding contract
  kernel-tpu-tiling  every block's last two dims are divisible by
                     (8, 128) or equal the operand's own — the rule the
                     TPU compiler enforces (a 1-D block: 128 or full)
  kernel-grid        no degenerate (zero-sized) grid dimensions
  kernel-vmem        estimated VMEM residency (all operand blocks +
                     scratch) fits the per-core budget

The VMEM estimate is deliberately simple — one block per operand plus
declared scratch, no double-buffering factor — and errs permissive; its
job is catching order-of-magnitude mistakes (a whole-array block) at
review time, not replacing the Mosaic compiler's accounting.
"""
from __future__ import annotations

import functools
import inspect
import math
from typing import Any, List, Optional, Sequence

import jax

from repro.analysis import jaxpr_tools as jt
from repro.analysis.findings import Finding

#: Per-core VMEM budget the estimate is checked against (v4/v5 cores
#: carry 16 MiB; CPU interpret mode has no real limit but the kernels
#: must stay deployable).
VMEM_BUDGET_BYTES = 16 * 2 ** 20

#: (sublane, lane) tile the TPU compiler requires of a block's last two
#: dims unless they span the operand's whole extent.
TPU_TILE = (8, 128)


def _positional_names(fn: Any) -> List[str]:
    sig = inspect.signature(fn)
    return [p.name for p in sig.parameters.values()
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]


def _keyword_names(fn: Any) -> List[str]:
    sig = inspect.signature(fn)
    return [p.name for p in sig.parameters.values()
            if p.kind == p.KEYWORD_ONLY]


def check_signature_parity(name: str, ref: Any, pallas: Any
                           ) -> List[Finding]:
    """ref/pallas public signatures must agree on the data arguments."""
    findings: List[Finding] = []
    ref_pos, pal_pos = _positional_names(ref), _positional_names(pallas)
    if ref_pos != pal_pos:
        findings.append(Finding(
            "kernel-signature", name,
            f"ref takes positional args {ref_pos} but pallas takes "
            f"{pal_pos}; the registry swaps backends blindly, so data "
            "signatures must match exactly"))
    if "interpret" not in _keyword_names(pallas):
        findings.append(Finding(
            "kernel-signature", name,
            "pallas impl lacks the keyword-only 'interpret' argument the "
            "registry binds for the interpret backend"))
    return findings


def pallas_call_eqns(closed: Any) -> List[Any]:
    return [e for e in jt.iter_eqns(closed, into_kernels=True)
            if e.primitive.name == "pallas_call"]


def trace_pallas(entry: Any) -> Any:
    """Trace the op's pallas impl on its example shapes (no execution)."""
    args, kwargs = entry.example()
    fn = functools.partial(entry.pallas, interpret=True,  # repro: allow[lint-interpret-kwarg]
                           **kwargs)
    return jax.make_jaxpr(fn)(*args)


def _block_dims(block_shape: Sequence[Any]) -> List[int]:
    """Block extents: ``Blocked(n)`` as n, squeezed dims as 1."""
    dims = [getattr(b, "block_size", b) for b in block_shape]
    return [b if isinstance(b, int) else 1 for b in dims]


def _tiling_violation(blk: Sequence[int], shape: Sequence[int]) -> bool:
    """True when a block breaks the TPU's (8, 128)-or-full rule."""
    n = min(2, len(blk))
    tail = zip(blk[-n:], shape[-n:], TPU_TILE[-n:])
    return any(b != dim and b % tile for b, dim, tile in tail)


def check_pallas_eqn(eqn: Any, subject: str,
                     budget: int = VMEM_BUDGET_BYTES) -> List[Finding]:
    """Block divisibility, grid sanity, and the VMEM estimate for one
    ``pallas_call`` equation."""
    findings: List[Finding] = []
    gm = eqn.params["grid_mapping"]

    for gi, g in enumerate(gm.grid):
        if isinstance(g, int) and g <= 0:
            findings.append(Finding(
                "kernel-grid", subject,
                f"grid dim {gi} is {g}; every grid extent must be >= 1"))

    vmem = 0
    for bi, bm in enumerate(gm.block_mappings):
        shape = bm.array_aval.shape
        dtype = bm.array_aval.dtype
        blk = _block_dims(bm.block_shape)
        for d, (dim, b) in enumerate(zip(shape, blk)):
            if b <= 0 or dim % b != 0:
                findings.append(Finding(
                    "kernel-block-div", subject,
                    f"operand {bi}: block shape {tuple(blk)} does not "
                    f"divide operand shape {tuple(shape)} at dim {d} "
                    f"({dim} % {b} != 0); pad the operand to a tile "
                    "multiple in the wrapper before pallas_call"))
                break
        if blk and _tiling_violation(blk, shape):
            findings.append(Finding(
                "kernel-tpu-tiling", subject,
                f"operand {bi}: block shape {tuple(blk)} over operand "
                f"{tuple(shape)}: the last two block dims must be "
                f"divisible by {TPU_TILE} or equal the operand's, or the "
                "TPU compiler refuses the kernel; block over rows in "
                "sublane multiples or use the whole dim"))
        vmem += math.prod(blk) * dtype.itemsize

    # declared scratch lives in VMEM for the kernel's whole lifetime
    n_scratch = getattr(gm, "num_scratch_operands", 0)
    if n_scratch:
        kjaxpr = eqn.params.get("jaxpr")
        if kjaxpr is not None:
            for v in kjaxpr.invars[len(kjaxpr.invars) - n_scratch:]:
                aval = getattr(v, "aval", None)
                shape = getattr(aval, "shape", None)
                dtype = getattr(aval, "dtype", None)
                if shape is not None and dtype is not None:
                    vmem += math.prod(shape) * dtype.itemsize

    if vmem > budget:
        findings.append(Finding(
            "kernel-vmem", subject,
            f"estimated VMEM residency {vmem / 2**20:.1f} MiB exceeds the "
            f"{budget / 2**20:.0f} MiB per-core budget; shrink the block "
            "shapes or stage through scratch"))
    return findings


def run(ops: Optional[Sequence[str]] = None,
        budget: int = VMEM_BUDGET_BYTES,
        disable: Sequence[str] = ()) -> List[Finding]:
    """Run every kernel check over every (or the given) registered op."""
    from repro.kernels import registry

    findings: List[Finding] = []
    names = tuple(ops) if ops is not None else registry.list_ops()
    for name in names:
        entry = registry._ensure(name)
        findings += check_signature_parity(name, entry.ref, entry.pallas)
        if entry.example is None:
            findings.append(Finding(
                "kernel-example", name,
                "no example= factory registered; register_op(..., "
                "example=lambda: (args, kwargs)) so analysis can trace "
                "the kernel on representative shapes"))
            continue
        closed = trace_pallas(entry)
        eqns = pallas_call_eqns(closed)
        if not eqns:
            findings.append(Finding(
                "kernel-trace", name,
                "tracing the pallas impl produced no pallas_call "
                "equation; the 'pallas' backend for this op never runs "
                "a kernel"))
        for i, eqn in enumerate(eqns):
            subject = name if len(eqns) == 1 else f"{name}#{i}"
            findings += check_pallas_eqn(eqn, subject, budget)
    return [f for f in findings if f.rule not in disable]
