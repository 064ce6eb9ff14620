"""Central kernel backend registry — ONE dispatch point for all Pallas ops.

Every accelerated op in the repo is registered here under a name with two
implementations:

  ref     — pure-jnp oracle, identical public signature (differentiable,
            runs anywhere; also the numerics ground truth in tests)
  pallas  — the tiled Pallas TPU kernel behind its padding wrapper; takes
            an ``interpret`` keyword so the same body executes on CPU

and callers resolve a concrete callable with::

    op = registry.get_op("quant_matmul", backend="auto")

Backends:
  auto       pallas on TPU, interpret on the CPU test platform; any other
             platform is an error, never a silent fallback
  pallas     compiled Pallas kernel (TPU only: off-TPU it raises rather
             than run the kernel in interpret mode)
  interpret  Pallas kernel body on the interpreter (CPU-testable)
  ref        the jnp oracle

``Backend`` is the value models/pipeline code threads around: a frozen,
hashable switch (safe as a jit static argument) whose ``op(name)`` resolves
through this registry.  ``set_default_backend`` rebinds what "auto" means
process-wide (benchmarks ``--backend``, CI).

Ops register themselves at import of their ``ops.py``; ``get_op`` lazily
imports the owning module so callers never need kernel-package imports.

Under an ambient ``dist.sharding.use_mesh`` mesh, an op that declares its
``batch_axes`` runs its Pallas kernel once per data-parallel shard
(``shard_map`` over the logical "dp" axis): XLA cannot partition a Mosaic
kernel itself, and every registered row is independent of the others, so
mapping the kernel over shards is the same computation.  The choice is
made when the op is called, under the mesh ambient at that trace.
"""
from __future__ import annotations

import dataclasses
import difflib
import functools
import importlib
import os
from typing import Any, Callable, Dict, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

BACKENDS = ("auto", "pallas", "interpret", "ref")

# op name -> module that registers it (lazy import on first get_op)
_OP_MODULES = {
    "quant_matmul": "repro.kernels.quant_matmul.ops",
    "gru_cell": "repro.kernels.gru_cell.ops",
    "gru_seq": "repro.kernels.gru_seq.ops",
    "beam_merge_multiframe": "repro.kernels.beam_strip.ops",
    "masked_logsumexp": "repro.kernels.ctc_merge.ops",
    "beam_merge_topk": "repro.kernels.ctc_merge.ops",
    "decode_attn": "repro.kernels.decode_attn.ops",
    "paged_decode_attn": "repro.kernels.decode_attn.ops",
    "mismatch_bits": "repro.kernels.vote_cmp.ops",
}


@dataclasses.dataclass(frozen=True)
class OpEntry:
    name: str
    ref: Callable
    pallas: Callable      # must accept an ``interpret: bool`` keyword
    # zero-argument factory returning ``(args, kwargs)`` exercising the op
    # on representative (deliberately ragged) shapes; used by
    # ``repro.analysis.kernel_checks`` to trace the kernel statically
    example: Optional[Callable] = None
    # (per positional arg, per output) the axis along which rows are
    # independent, None for an argument every row shares; set, it lets
    # the Pallas kernel run per dp shard under a mesh (see module doc)
    batch_axes: Optional[Tuple[Tuple, Any]] = None


_REGISTRY: Dict[str, OpEntry] = {}

# ``REPRO_DEFAULT_BACKEND`` seeds what "auto" means for the process (the CI
# backend matrix sets it); validated lazily at FIRST USE so a bad value
# produces one clear ValueError from the resolving call site instead of an
# opaque import-time failure in whatever module touched the registry first.
_default_backend: Optional[str] = None


def register_op(name: str, *, ref: Callable, pallas: Callable,
                example: Optional[Callable] = None,
                batch_axes: Optional[Tuple[Tuple, Any]] = None) -> None:
    """Register (or re-register) an op's reference + Pallas implementations.

    Called at import time by each kernel package's ``ops.py`` (see
    ``docs/kernels.md`` for the add-an-op walkthrough).

    Args:
        name: the registry key callers resolve with :func:`get_op`.
        ref: pure-jnp oracle — identical public signature, runs anywhere,
            and is the numerics ground truth in tests.
        pallas: the Pallas kernel wrapper; must accept an
            ``interpret: bool`` keyword (the registry supplies it for the
            "interpret" backend).
        example: zero-argument factory returning ``(args, kwargs)`` on
            representative shapes — lets ``repro.analysis`` (and other
            tooling) trace the op without knowing its signature.
        batch_axes: ``(in_axes, out_axes)`` — for each positional
            argument, and for each output (same tree as the result), the
            axis along which rows are independent, or None for an
            argument every row shares.  Declared, the Pallas kernel runs
            per data-parallel shard under an ambient mesh.

    Returns:
        None.

    Example::

        register_op("my_op", ref=my_op_ref, pallas=my_op_pallas,
                    example=lambda: ((jnp.zeros((3, 5)),), {}))
    """
    prev = _REGISTRY.get(name)
    if prev is not None:   # re-registration (tests) keeps the declarations
        example = example if example is not None else prev.example
        batch_axes = batch_axes if batch_axes is not None \
            else prev.batch_axes
    _REGISTRY[name] = OpEntry(name=name, ref=ref, pallas=pallas,
                              example=example, batch_axes=batch_axes)


def list_ops() -> tuple:
    """All registered op names (forces registration of the known set)."""
    for name in _OP_MODULES:
        _ensure(name)
    return tuple(sorted(_REGISTRY))


def set_default_backend(backend: str) -> None:
    """Process-wide backend used when callers pass backend=None/"auto"."""
    global _default_backend
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
    _default_backend = backend


def get_default_backend() -> str:
    """The process default backend, seeding ``REPRO_DEFAULT_BACKEND``.

    The env value is validated HERE, on first use: a typo like
    ``REPRO_DEFAULT_BACKEND=cuda`` raises one actionable ValueError from
    the call that first resolves a backend, not an import-time crash and
    not a shape error deep in kernel dispatch.
    """
    global _default_backend
    if _default_backend is None:
        env = os.environ.get("REPRO_DEFAULT_BACKEND", "auto")
        if env not in BACKENDS:
            raise ValueError(
                f"REPRO_DEFAULT_BACKEND={env!r} is not a known backend; "
                f"expected one of {BACKENDS}")
        _default_backend = env
    return _default_backend


def _platform() -> str:
    return jax.default_backend()


def resolve_backend(backend: Optional[str] = None) -> str:
    """None/"auto" -> the concrete backend for this process/host.

    "auto" is "pallas" on a TPU and "interpret" on the CPU test platform.
    Asking for "pallas" off-TPU — or "auto" on any other platform —
    raises instead of quietly running the kernel bodies in interpret
    mode, so a run never reports interpreter numbers as the device's."""
    b = backend or get_default_backend()
    if b == "auto":
        b = get_default_backend()
    platform = _platform()
    if b == "auto":
        if platform == "tpu":
            return "pallas"
        if platform == "cpu":
            return "interpret"
        raise RuntimeError(
            f"backend 'auto' has no kernel path on platform {platform!r}: "
            "Pallas kernels compile for TPU only; pass backend='ref' (or "
            "'interpret' to debug the kernel bodies)")
    if b not in BACKENDS:
        raise ValueError(f"unknown backend {b!r}; one of {BACKENDS}")
    if b == "pallas" and platform != "tpu":
        raise RuntimeError(
            f"backend 'pallas' needs a TPU but JAX runs on {platform!r}; "
            "use backend='interpret' to run the kernel bodies on the CPU "
            "or 'ref' for the jnp oracles")
    return b


def _ensure(name: str) -> OpEntry:
    if name not in _REGISTRY:
        mod = _OP_MODULES.get(name)
        if mod is not None:
            importlib.import_module(mod)
    if name not in _REGISTRY:
        close = difflib.get_close_matches(name, list(_OP_MODULES), n=1)
        hint = f"; did you mean {close[0]!r}?" if close else ""
        raise KeyError(f"unknown op {name!r}{hint} "
                       f"(known: {sorted(set(_REGISTRY) | set(_OP_MODULES))})")
    return _REGISTRY[name]


def get_op(name: str, backend: Optional[str] = None) -> Callable:
    """Resolve an op to a concrete callable for ``backend``.

    Args:
        name: a registered op name (``list_ops()`` enumerates them; the
            owning kernel module is imported lazily on first use).
        backend: "auto" | "pallas" | "interpret" | "ref", or None for the
            process default (``set_default_backend`` /
            ``REPRO_DEFAULT_BACKEND``).

    Returns:
        The op's concrete callable: the jnp oracle for "ref", otherwise
        the Pallas wrapper with ``interpret`` pre-bound.

    Raises:
        KeyError: unknown op name (with a did-you-mean hint).

    Example::

        qmm = get_op("quant_matmul", backend="interpret")
    """
    entry = _ensure(name)
    b = resolve_backend(backend)
    if b == "ref":
        return entry.ref
    kernel = functools.partial(entry.pallas, interpret=(b == "interpret"))
    if entry.batch_axes is None:
        return kernel
    return functools.partial(_per_dp_shard, kernel, *entry.batch_axes)


def _per_dp_shard(kernel: Callable, in_axes: Tuple, out_axes: Any,
                  *args, **kwargs):
    """``kernel(*args, **kwargs)``, run once per data-parallel shard when
    an ambient mesh has a "dp" axis (see the module docstring)."""
    from repro.dist import sharding as shd

    mesh = shd.get_mesh()
    dp = None if mesh is None else shd.logical_spec(("dp",), mesh)[0]
    if dp is None:
        return kernel(*args, **kwargs)
    n = shd.dp_size(mesh)
    for a, ax in zip(args, in_axes):
        if ax is not None and a.shape[ax] % n:
            raise ValueError(
                f"batch dim {a.shape[ax]} (axis {ax} of a {a.shape} "
                f"operand) does not divide the {n} data-parallel devices")

    def spec(ax, ndim):
        return P(*(dp if d == ax else None for d in range(ndim)))

    fn = functools.partial(kernel, **kwargs)
    out = jax.eval_shape(fn, *args)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(P() if ax is None else spec(ax, a.ndim)
                       for a, ax in zip(args, in_axes)),
        out_specs=jax.tree_util.tree_map(lambda ax, o: spec(ax, o.ndim),
                                         out_axes, out),
        axis_names=set(dp if isinstance(dp, tuple) else (dp,)),
        check_vma=False)(*args)


@dataclasses.dataclass(frozen=True)
class Backend:
    """The single compute-backend switch threaded through models/pipeline.

    Frozen + hashable so it can ride through jit static arguments.  ``mode``
    is a registry backend name; ``op(name)`` resolves through the registry
    at trace time.
    """
    mode: str = "auto"

    def __post_init__(self):
        if self.mode not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.mode!r}; one of {BACKENDS}")

    def op(self, name: str) -> Callable:
        """Resolve op ``name`` through the registry on this backend."""
        return get_op(name, self.mode)

    @property
    def resolved(self) -> str:
        return resolve_backend(self.mode)
