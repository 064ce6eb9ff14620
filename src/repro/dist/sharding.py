"""Logical-axis sharding: one vocabulary ("dp", "tp") over many meshes.

Models and launch code never name physical mesh axes.  They speak two
logical axes:

  "dp" — the batch/data direction.  Maps to every pure-data axis present
         on the mesh: ("pod", "data") on the 2-pod mesh, ("data",) on a
         single pod, () on a host mesh with no data axis.
  "tp" — the model/tensor direction.  Maps to ("model",) when present.

``use_mesh``/``get_mesh`` carry the ambient mesh (a plain context stack —
importing this module never touches jax device state), ``constrain``
applies a with_sharding_constraint and degrades to a no-op when no mesh is
active (CPU tests, single-host examples), and ``param_sharding_tree``
implements the path-name partitioning rules for parameter pytrees
(FSDP-style: last axis -> tp, first large axis -> dp; MoE expert tables
EP-shard over the model axis — see models/layers.moe_ff).
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# data-parallel-ish physical axes in priority order; "pod" is the pure-DP
# inter-pod axis of the 512-chip mesh (launch/mesh.py)
_DP_AXES = ("pod", "data")
_TP_AXIS = "model"

_state = threading.local()


def _stack():
    if not hasattr(_state, "meshes"):
        _state.meshes = []
    return _state.meshes


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` — the one mesh factory.

    ``constrain``/``replicate`` annotate values with
    ``with_sharding_constraint``, which only accepts ``Auto`` axes, while
    ``jax.make_mesh`` builds ``Explicit`` ones by default.  ``devices``
    picks the devices (default: all of them).
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         (jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def get_mesh() -> Optional[jax.sharding.Mesh]:
    """The innermost mesh installed by :func:`use_mesh`, or None."""
    stack = _stack()
    return stack[-1] if stack else None


@contextlib.contextmanager
def use_mesh(mesh: jax.sharding.Mesh):
    """Install ``mesh`` as the ambient mesh for ``constrain``/``get_mesh``.

    Everything downstream — model ``constrain`` calls, the pipeline's
    dp-sharded basecall path, engine slot scaling — keys off the ambient
    mesh, so a single ``with`` block turns the whole serving path
    multi-device without any API change at the call sites.

    Args:
        mesh: a ``jax.sharding.Mesh`` whose axis names the logical
            ``"dp"``/``"tp"`` vocabulary maps onto (``"pod"``/``"data"``
            are data-parallel, ``"model"`` is tensor-parallel) — or
            ``None`` to pin "no mesh", masking any outer ``use_mesh``
            (how the pipeline keeps a generator's device placement
            consistent with the mesh captured at its creation).

    Returns:
        A context manager yielding ``mesh``; on exit the previous ambient
        mesh (or none) is restored.  Nestable — the innermost mesh wins.

    Example::

        mesh = make_mesh((4,), ("data",))
        with use_mesh(mesh):
            result = pipe.basecall(signal)   # windows shard over "dp"
    """
    _stack().append(mesh)
    try:
        yield mesh
    finally:
        _stack().pop()


def _physical(logical_name, mesh):
    """One logical axis name -> physical axis (str | tuple | None)."""
    if logical_name is None:
        return None
    if logical_name == "dp":
        axes = tuple(a for a in _DP_AXES if a in mesh.axis_names)
        if not axes:
            return None
        return axes if len(axes) > 1 else axes[0]
    if logical_name == "tp":
        return _TP_AXIS if _TP_AXIS in mesh.axis_names else None
    # allow passing a physical axis name straight through
    return logical_name if logical_name in mesh.axis_names else None


def logical_spec(logical: Sequence, mesh) -> Tuple:
    """Map a tuple of logical axis names to physical mesh axes."""
    return tuple(_physical(a, mesh) for a in logical)


def dp_size(mesh: Optional[jax.sharding.Mesh] = None) -> int:
    """Device count behind the logical ``"dp"`` axis.

    Args:
        mesh: the mesh to inspect; defaults to the ambient :func:`use_mesh`
            mesh.

    Returns:
        The product of the mesh's data-parallel axis sizes, or ``1`` when
        no mesh is active (single-device paths stay untouched).
    """
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None:
        return 1
    ax = _physical("dp", mesh)
    return 1 if ax is None else _axis_size(mesh, ax)


def constrain(x, logical: Sequence, *, strict: bool = False):
    """``with_sharding_constraint`` under the ambient mesh.

    The single sharding annotation the models/pipeline speak: callers name
    logical axes ("dp"/"tp"), this maps them onto whatever physical mesh is
    ambient and degrades gracefully everywhere else.

    Args:
        x: the array to annotate.
        logical: one logical axis name (or ``None``) per dim of ``x``,
            e.g. ``("dp", None, None)`` to shard dim 0 over data-parallel
            devices.
        strict: when True, a sharded dim that does not divide its mesh-axis
            group raises a clear ``ValueError`` instead of silently
            skipping the constraint (the pipeline uses this so an
            indivisible window batch fails with a readable message, not an
            XLA shape crash deep inside GSPMD).

    Returns:
        ``x`` annotated with the resolved ``NamedSharding`` — or ``x``
        unchanged when no mesh is active, no logical axis resolves on this
        mesh, or (non-strict) a dim is indivisible.

    Example::

        windows = constrain(windows, ("dp", None, None))
    """
    mesh = get_mesh()
    if mesh is None:
        return x
    spec = logical_spec(logical, mesh)
    if all(a is None for a in spec):
        return x
    # only constrain when every sharded dim divides its axis group —
    # GSPMD handles padding, but uneven activation shards are never what
    # the rules here intend (smoke configs on production meshes).
    for dim, ax in zip(x.shape, spec):
        if ax is None:
            continue
        n = _axis_size(mesh, ax)
        if dim % n != 0:
            if strict:
                raise ValueError(
                    f"cannot shard dim of size {dim} over mesh axis "
                    f"{ax!r} ({n} devices): {dim} % {n} != 0. Pad the "
                    f"batch to a multiple of {n} or drop the mesh "
                    f"(shape={tuple(x.shape)}, logical={tuple(logical)})")
            return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def replicate(x):
    """All-gather ``x`` to fully-replicated under the ambient mesh.

    The pipeline applies this to per-window reads/lengths before the host
    stitch/vote, so every device (and the host) sees the complete window
    set.  No-op without an ambient mesh.
    """
    mesh = get_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))


def batch_sharding(mesh, ndim: int) -> NamedSharding:
    """``NamedSharding`` splitting dim 0 over logical "dp", rest replicated.

    What the pipeline/engines ``jax.device_put`` window batches with before
    a sharded decode step (dim 0 must divide :func:`dp_size` — the callers
    pad to a multiple first, or raise via strict :func:`constrain`).
    """
    spec = (_physical("dp", mesh),) + (None,) * (ndim - 1)
    return NamedSharding(mesh, P(*spec))


def _axis_size(mesh, axis) -> int:
    axes = axis if isinstance(axis, tuple) else (axis,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


# ---------------------------------------------------------------------------
# parameter partitioning by path name
# ---------------------------------------------------------------------------

def path_str(path) -> str:
    """jax key-path -> "a/0/b" style string (stable across jax versions)."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


#: sentinel logical "tuple" for rules that replicate a leaf on every dim
#: regardless of rank (the basecall serving artifact uses this — dp shards
#: windows, never weights)
REPLICATE = "replicate"

# (regex, logical tuple) pairs; first match wins.  The logical tuple is
# right-aligned against the param's trailing dims (scanned layer dims keep
# their leading None).
_DEFAULT_RULES: Tuple[Tuple[str, Tuple], ...] = (
    # MoE expert tables: EP over the model axis, per-expert ff over data
    # (2-D expert sharding; see models/layers.moe_ff docstring)
    (r"(^|/)moe/(w1|w3)$", ("tp", None, "dp")),
    (r"(^|/)moe/w2$", ("tp", "dp", None)),
    (r"(^|/)moe/(sw1|sw3|sw2|router)$", (None, "tp")),
    # embedding / head tables: FSDP over vocab, tp over d
    (r"(^|/)(embed|head)$", ("dp", "tp")),
)


def arch_overrides(cfg) -> Tuple[Tuple[str, Tuple], ...]:
    """Per-architecture extra rules, matched before the defaults."""
    rules = []
    if getattr(cfg, "tie_embeddings", False):
        # tied table doubles as the CE head: keep the vocab layout so the
        # head matmul contracts over the replicated d axis
        rules.append((r"(^|/)embed$", ("tp", None)))
    return tuple(rules)


def param_logical(path: str, ndim: int, scanned: bool,
                  overrides: Tuple[Tuple[str, Tuple], ...] = ()) -> Tuple:
    """Logical axes for one parameter leaf.

    Default rule: biases/scalars/norm gains replicate; matrices shard the
    last axis over "tp" and the first non-scanned axis over "dp" (FSDP).
    """
    eff = ndim - (1 if scanned else 0)       # dims the rules describe
    for pat, logical in tuple(overrides) + _DEFAULT_RULES:
        if re.search(pat, path):
            if logical == REPLICATE:
                return (None,) * ndim
            if len(logical) != eff:
                continue
            return (None,) * (ndim - eff) + tuple(logical)
    if eff <= 1:
        return (None,) * ndim
    logical = [None] * eff
    logical[-1] = "tp"
    logical[0] = "dp"
    return (None,) * (ndim - eff) + tuple(logical)


def param_sharding_tree(shapes, mesh, overrides=()):
    """ShapeDtypeStruct tree -> NamedSharding tree by path-name rules.

    A sharded dim that does not divide its mesh-axis group falls back to
    replicated on that dim (smoke configs lowering on big meshes).
    """
    def f(path, leaf):
        s = path_str(path)
        logical = param_logical(s, leaf.ndim, "blocks" in s, overrides)
        spec = list(logical_spec(logical, mesh))
        for i, (dim, ax) in enumerate(zip(leaf.shape, spec)):
            if ax is not None and dim % _axis_size(mesh, ax) != 0:
                spec[i] = None
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(f, shapes)


def replicated_sharding_tree(tree, mesh):
    """Sharding tree that fully replicates every leaf of ``tree`` on ``mesh``.

    :func:`param_sharding_tree` under a match-everything :data:`REPLICATE`
    rule — how the dp-sharded basecall path places its ``PackedParams``
    serving artifact (every device holds the whole model; only the window
    batch is split).
    """
    return param_sharding_tree(tree, mesh, overrides=((r"", REPLICATE),))
