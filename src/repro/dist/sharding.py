"""Logical-axis sharding: the contract between models and the mesh.

Model code never names mesh axes. It annotates tensors with *logical* dims —
``"dp"`` (batch / data parallel), ``"tp"`` (tensor / model parallel),
``"sp"`` (sequence parallel), ``"ep"`` (expert parallel), ``"zero"``
(optimizer-state partitioning) or ``None`` (replicated) — and this module
resolves them against whatever ``jax.sharding.Mesh`` is ambient:

======== ============================================ =====================
logical  resolves to mesh axes                        typical tensor dim
======== ============================================ =====================
``dp``   every batch-like axis (``pod``, ``data``)    batch
``tp``   the ``model`` axis                           heads / d_ff / vocab
``sp``   the ``model`` axis (same hardware, seq dim)  sequence
``ep``   the ``model`` axis                           experts
``zero`` batch-like + pipeline-stage axes (ZeRO-1)    largest divisible dim
======== ============================================ =====================

Resolution rules (all enforced by :func:`spec_for`):

1. **No mesh, no constraint** — with no ambient mesh every helper degrades
   to a no-op (``spec_for`` returns ``P()``, :func:`shard` returns its input
   unchanged), so the same model code runs on a laptop CPU.
2. **Divisibility** — a mesh axis is only assigned to a tensor dim whose
   size it divides; otherwise the axis is dropped for that dim (e.g. ``sp``
   on a length-1 decode step, or GQA kv-heads smaller than the model axis).
3. **First dim wins** — a mesh axis is used at most once per spec. When two
   logical dims map to the same axis (MoE's ``("ep", None, "tp")``) the
   first dim that passes rule 2 takes it and the other is replicated, which
   is exactly the EP-or-expert-internal-TP fallback the models document.

ZeRO-1: :func:`zero1_logical` upgrades a parameter's logical tuple by
assigning ``"zero"`` to the largest dim the DP axes divide (possibly
combining with an existing ``tp`` dim); :func:`spec_for_zero` resolves the
result. Gradients constrained to the ZeRO spec lower to reduce-scatters
instead of all-reduces.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


LogicalDim = Union[str, None, tuple]

# mesh-axis name classes; tests use ("data", "model") meshes and
# make_stage_mesh uses ("stage",) for the pipeline axis
_BATCH_AXES = ("pod", "data", "dp", "batch", "replica")
_MODEL_AXES = ("model", "tp", "mdl", "tensor")
_STAGE_AXES = ("stage", "pipe", "stages")

_tls = threading.local()


# ----------------------------------------------------------------------
# ambient mesh
# ----------------------------------------------------------------------
@contextlib.contextmanager
def set_mesh(mesh: Mesh):
    """``jax.set_mesh`` that also records ``mesh`` for :func:`ambient_mesh`.

    JAX hands out the concrete context mesh only outside ``jax.jit``
    (``jax.sharding.get_mesh``), and the model code asks for it while it
    is traced, so the mesh is kept in a thread-local as well.
    """
    prev = getattr(_tls, "mesh", None)
    _tls.mesh = mesh
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        _tls.mesh = prev


def ambient_mesh() -> Optional[Mesh]:
    """The mesh of the enclosing :func:`set_mesh` block, or None."""
    return getattr(_tls, "mesh", None)


# ----------------------------------------------------------------------
# logical-name -> mesh-axes resolution
# ----------------------------------------------------------------------
def axis_map(mesh: Optional[Mesh] = None) -> dict:
    """Map each logical name to the tuple of mesh axis names it may use."""
    mesh = mesh if mesh is not None else ambient_mesh()
    if mesh is None:
        return {}
    names = tuple(mesh.axis_names)
    batch = tuple(a for a in names if a in _BATCH_AXES)
    model = tuple(a for a in names if a in _MODEL_AXES)
    stage = tuple(a for a in names if a in _STAGE_AXES)
    # ZeRO shards optimizer state over DP replicas *and* the pipeline-stage
    # axis when one exists (the MeshBackend's ZeRO-1 layer); dp itself never
    # resolves to the stage axis — stages hold different micro-batches, not
    # replicas of the batch
    return {"dp": batch, "tp": model, "sp": model, "ep": model,
            "zero": batch + stage}


def axis_size(name: str, mesh: Optional[Mesh] = None) -> int:
    """Product of the mesh-axis sizes a logical name resolves to (1 if no
    mesh). Model code branches on this, e.g. ``heads_even`` checks
    ``n_heads % axis_size("tp")``."""
    mesh = mesh if mesh is not None else ambient_mesh()
    if mesh is None:
        return 1
    size = 1
    for a in axis_map(mesh).get(name, ()):
        size *= mesh.shape[a]
    return size


def _resolve_dim(names, dim_size: int, amap: dict, mesh: Mesh,
                 used: set) -> list:
    """Mesh axes for one tensor dim, honoring divisibility + first-dim-wins."""
    axes: list = []
    prod = 1
    for nm in names:
        for a in amap.get(nm, ()):
            if a in used or a in axes:
                continue
            sz = mesh.shape[a]
            if sz <= 1 or dim_size % (prod * sz):
                continue
            axes.append(a)
            prod *= sz
    return axes


def spec_for(shape: Sequence[int], logical: Sequence[LogicalDim],
             mesh: Optional[Mesh] = None) -> P:
    """Resolve a logical tuple against the mesh into a ``PartitionSpec``.

    ``logical`` entries may be a name, ``None``, or a tuple of names for a
    dim sharded over several logical axes (as :func:`zero1_logical` emits).
    With no mesh this returns ``P()`` (fully replicated).
    """
    mesh = mesh if mesh is not None else ambient_mesh()
    if mesh is None:
        return P()
    amap = axis_map(mesh)
    used: set = set()
    entries: list = []
    for dim_size, lg in zip(shape, logical):
        if lg is None:
            entries.append(None)
            continue
        names = tuple(lg) if isinstance(lg, (tuple, list)) else (lg,)
        axes = _resolve_dim(names, int(dim_size), amap, mesh, used)
        used.update(axes)
        if not axes:
            entries.append(None)
        elif len(axes) == 1:
            entries.append(axes[0])
        else:
            entries.append(tuple(axes))
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def shard(x: jax.Array, *logical: LogicalDim,
          mesh: Optional[Mesh] = None) -> jax.Array:
    """Annotate an activation with its logical placement.

    ``shard(h, "dp", "sp", None)`` constrains batch over the data axes and
    sequence over the model axis. Dims that fail divisibility are silently
    replicated (rule 2), and without an ambient mesh this is the identity —
    the property that lets one model source serve 1-device tests and
    multi-device meshes alike.
    """
    mesh = mesh if mesh is not None else ambient_mesh()
    if mesh is None:
        return x
    spec = spec_for(x.shape, logical, mesh)
    if not len(spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ----------------------------------------------------------------------
# ZeRO partitioning
# ----------------------------------------------------------------------
def zero1_logical(logical: Sequence[LogicalDim], shape: Sequence[int],
                  mesh: Optional[Mesh] = None) -> tuple:
    """Upgrade a parameter's logical tuple for ZeRO partitioning.

    Picks the largest *unsharded* dim the DP ("zero") axes divide and marks
    it ``"zero"``; if none qualifies, tries to co-shard an already
    ``tp``-sharded dim (entry becomes ``(name, "zero")``). If nothing
    divides — or there is no mesh — the tuple is returned unchanged and the
    optimizer state simply stays replicated over DP for that leaf.
    """
    logical = tuple(logical)
    mesh = mesh if mesh is not None else ambient_mesh()
    if mesh is None:
        return logical
    z = axis_size("zero", mesh)
    if z <= 1:
        return logical
    best = -1
    for i, (d, lg) in enumerate(zip(shape, logical)):
        if lg is None and d % z == 0 and (best < 0 or d > shape[best]):
            best = i
    if best >= 0:
        out = list(logical)
        out[best] = "zero"
        return tuple(out)
    for i, (d, lg) in enumerate(zip(shape, logical)):
        if isinstance(lg, str):
            t = axis_size(lg, mesh)
            if t > 0 and d % (t * z) == 0:
                out = list(logical)
                out[i] = (lg, "zero")
                return tuple(out)
    return logical


def spec_for_zero(shape: Sequence[int], zlogical: Sequence[LogicalDim],
                  mesh: Optional[Mesh] = None) -> P:
    """Resolve a :func:`zero1_logical` tuple into a ``PartitionSpec``.

    Identical resolution rules to :func:`spec_for`; kept as a separate entry
    point so call sites read as "this is the ZeRO layout" and so the two
    layouts can diverge later (e.g. hierarchical ZeRO over pods) without an
    API change.
    """
    return spec_for(shape, zlogical, mesh)
