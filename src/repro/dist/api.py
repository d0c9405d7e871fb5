"""Mesh context + logical-axis resolution + activation-sharding hints.

Model code names LOGICAL axes ("data", "model", "stage"); this module maps
them onto whatever mesh is active.  The "data" logical axis composes the
"pod" and "data" mesh axes when both exist (multi-pod batch/FSDP sharding —
see launch.mesh), so the same constrain() calls serve the 16x16 single-pod
and 2x16x16 multi-pod meshes unchanged.

Outside a ``use_mesh`` context every hint is a no-op — single-device smoke
tests run the exact same model code as the 512-chip dry-run.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# logical axis -> candidate mesh axes, in composition (major-to-minor) order
_LOGICAL_AXES = {
    "data": ("pod", "data"),
    "model": ("model",),
    "stage": ("stage",),
}

_state = threading.local()


def _translation(mesh: Mesh) -> dict[str, Any]:
    """Logical name -> mesh axis name (or tuple of names when composed)."""
    present = set(mesh.axis_names)
    tr: dict[str, Any] = {}
    for logical, cands in _LOGICAL_AXES.items():
        axes = tuple(a for a in cands if a in present)
        if axes:
            tr[logical] = axes[0] if len(axes) == 1 else axes
    return tr


def _current() -> tuple[Mesh, dict[str, Any]] | None:
    """The active (mesh, logical-axis translation), or None outside."""
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Activate ``mesh`` for constrain()/resolve_spec() in this thread.

    Composes with jax's own mesh context: ``with use_mesh(mesh), mesh:``.
    """
    prev = getattr(_state, "ctx", None)
    _state.ctx = (mesh, _translation(mesh))
    try:
        yield mesh
    finally:
        _state.ctx = prev


def _axes_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def resolve_spec(spec: tuple, shape: tuple) -> tuple:
    """Map a logical spec onto the active mesh with divisibility fallback.

    Per dimension: the logical entry resolves to its mesh axes; axes are
    dropped (major first) until the dimension extent divides the remaining
    axes' total size, degrading to None (replicated) when nothing fits.
    An entry naming a mesh axis directly passes through the same check.
    Unknown entries and all entries outside a mesh context resolve to None.
    """
    ctx = _current()
    if ctx is None:
        return tuple(None for _ in spec)
    mesh, tr = ctx
    present = set(mesh.axis_names)
    out: list[Any] = []
    used: set[str] = set()
    for entry, dim in zip(spec, shape):
        if entry is None:
            out.append(None)
            continue
        mapped = tr.get(entry, entry if entry in present else None)
        if mapped is None:
            out.append(None)
            continue
        axes = mapped if isinstance(mapped, tuple) else (mapped,)
        axes = tuple(a for a in axes if a not in used)
        while axes and (dim % _axes_size(mesh, axes) or dim == 0):
            axes = axes[1:]                 # drop the major axis, try again
        if not axes:
            out.append(None)
            continue
        used.update(axes)
        out.append(axes[0] if len(axes) == 1 else axes)
    return tuple(out)


def constrain(x: jax.Array, spec: tuple) -> jax.Array:
    """Sharding hint: with_sharding_constraint under an active mesh, else id.

    ``spec`` names logical axes; entries that don't resolve (axis absent
    from the mesh, or extent not divisible) fall back to replicated for
    that dimension, so the hint never fails on small/debug meshes.
    """
    ctx = _current()
    if ctx is None:
        return x
    mesh, _ = ctx
    resolved = resolve_spec(spec, x.shape)
    if all(e is None for e in resolved):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*resolved)))


def mesh_ndev(mesh: Mesh) -> int:
    """Total device count of a mesh (all axes combined)."""
    return _axes_size(mesh, tuple(mesh.axis_names))


def node_partition_spec(mesh: Mesh, ndim: int, dim0: int) -> PartitionSpec:
    """THE node-axis placement rule, shared by every layer of the HSS stack.

    Node-stacked arrays — (n_nodes, ·, ·) per-level blocks — shard their
    leading axis over ALL mesh axes when it divides the device count;
    everything else (small upper levels, the dense root LU/pivots, vectors
    handled elsewhere) replicates.  ``distributed.fac_shardings``,
    ``factorization.factorize_sharded`` and ``constrain_nodes`` all defer
    here so the rule can never drift between the build, the placement, and
    the solve's intermediate constraints.
    """
    if ndim >= 3 and dim0 % mesh_ndev(mesh) == 0 and dim0 > 1:
        return PartitionSpec(tuple(mesh.axis_names), *([None] * (ndim - 1)))
    return PartitionSpec(*([None] * ndim))


def constrain_nodes(x: jax.Array) -> jax.Array:
    """Pin the leading (node/sample) axis to the active mesh's full device set.

    The HSS per-level sweeps (``HSSMatrix.matmat``, ``hss_solve_mat``) are
    chains of pair/unpair reshapes across the node axis; left to sharding
    propagation alone, XLA's SPMD partitioner picks layouts for the small
    upper-level intermediates that (on some backends/versions) miscompile
    the interleaving reshapes.  This helper pins every per-level intermediate
    to the one layout the distributed solver is designed around: leading dim
    sharded over ALL mesh axes when it divides the device count, replicated
    otherwise — the exact rule of ``core.distributed.fac_shardings``.

    No-op outside a ``use_mesh`` context, so local single-device code paths
    are untouched.
    """
    ctx = _current()
    if ctx is None:
        return x
    mesh, _ = ctx
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, node_partition_spec(mesh, x.ndim, x.shape[0])))


def shard_map(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-axes check.

    The per-device bodies here (psum'd partial scores, per-level node
    blocks) are written against explicit specs, not inferred varying axes.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
