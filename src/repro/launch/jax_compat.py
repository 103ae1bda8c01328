"""The repo's single JAX seam: mesh construction, activation and
manual-region entry, written for the installed JAX 0.9.

* ``make_mesh``          — ``jax.make_mesh`` with every axis ``AxisType.Auto``
                           (0.9 defaults to ``Explicit``), so GSPMD sees the
                           CLEX hierarchy and models place data with
                           ``MeshContext.constrain``.
* ``shard_map``          — ``jax.shard_map`` under ``jax.jit``.  ``axis_names``
                           may name a subset of the mesh axes (partial manual:
                           the rest stay auto inside the region).  The jit is
                           what makes a partial-manual region callable outside
                           a trace: called eagerly, 0.9 rejects its out_specs.
* ``MeshContext``        — explicit mesh handle threaded through model and
                           runtime call signatures, replacing the implicit
                           ``jax.sharding.get_abstract_mesh()`` pattern.
* ``use_mesh``/``active_mesh`` — repo-owned ambient mesh for launcher-level
                           code (dry-run, training loop, tests) that lowers
                           many entry points under one mesh.

Collective code never needs this module: ``jax.lax`` collectives and
``jax.lax.axis_size`` are called directly.  ``tests/test_jax_compat.py``
pins this module against the installed JAX.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "MeshContext",
    "NO_MESH",
    "make_mesh",
    "shard_map",
    "use_mesh",
    "active_mesh",
    "resolve_mesh",
]


# --------------------------------------------------------------------------
# mesh construction
# --------------------------------------------------------------------------


def make_mesh(axis_shapes, axis_names, *, devices=None) -> Mesh:
    """CLEX hierarchy mesh with auto (GSPMD-visible) axis semantics."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), axis_names, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )


# --------------------------------------------------------------------------
# explicit mesh handle
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MeshContext:
    """The mesh as model/runtime code sees it: axis bookkeeping plus the one
    sharding op models emit (``constrain``).  Hashable and static, so it can
    be closed over by jitted functions and scan bodies."""

    mesh: Mesh

    @classmethod
    def from_any(cls, mesh) -> "MeshContext | None":
        if mesh is None:
            return None
        if isinstance(mesh, MeshContext):
            return mesh
        return cls(mesh)

    @property
    def axis_names(self) -> tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    def axis_sizes(self) -> dict[str, int]:
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    def axis_size(self, name: str, default: int = 1) -> int:
        return self.axis_sizes().get(name, default)

    def dp_axes(self) -> tuple[str, ...]:
        """Data-parallel axes, outermost first (the CLEX top levels)."""
        return tuple(a for a in ("pod", "data") if a in self.mesh.axis_names)

    def dp_size(self) -> int:
        out = 1
        for a in self.dp_axes():
            out *= self.axis_size(a)
        return out

    def model_size(self) -> int:
        return self.axis_size("model")

    def sharding(self, spec: PartitionSpec) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def constrain(self, x: jax.Array, spec: PartitionSpec) -> jax.Array:
        """``with_sharding_constraint`` bound to this mesh — works with or
        without any ambient mesh context."""
        return jax.lax.with_sharding_constraint(x, self.sharding(spec))


class _NoMesh:
    """Sentinel: run mesh-free even if an ambient mesh is active (used inside
    manual shard_map regions, where auto constraints are illegal)."""

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return "NO_MESH"


NO_MESH = _NoMesh()


# --------------------------------------------------------------------------
# repo-owned ambient mesh
# --------------------------------------------------------------------------

_AMBIENT = threading.local()


def _stack() -> list:
    if not hasattr(_AMBIENT, "stack"):
        _AMBIENT.stack = []
    return _AMBIENT.stack


def active_mesh() -> MeshContext | None:
    stack = _stack()
    return stack[-1] if stack else None


@contextlib.contextmanager
def use_mesh(mesh):
    """Activate ``mesh`` (Mesh, MeshContext, or None for a no-op) for model
    code that was not handed an explicit mesh, and enter JAX's own mesh
    context (``jax.set_mesh``) so spec-based APIs see it too."""
    ctx = MeshContext.from_any(mesh)
    if ctx is None:
        yield None
        return
    _stack().append(ctx)
    try:
        with jax.set_mesh(ctx.mesh):
            yield ctx
    finally:
        _stack().pop()


def resolve_mesh(mesh) -> MeshContext | None:
    """Normalise a mesh argument: explicit Mesh/MeshContext wins, ``None``
    falls back to the ambient ``use_mesh`` context, ``NO_MESH`` forces
    mesh-free execution."""
    if isinstance(mesh, _NoMesh):
        return None
    if mesh is None:
        return active_mesh()
    return MeshContext.from_any(mesh)


@contextlib.contextmanager
def _suppress_ambient():
    stack = _stack()
    saved, stack[:] = stack[:], []
    try:
        yield
    finally:
        stack[:] = saved


# --------------------------------------------------------------------------
# manual-region entry
# --------------------------------------------------------------------------


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None, check: bool = False):
    """``jax.shard_map`` over ``mesh`` (Mesh or MeshContext), jitted.

    ``axis_names`` is the set of manually-mapped axes (default: all of
    them); the others stay under GSPMD inside the region.  The body is
    traced with the repo-ambient mesh suppressed: inside a manual region,
    models must not emit auto sharding constraints.
    """
    ctx = MeshContext.from_any(mesh)
    if ctx is None:
        raise ValueError("shard_map requires an explicit mesh")

    def body(*args):
        with _suppress_ambient():
            return f(*args)

    kwargs = dict(mesh=ctx.mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check)
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.jit(jax.shard_map(body, **kwargs))
