"""Production meshes.

The mesh is the CLEX hierarchy seen by the framework: ``model`` is the
innermost (fastest, level-1) axis, ``data`` the intra-pod DP axis, ``pod``
the scarce top level.  ``make_production_mesh`` builds the assignment's
16x16 single-pod (256 chips) and 2x16x16 multi-pod (512 chips) meshes.

All mesh construction goes through ``jax_compat.make_mesh``, which gives
every axis ``AxisType.Auto`` (JAX 0.9 defaults to ``Explicit``).

Functions, not module-level constants: importing this module never touches
jax device state.
"""

from __future__ import annotations

import jax

from .jax_compat import make_mesh

__all__ = [
    "make_production_mesh",
    "make_elastic_mesh",
    "parse_mesh_flag",
    "dp_axes",
    "mesh_axis_sizes",
]


def parse_mesh_flag(value: str):
    """Parse the launchers' ``--mesh`` knob: ``DxM`` (data x model) or
    ``PxDxM`` (pod x data x model, the CLEX hierarchy).  Shared by
    ``launch/train.py`` and ``launch/serve.py``; raises ``SystemExit`` with
    the usage message on malformed input."""
    try:
        dims = tuple(int(x) for x in value.split("x"))
    except ValueError:
        dims = ()
    if len(dims) == 2:
        return make_mesh(dims, ("data", "model"))
    if len(dims) == 3:
        return make_mesh(dims, ("pod", "data", "model"))
    raise SystemExit(f"--mesh must be DxM or PxDxM, got {value!r}")


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_elastic_mesh(n_devices: int | None = None, model_parallel: int | None = None):
    """Elastic re-mesh after node loss: keep the model axis fixed (sharding
    of parameters must still fit) and shrink the data axis to whatever
    device count survives.

    An explicit ``model_parallel`` must divide ``n_devices`` exactly — a
    remesh that silently shrank the model axis would orphan parameter
    shards; only when ``model_parallel`` is None is the largest fitting
    power-of-two degree auto-picked.  Invalid survivor counts raise
    ``ValueError`` instead of building a bad mesh."""
    devices = jax.devices()
    if n_devices is not None and n_devices <= 0:
        raise ValueError(f"n_devices must be positive, got {n_devices}")
    n = n_devices or len(devices)
    if n > len(devices):
        raise ValueError(
            f"n_devices={n} exceeds the {len(devices)} devices visible to this process"
        )
    if model_parallel is not None:
        if model_parallel <= 0:
            raise ValueError(f"model_parallel must be positive, got {model_parallel}")
        if n % model_parallel:
            raise ValueError(
                f"{n} surviving devices are not divisible by model_parallel="
                f"{model_parallel}; shrinking the model axis would orphan "
                f"parameter shards — drop to the next multiple of "
                f"{model_parallel} devices or re-plan with plan_remesh"
            )
        mp = model_parallel
    else:
        mp = 16
        while mp > 1 and n % mp:
            mp //= 2
    dp = n // mp
    return make_mesh((dp, mp), ("data", "model"), devices=devices[:n])


def dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
