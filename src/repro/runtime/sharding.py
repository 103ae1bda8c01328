"""Logical-axis -> mesh-axis sharding rules (MaxText-style).

Param leaves carry logical axis names (see ``Model.param_axes``); the rules
below map them onto the production mesh.  Within one leaf a mesh axis is
used at most once (greedy left-to-right), e.g. MoE expert weights
("experts", "embed", "ff") shard experts over ``model`` and leave ff
replicated — expert parallelism subsumes tensor parallelism there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = [
    "LOGICAL_RULES",
    "spec_for_axes",
    "param_shardings",
    "batch_shardings",
    "cache_shardings",
    "opt_state_shardings",
    "reshard_params",
]

LOGICAL_RULES: dict[str, str | None] = {
    "vocab": "model",
    "heads": "model",
    "ff": "model",
    "experts": "model",
    "embed": None,  # activations replicated along d_model (TP over heads/ff)
    "layers": None,  # scan axis
}


def spec_for_axes(axes: tuple, mesh, shape=None, fsdp_axis: str | None = None) -> P:
    sizes = dict(mesh.shape)
    used = set()
    entries = []
    for i, name in enumerate(axes):
        target = LOGICAL_RULES.get(name) if name else None
        if (
            target is not None
            and target in mesh.axis_names
            and target not in used
            and (shape is None or shape[i] % sizes[target] == 0)
        ):
            entries.append(target)
            used.add(target)
        else:
            entries.append(None)
    if fsdp_axis and fsdp_axis in mesh.axis_names and fsdp_axis not in used and shape:
        # ZeRO/FSDP: shard the remaining largest divisible dim over the data
        # axis (never the scanned 'layers' dim — scan xs slice along it)
        for i, name in enumerate(axes):
            if (
                entries[i] is None
                and name != "layers"
                and shape[i] % sizes[fsdp_axis] == 0
                and shape[i] >= sizes[fsdp_axis]
            ):
                entries[i] = fsdp_axis
                break
    return P(*entries)


def _is_axes_leaf(x):
    return isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x)


def param_shardings(axes_tree, mesh, params_tree=None, fsdp_axis: str | None = None):
    if params_tree is None:
        return jax.tree.map(
            lambda axes: NamedSharding(mesh, spec_for_axes(axes, mesh)),
            axes_tree,
            is_leaf=_is_axes_leaf,
        )
    return jax.tree.map(
        lambda axes, p: NamedSharding(
            mesh, spec_for_axes(axes, mesh, shape=p.shape, fsdp_axis=fsdp_axis)
        ),
        axes_tree,
        params_tree,
        is_leaf=_is_axes_leaf,
    )


def _dp(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _dp_size(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = 1
    for a in _dp(mesh):
        out *= sizes[a]
    return out


def batch_shardings(batch_tree, mesh):
    """Shard the leading (batch) dim over the DP axes when divisible."""
    dp = _dp(mesh)
    dpn = _dp_size(mesh)

    def leaf(x):
        if dp and x.shape and x.shape[0] % dpn == 0:
            return NamedSharding(mesh, P(dp, *([None] * (x.ndim - 1))))
        return NamedSharding(mesh, P(*([None] * x.ndim)))

    return jax.tree.map(leaf, batch_tree)


def cache_shardings(cache_tree, mesh):
    """Decode caches (layer-stacked: a leading layer axis, rows on axis 1),
    walked by name: batch over DP when divisible; KV heads over ``model``
    when divisible, else the sequence axis (split-KV decode for long
    contexts / small batch); SSM heads/channels over ``model``.  Self-
    attention ``kv`` is head-major ``[r, B, Kv, L, 2*D]``; cross-attention
    memory ``k``/``v`` is ``[r, B, S, Kv, D]``."""
    dp = _dp(mesh)
    dpn = _dp_size(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    mp = sizes.get("model", 1)

    def spec(name: str, arr) -> NamedSharding:
        entries: list = [None] * arr.ndim
        if dp and arr.shape[1] % dpn == 0 and arr.shape[1] > 1:
            entries[1] = dp
        if mp > 1:
            if name in ("kv", "k", "v"):
                kvdim, sdim = (2, 3) if name == "kv" else (3, 2)
                if arr.shape[kvdim] % mp == 0:
                    entries[kvdim] = "model"
                elif arr.shape[sdim] % mp == 0:
                    entries[sdim] = "model"  # split-KV decode
            elif name == "latent":  # MLA [r, B, L, rank + rope]: split the sequence
                if arr.shape[2] % mp == 0:
                    entries[2] = "model"
            elif name == "conv":
                if arr.shape[3] % mp == 0:
                    entries[3] = "model"
            elif name == "h":
                if arr.shape[2] % mp == 0:
                    entries[2] = "model"
        return NamedSharding(mesh, P(*entries))

    def walk(subtree):
        if isinstance(subtree, dict):
            return {
                k: (spec(k, v) if not isinstance(v, (dict, tuple, list)) else walk(v))
                for k, v in subtree.items()
            }
        if isinstance(subtree, (tuple, list)):
            out = [walk(v) for v in subtree]
            return tuple(out) if isinstance(subtree, tuple) else out
        return NamedSharding(mesh, P(*([None] * subtree.ndim)))

    return walk(cache_tree)


def opt_state_shardings(param_shardings_tree, mesh):
    """Adam m/v mirror the param shardings; scalars replicated."""
    return {
        "step": NamedSharding(mesh, P()),
        "m": param_shardings_tree,
        "v": param_shardings_tree,
    }


def reshard_params(axes_tree, params, mesh):
    """``device_put`` every param leaf onto the ``NamedSharding`` the logical
    rules imply on ``mesh`` — pure data movement, bit-exact.  The shared core
    of the trainer's :func:`~repro.runtime.orchestrator.reshard_to_mesh` and
    the serving orchestrator's KV-pool migration.  Direction-agnostic: the
    target mesh may be smaller (device/pod loss onto a survivor
    sub-hierarchy) *or larger* (``device_gain`` re-admission regrows the
    data axis) than where ``params`` currently live — either way no
    checkpoint round-trip, and a shrink→grow round trip returns every leaf
    bit-identical (``tests/test_orchestrator.py`` pins this)."""
    psh = param_shardings(axes_tree, mesh, params)
    return jax.tree.map(lambda x, s: jax.device_put(x, s), params, psh)
