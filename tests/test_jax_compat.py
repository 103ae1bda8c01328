"""Guardrails for the mesh/sharding seam on the one supported JAX (0.9).

Every construct the repo relies on from ``repro.launch.jax_compat`` is
exercised here under the *installed* JAX, so the next API drift (a rename,
a removed kwarg, a semantics change) fails loudly in one module instead of
as 47 scattered model/runtime failures."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.launch import jax_compat as jc


def _mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return jc.make_mesh((2, 2, 2), ("pod", "data", "model"))


# ------------------------------------------------------------- construction
def test_make_mesh_axes_and_shape():
    mesh = _mesh()
    assert tuple(mesh.axis_names) == ("pod", "data", "model")
    assert mesh.devices.shape == (2, 2, 2)
    flat = jc.make_mesh((8,), ("model",))
    assert dict(zip(flat.axis_names, flat.devices.shape)) == {"model": 8}


def test_make_mesh_device_subset():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = jc.make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    assert mesh.devices.size == 4


def test_mesh_context_bookkeeping():
    mesh = _mesh()
    ctx = jc.MeshContext.from_any(mesh)
    assert jc.MeshContext.from_any(ctx) is ctx
    assert jc.MeshContext.from_any(None) is None
    assert ctx.axis_sizes() == {"pod": 2, "data": 2, "model": 2}
    assert ctx.dp_axes() == ("pod", "data")
    assert ctx.dp_size() == 4
    assert ctx.model_size() == 2
    assert ctx.axis_size("absent") == 1


# ------------------------------------------------------------- ambient mesh
def test_use_mesh_nesting_and_resolution():
    mesh = _mesh()
    inner = jc.make_mesh((8,), ("model",))
    assert jc.active_mesh() is None
    with jc.use_mesh(mesh) as ctx:
        assert jc.active_mesh() is ctx
        assert jc.resolve_mesh(None) is ctx
        with jc.use_mesh(inner) as ictx:
            assert jc.active_mesh() is ictx
        assert jc.active_mesh() is ctx
        # explicit argument beats ambient; NO_MESH suppresses both
        assert jc.resolve_mesh(inner).mesh is inner
        assert jc.resolve_mesh(jc.NO_MESH) is None
    assert jc.active_mesh() is None


def test_use_mesh_none_is_noop():
    with jc.use_mesh(None) as ctx:
        assert ctx is None
        assert jc.active_mesh() is None


# ------------------------------------------------------------- constraints
def test_constrain_under_jit_without_native_context():
    mesh = _mesh()
    ctx = jc.MeshContext.from_any(mesh)

    @jax.jit
    def f(x):
        return ctx.constrain(x * 2, P(("pod", "data"), None))

    out = f(jnp.ones((8, 4)))
    np.testing.assert_allclose(np.asarray(out), 2.0)


def test_explicit_threading_through_model_stack():
    """The tentpole contract: model code sees the mesh via the threaded
    argument (or ambient fallback), never via a global jax query."""
    from repro.configs.base import get_config
    from repro.models.transformer import constrain_residual

    mesh = _mesh()
    cfg = get_config("internlm2-1.8b", reduced=True)
    x = jnp.ones((8, 16, 32))
    # explicit, ambient, and mesh-free all trace and preserve the value
    for out in (
        jax.jit(lambda v: constrain_residual(v, cfg, mesh))(x),
        jax.jit(lambda v: constrain_residual(v, cfg))(x),
        jax.jit(lambda v: constrain_residual(v, cfg, jc.NO_MESH))(x),
    ):
        np.testing.assert_allclose(np.asarray(out), 1.0)


# ------------------------------------------------------------- manual entry
def test_shard_map_psum_semantics():
    mesh = _mesh()

    def body(x):
        return jax.lax.psum(x, ("pod", "data"))

    out = jax.jit(
        jc.shard_map(
            body,
            mesh=mesh,
            in_specs=P(("pod", "data")),
            out_specs=P(("pod", "data")),
            axis_names={"pod", "data"},
        )
    )(jnp.ones((8, 2)))
    np.testing.assert_allclose(np.asarray(out), 4.0)


def test_shard_map_accepts_mesh_context_and_requires_mesh():
    mesh = _mesh()
    ctx = jc.MeshContext.from_any(mesh)
    out = jc.shard_map(
        lambda x: jax.lax.psum(x, "model"),
        mesh=ctx,
        in_specs=P("model"),
        out_specs=P("model"),
        axis_names={"model"},
    )(jnp.ones((8,)))
    np.testing.assert_allclose(np.asarray(out), 2.0)
    with pytest.raises(ValueError):
        jc.shard_map(lambda x: x, mesh=None, in_specs=P(), out_specs=P())


def test_shard_map_suppresses_ambient_mesh():
    """Inside a manual region the model must run mesh-free: an ambient
    ``use_mesh`` outside must not leak auto constraints into the body."""
    mesh = _mesh()
    seen = []

    def body(x):
        seen.append(jc.active_mesh())
        return x

    with jc.use_mesh(mesh):
        jax.jit(jc.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P()))(jnp.ones(4))
    assert seen and all(m is None for m in seen)


# ------------------------------------------------------------- pjit entry
def test_pjit_with_named_shardings_lowers_and_runs():
    mesh = _mesh()
    ctx = jc.MeshContext.from_any(mesh)
    sh_in = ctx.sharding(P(("pod", "data"), None))
    compiled = (
        jax.jit(lambda x: (x * x).sum(), in_shardings=(sh_in,))
        .lower(jax.ShapeDtypeStruct((8, 4), jnp.float32))
        .compile()
    )
    cost = compiled.cost_analysis()
    assert isinstance(cost, dict)
    assert cost.get("flops", 0) > 0


def test_cost_analysis_dict_shape():
    compiled = (
        jax.jit(lambda a, b: a @ b)
        .lower(
            jax.ShapeDtypeStruct((16, 8), jnp.float32),
            jax.ShapeDtypeStruct((8, 4), jnp.float32),
        )
        .compile()
    )
    cost = compiled.cost_analysis()
    assert isinstance(cost, dict)
    assert cost["flops"] == pytest.approx(2 * 16 * 8 * 4, rel=0.01)


# ------------------------------------------------------------- 0.9 behaviour
def test_version_probes_consistent_with_installed_jax():
    """The one supported JAX is 0.9, whose ``make_mesh`` defaults to
    Explicit axes: the seam must hand back Auto ones."""
    assert jax.__version__.startswith("0.9.")
    mesh = _mesh()
    assert set(mesh.axis_types) == {jax.sharding.AxisType.Auto}


def test_axis_size_inside_manual_region():
    mesh = _mesh()
    out = jc.shard_map(
        lambda x: x * jax.lax.axis_size("model"),
        mesh=mesh,
        in_specs=P(),
        out_specs=P(),
        axis_names={"model"},
    )(jnp.ones(4))
    np.testing.assert_allclose(np.asarray(out), 2.0)


def test_tpu_compiler_params_constructs():
    from jax.experimental.pallas import tpu as pltpu

    params = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))
    assert params.dimension_semantics == ("parallel", "arbitrary")
