"""MoE dispatch paths: the shard_map expert-parallel implementations must
agree with the single-device reference exactly (same routing, same drops),
and the CLEX knobs (capacity, Valiant shuffle) must behave as specified."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig, get_config
from repro.launch.jax_compat import make_mesh, use_mesh
from repro.models.moe import moe_apply, moe_local, router_topk

D, T = 64, 64


def _cfg(**over):
    base = get_config("olmoe-1b-7b", reduced=True)
    fields = dict(n_experts=8, top_k=2, d_expert_ff=32, capacity_factor=8.0)
    fields.update(over)
    moe = dataclasses.replace(base.moe, **fields)
    return dataclasses.replace(base, d_model=D, moe=moe, compute_dtype="float32")


def _params(cfg, key):
    from repro.models.layers import Initializer
    from repro.models.moe import moe_init

    p, _ = moe_init(Initializer(key), cfg, jnp.float32)
    return p


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return make_mesh((2, 2, 2), ("pod", "data", "model"))


def test_router_topk_normalised():
    cfg = _cfg()
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(D, 8)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    weights, experts, aux = router_topk(w, x, 2)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-6)
    assert experts.shape == (T, 2)
    assert float(aux) > 0


def test_sharded_a2a_matches_local(mesh):
    """Token-sharded a2a EP == the local oracle (capacity not binding)."""
    cfg = _cfg()
    params = _params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, T // 2, D)), jnp.float32)  # [B,S,D]
    ref, aux_ref = moe_local(params, x.reshape(T, D), cfg)
    with use_mesh(mesh):
        out, aux = jax.jit(lambda p, x: moe_apply(p, x, cfg))(params, x)
    np.testing.assert_allclose(np.asarray(out.reshape(T, D)), np.asarray(ref), atol=2e-5)


def test_replicated_ep_matches_local(mesh):
    """Tiny token counts (decode) use replicated EP — also exact."""
    cfg = _cfg()
    params = _params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 1, D)), jnp.float32)  # 2 tokens: decode-like
    ref, _ = moe_local(params, x.reshape(2, D), cfg)
    with use_mesh(mesh):
        out, _ = jax.jit(lambda p, x: moe_apply(p, x, cfg))(params, x)
    np.testing.assert_allclose(np.asarray(out.reshape(2, D)), np.asarray(ref), atol=2e-5)


def test_capacity_drops_tokens():
    """capacity_factor 0+ forces drops: output loses some token
    contributions but stays finite (GShard semantics)."""
    cfg_tight = _cfg(capacity_factor=0.25)
    params = _params(cfg_tight, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    out_tight, _ = moe_local(params, x, cfg_tight)
    out_loose, _ = moe_local(params, x, _cfg())
    assert bool(jnp.all(jnp.isfinite(out_tight)))
    # dropped tokens produce zero output rows
    zero_rows = int(jnp.sum(jnp.all(out_tight == 0.0, axis=-1)))
    assert zero_rows > 0
    assert float(jnp.max(jnp.abs(out_tight - out_loose))) > 0


def test_valiant_shuffle_preserves_semantics(mesh):
    """The lightweight Valiant indirection must be a no-op on the output
    (shuffle + route + unshuffle) up to capacity-drop differences — with
    loose capacity it is exact."""
    cfg = _cfg()
    cfg_v = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, valiant_shuffle=True))
    params = _params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2, T // 2, D)), jnp.float32)
    with use_mesh(mesh):
        out_plain, _ = jax.jit(lambda p, x: moe_apply(p, x, cfg))(params, x)
        out_val, _ = jax.jit(
            lambda p, x, k: moe_apply(p, x, cfg_v, key=k)
        )(params, x, jax.random.PRNGKey(7))
    np.testing.assert_allclose(np.asarray(out_val), np.asarray(out_plain), atol=2e-5)


@pytest.mark.parametrize("jit", [True, False])
def test_sharded_a2a_matches_local_on_4_devices(jit):
    """The token-sharded a2a path through ``jax_compat.shard_map`` on a
    2x2 (data x model) mesh, traced under an outer jit and called eagerly:
    both must equal the local oracle."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    cfg = _cfg()
    params = _params(cfg, jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, T // 2, D)), jnp.float32)
    ref, _ = moe_local(params, x.reshape(T, D), cfg)
    fn = lambda p, x: moe_apply(p, x, cfg, mesh=mesh)
    out, aux = (jax.jit(fn) if jit else fn)(params, x)
    np.testing.assert_allclose(np.asarray(out.reshape(T, D)), np.asarray(ref), atol=2e-5)
    assert np.isfinite(float(aux))
