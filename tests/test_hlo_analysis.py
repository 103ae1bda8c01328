"""The HLO analyzer against exactly-known modules: dot FLOPs, while
trip-count scaling, per-device SPMD semantics, collective byte counts and
cross-pod replica-group detection."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from benchmarks.hlo_analysis import _expand_replica_groups, analyze_hlo
from repro.launch.jax_compat import make_mesh, use_mesh


def test_plain_matmul_flops_exact():
    c = jax.jit(lambda a, b: a @ b).lower(
        jax.ShapeDtypeStruct((64, 32), jnp.float32), jax.ShapeDtypeStruct((32, 16), jnp.float32)
    ).compile()
    a = analyze_hlo(c.as_text())
    assert a.flops == pytest.approx(2 * 64 * 32 * 16, rel=0.01)


def test_scan_trip_count_scaling():
    def scanned(x, ws):
        def body(h, w):
            return h @ w, None

        h, _ = jax.lax.scan(body, x, ws)
        return h

    c = jax.jit(scanned).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32), jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)
    ).compile()
    a = analyze_hlo(c.as_text())
    # XLA's own cost_analysis undercounts by 4x; ours must not
    assert a.flops == pytest.approx(4 * 2 * 64**3, rel=0.01)
    assert c.cost_analysis()["flops"] < a.flops / 2


def test_spmd_per_device_flops_and_collectives():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = make_mesh((8,), ("model",))
    ns = lambda spec: NamedSharding(mesh, spec)  # bound to the mesh, not the ambient one
    with use_mesh(mesh):
        c = jax.jit(
            lambda a, b: a @ b,
            in_shardings=(ns(P(None, "model")), ns(P("model", None))),
            out_shardings=ns(P(None, None)),
        ).lower(
            jax.ShapeDtypeStruct((256, 256), jnp.float32),
            jax.ShapeDtypeStruct((256, 256), jnp.float32),
        ).compile()
    a = analyze_hlo(c.as_text(), pod_size=4)
    assert a.flops == pytest.approx(2 * 256 * 32 * 256, rel=0.01)  # per-device K shard
    assert a.per_kind.get("all-reduce", 0) == pytest.approx(256 * 256 * 4, rel=0.01)
    # groups of 8 span two "pods" of 4
    assert a.cross_pod_bytes == a.collective_bytes


def test_collective_inside_scan_counts_trips():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = make_mesh((8,), ("model",))
    ns = lambda spec: NamedSharding(mesh, spec)  # bound to the mesh, not the ambient one

    def f(x):
        def body(h, _):
            return jax.lax.with_sharding_constraint(h @ h.T, ns(P(None, "model"))), None

        h, _ = jax.lax.scan(body, x, jnp.arange(3))
        return h

    with use_mesh(mesh):
        c = jax.jit(f, in_shardings=ns(P(None, "model")), out_shardings=ns(P(None, "model"))).lower(
            jax.ShapeDtypeStruct((128, 128), jnp.float32)
        ).compile()
    a = analyze_hlo(c.as_text())
    counts = sorted({r.count for r in a.collectives})
    assert counts and counts[-1] == 3.0


def test_replica_group_expansion():
    explicit = _expand_replica_groups("replica_groups={{0,1},{2,3}}")
    assert explicit == [[0, 1], [2, 3]]
    iota = _expand_replica_groups("replica_groups=[2,4]<=[8]")
    assert iota == [[0, 1, 2, 3], [4, 5, 6, 7]]
    # transposed iota: [4,2]<=[2,4]T(1,0) -> groups stride across the pods
    t = _expand_replica_groups("replica_groups=[4,2]<=[2,4]T(1,0)")
    assert t == [[0, 4], [1, 5], [2, 6], [3, 7]]
