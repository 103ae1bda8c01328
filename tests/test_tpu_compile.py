"""Ahead-of-time compiles for a described TPU v5e, without the chip.

The TPU compiler refuses what interpret mode accepts (block shapes off the
(8, 128) tiling, too much VMEM) and what does not fit the chip's memory.
These tests compile the three Pallas kernels with ``interpret=False`` at
the widths of the models that use them, and the full-width internlm2-1.8b
ragged decode step of the serving engine, for one chip of a ``v5e:2x2``
topology.  The topology is described inside a fixture, never at import:
only one process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_internlm2_width(one_chip):
    """head_dim 128, 16 query heads over 8 KV heads, a 4096-token prompt."""
    from repro.kernels.flash_attention.ops import flash_attention

    q = _shape(one_chip, (1, 4096, 16, 128))
    kv = _shape(one_chip, (1, 4096, 8, 128))
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=False))
    _assert_kernel(fn.lower(q, kv, kv).compile())


def test_grouped_matmul_compiles_at_olmoe_expert_width(one_chip):
    """64 experts, d_model 2048, d_expert_ff 1024, 256-token buckets."""
    from repro.kernels.moe_gmm.ops import gmm

    x = _shape(one_chip, (64, 256, 2048))
    w = _shape(one_chip, (64, 2048, 1024))
    _assert_kernel(jax.jit(lambda x, w: gmm(x, w, interpret=False)).lower(x, w).compile())


@pytest.mark.parametrize("chunk", [256, 512])
def test_ssd_scan_compiles_at_mamba2_width(one_chip, chunk):
    """64 heads of head_dim 64, state 128, a 4096-token sequence."""
    from repro.kernels.ssd_scan.ops import ssd

    f32 = jnp.float32
    args = (
        _shape(one_chip, (1, 4096, 64, 64)),
        _shape(one_chip, (1, 4096, 64), f32),
        _shape(one_chip, (64,), f32),
        _shape(one_chip, (1, 4096, 128)),
        _shape(one_chip, (1, 4096, 128)),
    )
    fn = jax.jit(lambda *a: ssd(*a, chunk=chunk, interpret=False))
    _assert_kernel(fn.lower(*args).compile())


def test_internlm2_ragged_decode_fits_one_chip(topo, one_chip):
    """The serving engine's decode program at full width: fp32 params plus a
    4-slot x 2048-token KV pool and the step's temporaries fit 16 GB."""
    from repro.configs.base import get_config
    from repro.launch.dryrun import peaks
    from repro.models import build_model

    model = build_model(get_config("internlm2-1.8b"))
    place = lambda t: jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype), t)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(lambda: model.init_cache(4, 2048)))
    tokens = _shape(one_chip, (4, 1), jnp.int32)
    pos = _shape(one_chip, (4,), jnp.int32)
    step = jax.jit(lambda p, c, t, q: model.decode_step(p, c, t, q, ragged=True),
                   donate_argnums=(1,))
    mem = step.lower(params, caches, tokens, pos).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 7e9  # really the full-width fp32 model
    assert total < peaks(topo.devices[0].device_kind)["hbm_per_chip"], total
