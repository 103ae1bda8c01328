"""Ahead-of-time compiles for a described TPU v5e, without the chip.

The TPU compiler refuses what interpret mode accepts (block shapes off the
(8, 128) tiling, too much VMEM) and what does not fit the chip's memory.
These tests compile the three Pallas kernels with ``interpret=False`` at
the widths of the models that use them, and the full-width ragged decode
step of the serving engine, for one chip of a ``v5e:2x2`` topology: that it
fits, and that it writes the KV pool in place at the benchmark cells'
shapes.  The topology is described inside a fixture, never at import:
only one process at a time may load the TPU library.
"""

import os
import re
import sys
from pathlib import Path

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


# results that name a buffer without moving data into it
_NO_MOVE = {"parameter", "get-tuple-element", "tuple", "bitcast", "while", "conditional",
            "call", "optimization-barrier", "constant", "copy-done", "slice-done",
            "dynamic-slice-done", "async-done"}


def _computations(hlo: str) -> dict:
    """``{computation: [(instruction, result type, op, root?)]}`` of HLO text."""
    comps, body = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head and not line.startswith(" "):
            body = comps.setdefault(head.group(1), [])
            continue
        m = re.match(r"\s*(ROOT )?%(\S+) = (.+?) ([\w\-]+)\(", line)
        if m and body is not None:
            body.append((m.group(2), m.group(3), m.group(4), bool(m.group(1))))
    return comps


def _layer_sized_moves(hlo: str, elems: int) -> list[str]:
    """Instructions that write ``elems`` elements or more into a buffer of
    their own: copies (into any memory space, async or not), selects,
    slices, and fusions, anywhere but inside a fusion (an op fused into
    another reads its operand in place; a fusion's own result is what it
    writes).  An in-place update — a ``dynamic-update-slice``, or a fusion
    whose root is one — writes only its update and is not counted."""
    comps = _computations(hlo)
    fused = set(re.findall(r"\bfusion\(.*?calls=%([\w.\-]+)", hlo))
    roots = {comp: op for comp, body in comps.items() for _, _, op, root in body if root}
    calls = dict(re.findall(r"%(\S+) = .*?\bfusion\(.*?calls=%([\w.\-]+)", hlo))

    def in_place(name, op):
        if op == "fusion":
            op = roots.get(calls.get(name), op)
        return op == "dynamic-update-slice"

    found = []
    for comp, body in comps.items():
        if comp in fused:
            continue
        for name, rtype, op, _ in body:
            if op in _NO_MOVE or in_place(name, op):
                continue
            dims = re.findall(r"\w+\[([\d,]*)\]", rtype)
            if not dims:
                continue
            n = 1
            for d in filter(None, dims[0].split(",")):  # an async start opens with its result
                n *= int(d)
            if n >= elems:
                found.append(f"{name} {op} [{dims[0]}]")
    return found


@pytest.mark.parametrize("workload", ["internlm2-1.8b.chat_open",
                                      "h2o-danube-1.8b.longctx_decode",
                                      "deepseek-v2-lite.latent_decode"])
def test_ragged_decode_writes_the_pool_in_place(one_chip, workload):
    """The engine's bf16 ragged decode at a benchmark cell's published widths
    and pool shape, with the pool donated: the pool aliases the output, the
    step's temporaries stay under a tenth of the pool, and no copy, select,
    slice or fusion writes half a layer's cache (a layer's K or V; half a
    layer's latent entries) or more anywhere: each layer's cache is read
    where it lies in the stack.  For the latent cache this is the guard
    against relaying the whole pool into another layout around the layer
    loop (a 6.1 GB temporary at the latent cell's shapes)."""
    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    from bench import spec
    from repro.models import build_model
    from repro.runtime.serving import ContinuousBatchingEngine

    cell = spec.load_cell(workload)
    model = build_model(spec.model_config(cell.config))
    slots, cap = int(cell.cell["n_slots"]), int(cell.cell["max_len"])
    engine = ContinuousBatchingEngine(model, None, n_slots=1, max_len=cap)
    place = lambda t: jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype), t)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = place(jax.eval_shape(lambda: model.init_cache(slots, cap)))
    rows = lambda dtype=jnp.int32: _shape(one_chip, (slots,), dtype)
    compiled = engine._decode.lower(
        params, pool, rows(), rows(), rows(jnp.float32), rows(), rows()
    ).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 0.1 * pool_bytes, (mem.temp_size_in_bytes, pool_bytes)
    caches = [c["mixer"]["kv" if "kv" in c["mixer"] else "latent"] for c in pool]
    half_layer = min(leaf.size // leaf.shape[0] // 2 for leaf in caches)
    assert _layer_sized_moves(compiled.as_text(), half_layer) == []
