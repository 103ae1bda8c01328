"""Dry-run machinery smoke test on an 8-device mesh with reduced configs:
the same lowering path as the production 512-device dry-run (sharding
rules, train/prefill/decode steps, memory/cost/HLO analysis) must compile
for every model family."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from benchmarks.hlo_analysis import analyze_hlo
from repro.configs.base import ParallelConfig, ShapeConfig, get_config
from repro.launch.jax_compat import make_mesh, use_mesh
from repro.launch.specs import abstract_caches, abstract_params, input_specs
from repro.models import build_model
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.runtime import sharding as shd
from repro.runtime.trainer import make_train_step

FAMILIES = ["internlm2-1.8b", "olmoe-1b-7b", "jamba-v0.1-52b", "mamba2-1.3b",
            "minicpm3-4b", "seamless-m4t-large-v2", "h2o-danube-1.8b"]


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return make_mesh((2, 2, 2), ("pod", "data", "model"))


def _reduced(arch, **over):
    cfg = get_config(arch, reduced=True)
    return dataclasses.replace(cfg, **over)


@pytest.mark.parametrize("arch", FAMILIES)
def test_train_cell_lowers_and_compiles(arch, mesh):
    cfg = _reduced(arch)
    shape = ShapeConfig("train_tiny", seq_len=64, global_batch=8, kind="train")
    model = build_model(cfg)
    with use_mesh(mesh):
        params_abs = abstract_params(model)
        params_sh = shd.param_shardings(model.param_axes(), mesh, params_abs, fsdp_axis="data")
        opt_abs = jax.eval_shape(lambda p: adamw_init(p, AdamWConfig()), params_abs)
        opt_sh = shd.opt_state_shardings(params_sh, mesh)
        batch = input_specs(cfg, shape)
        batch_sh = shd.batch_shardings(batch, mesh)
        step = make_train_step(model, AdamWConfig(),
                               ParallelConfig(hierarchical_grad_sync=False), mesh=mesh)
        compiled = jax.jit(
            step,
            in_shardings=(params_sh, opt_sh, batch_sh),
            out_shardings=(params_sh, opt_sh, NamedSharding(mesh, P())),
        ).lower(params_abs, opt_abs, batch).compile()
    assert compiled.memory_analysis().temp_size_in_bytes >= 0
    a = analyze_hlo(compiled.as_text(), pod_size=4)
    assert a.flops > 0
    assert a.collective_bytes > 0  # TP/FSDP collectives present


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "jamba-v0.1-52b", "minicpm3-4b"])
def test_decode_cell_lowers_and_compiles(arch, mesh):
    cfg = _reduced(arch, param_dtype="bfloat16")
    shape = ShapeConfig("decode_tiny", seq_len=128, global_batch=8, kind="decode")
    model = build_model(cfg)
    with use_mesh(mesh):
        params_abs = abstract_params(model)
        params_sh = shd.param_shardings(model.param_axes(), mesh, params_abs)
        caches_abs = abstract_caches(model, shape)
        caches_sh = shd.cache_shardings(caches_abs, mesh)
        batch = input_specs(cfg, shape)
        batch_sh = shd.batch_shardings(batch, mesh)
        compiled = jax.jit(
            model.decode_step,
            in_shardings=(params_sh, caches_sh, batch_sh["tokens"], batch_sh["pos"]),
            donate_argnums=(1,),
        ).lower(params_abs, caches_abs, batch["tokens"], batch["pos"]).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0  # donated caches alias in place


def test_prefill_cell_lowers_and_compiles(mesh):
    cfg = _reduced("qwen3-32b")
    shape = ShapeConfig("prefill_tiny", seq_len=256, global_batch=8, kind="prefill")
    model = build_model(cfg)
    with use_mesh(mesh):
        params_abs = abstract_params(model)
        params_sh = shd.param_shardings(model.param_axes(), mesh, params_abs)
        batch = input_specs(cfg, shape)
        batch_sh = shd.batch_shardings(batch, mesh)
        compiled = jax.jit(model.prefill, in_shardings=(params_sh, batch_sh)).lower(
            params_abs, batch
        ).compile()
    assert compiled.cost_analysis()["flops"] > 0


def test_importing_dryrun_leaves_xla_flags_alone(monkeypatch):
    """The 512-device host setting belongs to the dry-run CLI's ``main``:
    importing the module (as the perf tools do) must not change it."""
    import importlib
    import os
    import sys

    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    monkeypatch.delitem(sys.modules, "repro.launch.dryrun", raising=False)
    importlib.import_module("repro.launch.dryrun")
    assert os.environ["XLA_FLAGS"] == "--xla_force_host_platform_device_count=8"


def test_peak_table_is_keyed_by_device_kind():
    from repro.launch.dryrun import DRYRUN_DEVICE_KIND, peaks

    v5e = peaks(DRYRUN_DEVICE_KIND)
    assert v5e["peak_flops"] == 197e12 and v5e["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")
