"""Perf-iteration driver for §Perf hillclimbing.

Lowers one (arch x shape x mesh) cell with named experiment overrides and
reports the three roofline terms + per-collective bytes, so each
hypothesis -> change -> before/after cycle is one function call.

  PYTHONPATH=src:. python -m benchmarks.perf_iter --arch olmoe-1b-7b \
      --shape train_4k --mesh multi --variant hier_sync
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, "src")
sys.path.insert(0, ".")

VARIANTS = {
    "baseline": {},
    # memory-term experiments
    "no_sp": {"cfg": {"sequence_parallel": False}},
    "no_remat": {"cfg": {"remat": False}},
    "qchunk_512": {"q_chunk": 512},
    "qchunk_2048": {"q_chunk": 2048},
    "qchunk_4096": {"q_chunk": 4096},
    # collective-term experiments (CLEX technique)
    "hier_sync": {"pcfg": {"hierarchical_grad_sync": True}},
    "hier_sync_int8": {"pcfg": {"hierarchical_grad_sync": True, "compress_cross_pod": True}},
    "no_fsdp": {"fsdp": False},
    "moe_cap_1_0": {"moe": {"capacity_factor": 1.0}},
    "moe_cap_2_0": {"moe": {"capacity_factor": 2.0}},
    "valiant": {"moe": {"valiant_shuffle": True}},
    "microbatch_2": {"microbatches": 2},
    "microbatch_8": {"microbatches": 8},
    "microbatch_16": {"microbatches": 16},
    # SSD kernel-shape experiments (chunk Q: decay traffic ~ S*Q*H)
    "ssd_chunk_64": {"ssm": {"chunk_size": 64}},
    "ssd_chunk_128": {"ssm": {"chunk_size": 128}},
    "ssd_chunk_512": {"ssm": {"chunk_size": 512}},
    "ssd_chunk_1024": {"ssm": {"chunk_size": 1024}},
    "microbatch_4": {"microbatches": 4},
}


def run_variant(arch: str, shape_name: str, mesh_name: str, variant: str) -> dict:
    os.environ.setdefault(
        "XLA_FLAGS",
        "--xla_force_host_platform_device_count=512 "
        "--xla_llvm_disable_expensive_passes=true --xla_backend_optimization_level=0",
    )
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from benchmarks.hlo_analysis import analyze_hlo
    from repro.configs.base import SHAPES, ParallelConfig, get_config
    from repro.launch.dryrun import DRYRUN_DEVICE_KIND, _model_flops, peaks
    from repro.launch.jax_compat import use_mesh
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import abstract_caches, abstract_params, input_specs
    from repro.models import build_model
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.runtime import sharding as shd
    from repro.runtime.trainer import make_train_step

    spec = VARIANTS[variant]
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape.kind != "train":
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    for k, v in spec.get("cfg", {}).items():
        cfg = dataclasses.replace(cfg, **{k: v})
    if "moe" in spec and cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **spec["moe"]))
    if "ssm" in spec and cfg.ssm is not None:
        cfg = dataclasses.replace(cfg, ssm=dataclasses.replace(cfg.ssm, **spec["ssm"]))
    if "q_chunk" in spec:
        from repro.models import attention as attn_mod

        orig = attn_mod.blockwise_attention
        import functools

        attn_mod.blockwise_attention = functools.partial(orig, q_chunk=spec["q_chunk"])

    pcfg_kwargs = {"hierarchical_grad_sync": False}
    pcfg_kwargs.update(spec.get("pcfg", {}))
    pcfg = ParallelConfig(**pcfg_kwargs)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    n_chips = mesh.devices.size
    model = build_model(cfg)
    fsdp = spec.get("fsdp", True)

    t0 = time.time()
    with use_mesh(mesh):
        params_abs = abstract_params(model)
        axes = model.param_axes()
        batch = input_specs(cfg, shape)
        if shape.kind == "train":
            params_sh = shd.param_shardings(axes, mesh, params_abs,
                                            fsdp_axis="data" if fsdp else None)
            opt_abs = jax.eval_shape(lambda p: adamw_init(p, AdamWConfig()), params_abs)
            opt_sh = shd.opt_state_shardings(params_sh, mesh)
            if pcfg.compress_cross_pod:
                from repro.core.collectives import error_feedback_slots

                sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
                n_low = sizes.get("data", 1)
                dp_total = n_low * sizes.get("pod", 1)
                slots = jax.eval_shape(lambda p: error_feedback_slots(p, n_low), params_abs)
                opt_abs["err"] = jax.tree.map(
                    lambda e: jax.ShapeDtypeStruct((dp_total,) + e.shape, e.dtype), slots
                )
                dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
                opt_sh["err"] = jax.tree.map(
                    lambda e: NamedSharding(mesh, P(dp_axes, None)), opt_abs["err"]
                )
            batch_sh = shd.batch_shardings(batch, mesh)
            mb = spec.get("microbatches")
            if mb is None:
                mb = 1
                if cfg.d_model >= 3072 or cfg.enc_dec:
                    mb = 4
                if cfg.d_model >= 4096:
                    mb = 8
            step = make_train_step(model, AdamWConfig(), pcfg, mesh=mesh,
                                   microbatches=mb)
            compiled = jax.jit(
                step,
                in_shardings=(params_sh, opt_sh, batch_sh),
                out_shardings=(params_sh, opt_sh, NamedSharding(mesh, P())),
                donate_argnums=(0, 1),
            ).lower(params_abs, opt_abs, batch).compile()
        elif shape.kind == "prefill":
            params_sh = shd.param_shardings(axes, mesh, params_abs)
            batch_sh = shd.batch_shardings(batch, mesh)
            compiled = jax.jit(model.prefill, in_shardings=(params_sh, batch_sh)).lower(
                params_abs, batch
            ).compile()
        else:
            params_sh = shd.param_shardings(axes, mesh, params_abs)
            caches_abs = abstract_caches(model, shape)
            caches_sh = shd.cache_shardings(caches_abs, mesh)
            batch_sh = shd.batch_shardings(batch, mesh)
            compiled = jax.jit(
                model.decode_step,
                in_shardings=(params_sh, caches_sh, batch_sh["tokens"], batch_sh["pos"]),
                donate_argnums=(1,),
            ).lower(params_abs, caches_abs, batch["tokens"], batch["pos"]).compile()

        mem = compiled.memory_analysis()
        hlo = analyze_hlo(compiled.as_text(), pod_size=256)

    model_flops = _model_flops(get_config(arch), shape)
    hw = peaks(DRYRUN_DEVICE_KIND)
    terms = {
        "compute_s": hlo.flops / hw["peak_flops"],
        "memory_s": hlo.hbm_bytes / hw["hbm_bw"],
        "collective_s": hlo.collective_bytes / hw["ici_bw"],
    }
    useful_s = model_flops / n_chips / hw["peak_flops"]
    out = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "variant": variant,
        "compile_s": round(time.time() - t0, 1),
        **{k: round(v, 4) for k, v in terms.items()},
        "dominant": max(terms, key=terms.get),
        "roofline_fraction": round(useful_s / max(terms.values()), 4),
        "cross_pod_gb": round(hlo.cross_pod_bytes / 1e9, 2),
        "per_kind_gb": {k: round(v / 1e9, 2) for k, v in hlo.per_kind.items()},
        "mem_total_gb": round(
            (mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
             - mem.alias_size_in_bytes) / 1e9, 2),
    }
    os.makedirs("benchmarks/results/perf", exist_ok=True)
    with open(f"benchmarks/results/perf/{arch}__{shape_name}__{mesh_name}__{variant}.json",
              "w") as f:
        json.dump(out, f, indent=1)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--variant", default="baseline", choices=list(VARIANTS))
    args = ap.parse_args()
    out = run_variant(args.arch, args.shape, args.mesh, args.variant)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
