"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell: build the production mesh (16x16 single-pod / 2x16x16
multi-pod), lower the appropriate step (train_step / prefill / decode_step)
with ShapeDtypeStruct inputs (zero allocation), compile, and record

  * memory_analysis()  — proves the cell fits 16 GB/chip,
  * cost_analysis()    — XLA's per-device FLOPs/bytes,
  * the trip-count-scaled HLO analysis (benchmarks/hlo_analysis.py) —
    FLOPs, HBM bytes, per-collective bytes, cross-pod bytes,

into benchmarks/results/dryrun/<arch>__<shape>__<mesh>.json (incremental:
existing results are skipped unless --force).

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs.base import ARCH_IDS, SHAPES, ParallelConfig, get_config
from ..models import build_model
from ..optim.adamw import AdamWConfig, adamw_init
from ..runtime import sharding as shd
from ..runtime.trainer import make_train_step
from .jax_compat import use_mesh
from .mesh import make_production_mesh
from .specs import abstract_caches, abstract_params, cell_is_applicable, input_specs

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "benchmarks",
                           "results", "dryrun")

# Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
# HBM at 819 GB/s, 1,600 Gbit/s of interchip interconnect over four links
# (50 GB/s each).
PEAKS = {
    "TPU v5 lite": {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9,
                    "hbm_per_chip": 16e9},
}
# the chip the virtual production meshes stand for
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown chip is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def _model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6*N_active*D for training, 2*N_active*D for inference."""
    n_active = cfg.active_params()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch  # decode: one token per seq


def run_cell(arch: str, shape_name: str, multi_pod: bool, force: bool = False) -> dict:
    from benchmarks.hlo_analysis import analyze_hlo  # repo-root import

    mesh_name = "multi" if multi_pod else "single"
    os.makedirs(RESULTS_DIR, exist_ok=True)
    out_path = os.path.join(RESULTS_DIR, f"{arch}__{shape_name}__{mesh_name}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "time": time.strftime("%F %T"),
    }
    ok, reason = cell_is_applicable(cfg, shape)
    if not ok:
        record.update(status="skipped", reason=reason)
        _write(out_path, record)
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    hw = peaks(DRYRUN_DEVICE_KIND)
    if shape.kind != "train":
        # serving runs bf16 weights (deployment standard); training keeps
        # fp32 masters with ZeRO/FSDP sharding of params + optimizer state.
        # Decode unrolls layers so every cache aliases in place.
        import dataclasses as _dc

        cfg = _dc.replace(cfg, param_dtype="bfloat16",
                          scan_layers=(shape.kind != "decode"))
    model = build_model(cfg)
    t0 = time.time()
    try:
        with use_mesh(mesh):
            params_abs = abstract_params(model)
            axes = model.param_axes()
            batch = input_specs(cfg, shape)
            if shape.kind == "train":
                params_sh = shd.param_shardings(axes, mesh, params_abs, fsdp_axis="data")
                opt_abs = jax.eval_shape(lambda p: adamw_init(p, AdamWConfig()), params_abs)
                opt_sh = shd.opt_state_shardings(params_sh, mesh)
                batch_sh = shd.batch_shardings(batch, mesh)
                # big models accumulate gradients over microbatches (standard
                # practice at 1M-token global batches) to bound activations;
                # MoE archs benefit most (smaller dispatch buckets — §Perf)
                microbatches = 1
                if cfg.d_model >= 3072 or cfg.enc_dec:
                    microbatches = 4
                if cfg.d_model >= 4096:
                    microbatches = 8
                if cfg.moe is not None and multi_pod:
                    # measured (§Perf olmoe cell): dispatch buckets shrink with
                    # tokens/shard on the 512-chip mesh; on the single pod the
                    # same setting regresses (GSPMD reshard fixpoint) — keep 1
                    microbatches = max(microbatches, 8)
                # auto (GSPMD) grad sync: the mesh is threaded for the
                # model's sharding constraints only — hierarchical sync would
                # change the measured program vs the seed baseline
                step = make_train_step(
                    model, AdamWConfig(), ParallelConfig(hierarchical_grad_sync=False),
                    mesh=mesh, microbatches=microbatches,
                )
                lowered = jax.jit(
                    step,
                    in_shardings=(params_sh, opt_sh, batch_sh),
                    out_shardings=(params_sh, opt_sh, NamedSharding(mesh, P())),
                    donate_argnums=(0, 1),
                ).lower(params_abs, opt_abs, batch)
            elif shape.kind == "prefill":
                params_sh = shd.param_shardings(axes, mesh, params_abs)
                batch_sh = shd.batch_shardings(batch, mesh)
                lowered = jax.jit(
                    model.prefill, in_shardings=(params_sh, batch_sh)
                ).lower(params_abs, batch)
            else:  # decode
                params_sh = shd.param_shardings(axes, mesh, params_abs)
                caches_abs = abstract_caches(model, shape)
                caches_sh = shd.cache_shardings(caches_abs, mesh, cfg, shape.global_batch)
                batch_sh = shd.batch_shardings(batch, mesh)
                lowered = jax.jit(
                    model.decode_step,
                    in_shardings=(params_sh, caches_sh, batch_sh["tokens"], batch_sh["pos"]),
                    donate_argnums=(1,),
                ).lower(params_abs, caches_abs, batch["tokens"], batch["pos"])
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = analyze_hlo(compiled.as_text(), pod_size=256)

        per_device_bytes = (
            mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes
        )
        model_flops = _model_flops(cfg, shape)
        hlo_flops = hlo.flops
        terms = {
            "compute_s": hlo_flops / hw["peak_flops"],
            "memory_s": hlo.hbm_bytes / hw["hbm_bw"],
            "collective_s": hlo.collective_bytes / hw["ici_bw"],
        }
        dominant = max(terms, key=terms.get)
        useful_s = model_flops / n_chips / hw["peak_flops"]
        if shape.kind == "decode":
            # decode is legitimately memory-bound: "useful" work = streaming
            # each active parameter byte + each cache byte exactly once
            ideal_bytes = (
                sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params_abs))
                * cfg.active_params() / max(cfg.total_params(), 1)
                + sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches_abs))
            ) / n_chips
            useful_s = ideal_bytes / hw["hbm_bw"]
        record.update(
            status="ok",
            n_chips=n_chips,
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "per_device_total": per_device_bytes,
                # persistent state (params/opt/caches); temp on the CPU
                # backend includes fp32 float-normalization copies of bf16
                # buffers that do not exist on TPU (native bf16)
                "state_bytes": mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes,
                "fits_16GB": bool(per_device_bytes < hw["hbm_per_chip"]),
            },
            xla_cost={
                "flops": cost.get("flops", -1.0),
                "bytes_accessed": cost.get("bytes accessed", -1.0),
            },
            hlo={
                "flops": hlo_flops,
                "hbm_bytes": hlo.hbm_bytes,
                "collective_bytes": hlo.collective_bytes,
                "cross_pod_bytes": hlo.cross_pod_bytes,
                "per_kind": hlo.per_kind,
            },
            roofline={
                **{k: float(v) for k, v in terms.items()},
                "dominant": dominant,
                "model_flops_total": model_flops,
                "model_flops_per_chip": model_flops / n_chips,
                "useful_fraction_of_hlo": model_flops / n_chips / max(hlo_flops, 1.0),
                "useful_s": useful_s,
                "roofline_fraction": useful_s / max(terms.values()),
            },
        )
    except Exception as e:  # noqa: BLE001 - record the failure for the report
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-2000:])
    _write(out_path, record)
    return record


def _write(path: str, record: dict) -> None:
    with open(path, "w") as f:
        json.dump(record, f, indent=1)


def main() -> None:
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        # dry-run only needs the post-SPMD HLO, not fast host code: keep LLVM
        # cheap so 80 cells compile in reasonable wall time
        "--xla_llvm_disable_expensive_passes=true "
        "--xla_backend_optimization_level=0"
    )
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", ".."))

    archs = ARCH_IDS if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, force=args.force)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" dominant={r['dominant']} frac={r['roofline_fraction']:.3f}"
                             f" mem/dev={rec['memory']['per_device_total']/1e9:.2f}GB"
                             f" compile={rec['compile_s']}s")
                elif status == "error":
                    failures += 1
                    extra = " " + rec["error"][:160]
                print(f"[{status:>7}] {arch} x {shape} x {rec['mesh']}{extra}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
