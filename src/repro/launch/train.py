"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
      --reduced --steps 200 --batch 8 --seq 256 --ckpt-dir /tmp/run1

Production knobs: --mesh DxM (data x model) or PxDxM (pod x data x model,
the CLEX hierarchy — needed for cross-pod sync tiering), --microbatches N
(grad accumulation), --hierarchical-sync / --compress (CLEX-staged
gradient collectives), --resume.

Elastic fault-tolerant mode (docs/TRAINING.md): --orchestrate runs the
loop under ``runtime.orchestrator.Orchestrator`` — device/pod-loss events
remesh + reshard in memory, link degradation switches the gradient-sync
tier (requires a PxDxM mesh + --hierarchical-sync), and checkpoints become
an async fallback.  Without --mesh the orchestrator gets an elastic mesh
over all visible devices.  Inject faults with --fault-schedule '<json>'
(or @file.json), e.g.

  --orchestrate --mesh 4x1 --fault-schedule \
      '[{"step": 50, "kind": "device_loss", "devices": 2}]'
"""

from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp

from .. import obs as obslib
from ..checkpoint.checkpointing import latest_intact_step, restore_checkpoint, save_checkpoint
from ..configs.base import ARCH_IDS, ParallelConfig, get_config
from ..data.pipeline import SyntheticLM
from ..models import build_model
from ..obs import log
from ..optim.adamw import AdamWConfig
from ..runtime.autoscale import AutoscaleConfig
from ..runtime.fault_tolerance import StragglerMonitor
from ..runtime.orchestrator import Orchestrator, OrchestratorConfig, load_schedule
from ..runtime.trainer import Trainer
from .jax_compat import use_mesh
from .compile_cache import enable_compile_cache
from .mesh import make_elastic_mesh, parse_mesh_flag


def finish_obs(ob, trace_path: str, want_metrics: bool) -> None:
    """Shared launcher epilogue (docs/OBSERVABILITY.md): export the trace
    (Chrome/Perfetto JSON at the given path, lossless JSONL next to it) and
    dump the metrics registry + calibration summary to stdout."""
    if trace_path:
        chrome = ob.tracer.export_chrome(trace_path)
        jsonl = ob.tracer.export_jsonl(os.path.splitext(trace_path)[0] + ".jsonl")
        log.info(f"trace written: {chrome} (+ {jsonl})")
    if want_metrics:
        print(ob.registry.to_json())
        print(json.dumps({"calibration": ob.calibration.summary()}, indent=2))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true", help="smoke-scale config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", type=str, default="",
                    help="DxM e.g. 4x2, or PxDxM e.g. 2x2x2 for a pod axis")
    ap.add_argument("--hierarchical-sync", action="store_true")
    ap.add_argument("--compress", action="store_true", help="int8 cross-pod grad sync")
    ap.add_argument("--ckpt-dir", type=str, default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--orchestrate", action="store_true",
                    help="elastic fault-tolerant loop (docs/TRAINING.md)")
    ap.add_argument("--fault-schedule", type=str, default="",
                    help="JSON list of fault events, or @path/to/file.json; "
                         "device_gain/pod_gain events regrow the data axis")
    ap.add_argument("--drain-stragglers", action="store_true",
                    help="remesh away from hosts still slow after the "
                         "patience window (drains are priced: tiny "
                         "stragglers are tolerated)")
    ap.add_argument("--no-price-drains", action="store_true",
                    help="always drain stragglers instead of pricing the "
                         "remesh against the remaining slowdown")
    ap.add_argument("--spare-devices", type=int, default=0,
                    help="warm spares device_gain events may admit beyond "
                         "previously-lost chips")
    ap.add_argument("--trace", type=str, default="",
                    help="write a Chrome/Perfetto trace_event JSON here "
                         "(plus a .jsonl next to it) — docs/OBSERVABILITY.md")
    ap.add_argument("--metrics", action="store_true",
                    help="dump the metrics registry and cost-model "
                         "calibration summary after the run")
    args = ap.parse_args()
    enable_compile_cache()

    # --trace/--metrics install an enabled observability bundle process-wide
    # before any orchestrator/engine is constructed; default stays NULL_OBS
    ob = obslib.get_obs()
    if args.trace or args.metrics:
        ob = obslib.set_obs(obslib.Obs())

    cfg = get_config(args.arch, reduced=args.reduced)
    model = build_model(cfg)
    mesh = None
    if args.mesh:
        mesh = parse_mesh_flag(args.mesh)
    elif args.orchestrate:
        # fault handling needs a mesh to remesh from; default to pure DP so
        # any survivor count can host the model axis
        mesh = make_elastic_mesh(model_parallel=1)

    pcfg = ParallelConfig(
        hierarchical_grad_sync=args.hierarchical_sync,
        compress_cross_pod=args.compress,
    )
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                          total_steps=args.steps)
    trainer = Trainer(model, opt_cfg, pcfg, mesh=mesh, microbatches=args.microbatches)
    params, opt = trainer.init(jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log.info(f"arch={cfg.name} params={n_params/1e6:.1f}M devices={len(jax.devices())}")

    start = 0
    if args.resume and args.ckpt_dir:
        last = latest_intact_step(args.ckpt_dir)
        if last is not None:
            (params, opt), start = restore_checkpoint(args.ckpt_dir, (params, opt),
                                                      step=last)
            start += 1
            log.info(f"resumed from step {start - 1}")

    pipe = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch)

    if args.orchestrate:
        orch = Orchestrator(
            model, opt_cfg, pcfg, mesh=mesh,
            schedule=load_schedule(args.fault_schedule),
            cfg=OrchestratorConfig(
                ckpt_dir=args.ckpt_dir or None,
                ckpt_every=args.ckpt_every if args.ckpt_dir else 0,
                drain_stragglers=args.drain_stragglers,
                autoscale=AutoscaleConfig(price_drains=not args.no_price_drains),
                spare_devices=args.spare_devices,
            ),
            microbatches=args.microbatches,
            obs=ob,
        )
        params, opt, report = orch.run(params, opt, pipe, args.steps, start_step=start)
        for line in report.log:
            log.info(line)
        log.info(
            f"orchestrated run done: {report.useful_steps} useful steps in "
            f"{report.wall_s:.1f}s (goodput {report.goodput():.2f} steps/s), "
            f"{len(report.remesh_events)} remesh "
            f"({len(report.drains_tolerated)} drains tolerated), "
            f"{len(report.sync_switches)} sync decisions, {report.restores} "
            f"restores, final {report.final_state}"
        )
        finish_obs(ob, args.trace, args.metrics)
        return

    step_fn = trainer.jitted_step(donate=False)
    monitor = StragglerMonitor()

    with use_mesh(mesh):
        for step in range(start, args.steps):
            if ob.enabled:
                ob.tracer.step = step
            monitor.step_start()
            batch = {k: jnp.asarray(v) for k, v in pipe.global_batch_arrays(step).items()}
            with ob.span("train_step", "train"):
                params, opt, metrics = step_fn(params, opt, batch)
                straggler = monitor.step_end()
            if step % args.log_every == 0 or step == args.steps - 1:
                log.info(
                    f"step {step:5d} loss {float(metrics['loss']):.4f} "
                    f"gnorm {float(metrics['grad_norm']):.3f} lr {float(metrics['lr']):.2e}"
                    f"{' [straggler]' if straggler else ''}"
                )
            if args.ckpt_dir and (step % args.ckpt_every == 0 or step == args.steps - 1):
                with ob.span("ckpt", "train"):
                    save_checkpoint(args.ckpt_dir, step, (params, opt))
    finish_obs(ob, args.trace, args.metrics)


if __name__ == "__main__":
    main()
