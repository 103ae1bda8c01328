"""Serving launcher: continuous batching (default) or the one-shot baseline.

  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --reduced \
      --requests 16 --slots 4 --prompt-len 32 --new-tokens 16

  # one-shot lockstep baseline (the seed behaviour)
  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --reduced \
      --one-shot --batch 4 --prompt-len 32 --new-tokens 16

Continuous mode submits a ragged closed-loop workload (prompt lengths and
token budgets jittered around --prompt-len/--new-tokens), serves it through
the pooled-KV scheduler, and reports tokens/s plus slot utilization.  See
docs/SERVING.md for the scheduler/KV-pool knobs.

Elastic fault-tolerant mode (docs/SERVING.md, elasticity section — the
serving mirror of ``launch/train.py --orchestrate``): --orchestrate runs
the engine under ``runtime.serving_elastic.ServingOrchestrator`` —
device/pod-loss events migrate the live KV pool onto the survivor mesh,
stragglers are drained, link degradation re-prices admission.  Without
--mesh the engine gets an elastic mesh over all visible devices.  Inject
faults with --fault-schedule '<json>' (or @file.json), e.g.

  --orchestrate --fault-schedule \
      '[{"step": 20, "kind": "device_loss", "devices": 2}]'

Tiered KV-cache pooling (docs/SERVING.md, memory hierarchy): --tiered gives
every request a session identity; finished sessions demote their cache row
into the HBM -> host -> pooled hierarchy instead of discarding it, and
--turns N resumes each session N-1 more times — wakeups page the resident
row back in and skip re-prefill.  --host-sessions/--pooled-sessions size
the tier ledgers.

  PYTHONPATH=src python -m repro.launch.serve --reduced --tiered --turns 3 \
      --requests 24 --slots 4 --host-sessions 12 --pooled-sessions 12
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from .. import obs as obslib
from ..configs.base import ARCH_IDS, get_config
from ..models import build_model
from ..obs import log
from ..runtime.orchestrator import load_schedule
from ..runtime.serving import ContinuousBatchingEngine, ServingEngine
from ..runtime.serving_elastic import ServingOrchestrator
from ..runtime.sharding import reshard_params
from .compile_cache import enable_compile_cache
from .mesh import make_elastic_mesh, parse_mesh_flag
from .train import finish_obs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--one-shot", action="store_true",
                    help="seed ServingEngine: one fixed batch, lockstep decode")
    ap.add_argument("--batch", type=int, default=4, help="one-shot batch size")
    ap.add_argument("--requests", type=int, default=16,
                    help="continuous mode: number of ragged requests")
    ap.add_argument("--slots", type=int, default=4, help="KV-pool decode slots")
    ap.add_argument("--policy", choices=["fcfs", "cost_aware"], default="cost_aware")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--mesh", type=str, default="",
                    help="DxM e.g. 4x1, or PxDxM for a pod axis (orchestrated mode)")
    ap.add_argument("--orchestrate", action="store_true",
                    help="elastic fault-tolerant serving (docs/SERVING.md)")
    ap.add_argument("--fault-schedule", type=str, default="",
                    help="JSON list of fault events, or @path/to/file.json "
                         "(events are keyed by engine step)")
    ap.add_argument("--open-rate", type=float, default=0.0,
                    help="Poisson arrival rate in req/s (0 = closed loop)")
    ap.add_argument("--tiered", action="store_true",
                    help="tiered KV-cache pooling: demote finished sessions "
                         "into the HBM -> host -> pooled hierarchy")
    ap.add_argument("--host-sessions", type=int, default=64,
                    help="tiered: cache rows kept in host memory")
    ap.add_argument("--pooled-sessions", type=int, default=256,
                    help="tiered: rows spilled to the modeled pooled tier")
    ap.add_argument("--turns", type=int, default=1,
                    help="tiered: serve each session this many turns; turns "
                         "after the first resume the demoted session")
    ap.add_argument("--shed-depth", type=int, default=0,
                    help="orchestrated: shed the queue tail once the arrived "
                         "backlog exceeds this depth (0 = never shed)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="drop requests not admitted within this many seconds "
                         "of arrival (0 = no deadlines)")
    ap.add_argument("--spare-devices", type=int, default=0,
                    help="warm spares device_gain events may admit beyond "
                         "previously-lost chips")
    ap.add_argument("--no-price-drains", action="store_true",
                    help="always drain stragglers instead of pricing the "
                         "migration against the remaining slowdown")
    ap.add_argument("--trace", type=str, default="",
                    help="write a Chrome/Perfetto trace_event JSON here "
                         "(plus a .jsonl next to it) — docs/OBSERVABILITY.md")
    ap.add_argument("--metrics", action="store_true",
                    help="dump the metrics registry and cost-model "
                         "calibration summary after the run")
    args = ap.parse_args()
    enable_compile_cache()

    # --trace/--metrics install an enabled observability bundle process-wide
    # before the engine is constructed; default stays NULL_OBS
    ob = obslib.get_obs()
    if args.trace or args.metrics:
        ob = obslib.set_obs(obslib.Obs())

    cfg = get_config(args.arch, reduced=args.reduced)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    if args.one_shot:
        engine = ServingEngine(model, params, max_len=args.prompt_len + args.new_tokens + 8)
        prompts = rng.integers(1, cfg.vocab, (args.batch, args.prompt_len)).astype(np.int32)
        t0 = time.time()
        out = engine.generate(prompts, args.new_tokens, temperature=args.temperature)
        dt = time.time() - t0
        toks = args.batch * args.new_tokens
        log.info(f"generated {toks} tokens in {dt:.2f}s ({toks/dt:.1f} tok/s incl. compile)")
        for row in out[: min(args.batch, 4)]:
            log.debug(f"  {row.tolist()}")
        finish_obs(ob, args.trace, args.metrics)
        return

    mesh = None
    if args.mesh:
        mesh = parse_mesh_flag(args.mesh)
    elif args.orchestrate:
        # fault handling needs a mesh to remesh from; default to pure DP so
        # any survivor count can host the model axis
        mesh = make_elastic_mesh(model_parallel=1)
    if mesh is not None:
        params = reshard_params(model.param_axes(), params, mesh)

    tiers = None
    resume_budget = max(args.new_tokens // 2, 1)
    if args.tiered:
        from ..runtime.serving import TierConfig

        tiers = TierConfig(host_sessions=args.host_sessions,
                           pooled_sessions=args.pooled_sessions)
    # later turns append to each session's history, so capacity must hold
    # the full multi-turn transcript
    max_len = (args.prompt_len + args.new_tokens
               + max(args.turns - 1, 0) * resume_budget + 8)
    engine = ContinuousBatchingEngine(
        model, params, n_slots=args.slots, max_len=max_len, policy=args.policy,
        mesh=mesh, tiers=tiers,
    )
    lens = rng.integers(max(args.prompt_len // 2, 1), args.prompt_len + 1, args.requests)
    budgets = rng.integers(max(args.new_tokens // 4, 1), args.new_tokens + 1, args.requests)
    arrivals = None
    if args.open_rate > 0:
        arrivals = np.cumsum(rng.exponential(1.0 / args.open_rate, args.requests))

    t0 = time.time()
    base = time.monotonic()
    prompts = [rng.integers(1, cfg.vocab, (int(l),)).astype(np.int32) for l in lens]
    rids = [
        engine.submit(
            p,
            int(b),
            temperature=args.temperature,
            arrival_time=None if arrivals is None else base + float(arrivals[i]),
            session_id=i if args.tiered else None,
            deadline=(
                None if args.deadline_s <= 0
                else base + (float(arrivals[i]) if arrivals is not None else 0.0)
                + args.deadline_s
            ),
        )
        for i, (p, b) in enumerate(zip(prompts, budgets))
    ]

    if args.orchestrate:
        from ..runtime.autoscale import AutoscaleConfig
        from ..runtime.serving_elastic import ServingOrchestratorConfig

        ocfg = ServingOrchestratorConfig(
            autoscale=AutoscaleConfig(
                shed_depth=args.shed_depth or None,
                resume_depth=max(args.shed_depth // 4, 1),
                deadline_s=args.deadline_s or None,
                price_drains=not args.no_price_drains,
            ),
            spare_devices=args.spare_devices,
        )
        orch = ServingOrchestrator(engine, load_schedule(args.fault_schedule),
                                   cfg=ocfg)
        out = orch.run()
        dt = time.time() - t0
        report = orch.report
        for line in report.log:
            log.info(line)
        log.info(
            f"orchestrated serving done: {report.tokens} tokens in "
            f"{report.wall_s:.2f}s (goodput {report.goodput():.1f} tok/s), "
            f"{len(report.migrations)} migrations ({len(report.drains)} "
            f"straggler drains, {len(report.drains_tolerated)} tolerated), "
            f"{report.shed + engine.metrics.deadline_drops} shed, "
            f"{len(report.repricings)} repricings, final {report.final_state}"
        )
    else:
        out = engine.run()
        dt = time.time() - t0

    toks = sum(len(out[r]) for r in rids if r in out)

    # multi-turn sessions: wake every demoted session for each extra turn —
    # resident rows page back in and skip re-prefill; dropped ones
    # re-prefill cold (either way the stream stays bit-exact)
    if args.tiered and args.turns > 1:
        histories = {i: np.concatenate([prompts[i], out[rids[i]]])
                     for i in range(len(rids)) if rids[i] in out}
        for _ in range(args.turns - 1):
            turn_rids = {
                i: engine.submit(h, resume_budget,
                                 temperature=args.temperature, session_id=i)
                for i, h in histories.items()
            }
            turn_out = engine.run()
            for i, r in turn_rids.items():
                histories[i] = np.concatenate([histories[i], turn_out[r]])
                toks += len(turn_out[r])
        dt = time.time() - t0

    m = engine.metrics
    log.info(
        f"served {len(rids)} ragged requests / {toks} tokens in {dt:.2f}s "
        f"({toks/dt:.1f} tok/s incl. compile)"
    )
    log.info(
        f"slots={engine.pool.n_slots} policy={args.policy} decode_steps={m.decode_steps} "
        f"prefills={m.prefills} slot_utilization={m.slot_utilization:.2f} "
        f"pool_evictions={engine.pool.n_evict}"
    )
    if args.tiered:
        p = engine.pool
        log.info(
            f"tiers: resident_sessions={p.resident_sessions} "
            f"(host={len(p.host)} pooled={len(p.pooled)} dropped={len(p.dropped)}) "
            f"demotions={p.n_demote} wakeups={m.wakeups} "
            f"cold_resumes={m.cold_resumes} spills={p.n_spill} "
            f"refills={p.n_refill} modeled_tier_s={p.modeled_tier_s:.4f}"
        )
    for r in [r for r in rids if r in out][:4]:
        log.debug(f"  {out[r].tolist()}")
    if ob.enabled:
        engine.absorb_pool_metrics()
    finish_obs(ob, args.trace, args.metrics)


if __name__ == "__main__":
    main()
