"""Chip smoke test: serve internlm2-1.8b at its published width on a TPU.

  python chip_smoke.py              # one chip: continuous-batching serving
  python chip_smoke.py --chips 4    # four chips: elastic serving only

The model is the full-width registry config (no ``reduced``) with random
weights from a fixed seed.  The one-chip phase serves ragged requests
through ``ContinuousBatchingEngine``; the four-chip phase serves the same
traffic under ``ServingOrchestrator`` on a 4x1 (data x model) mesh, loses
two chips mid-run, and compares the completed streams with a fault-free
run on the surviving mesh.

Progress lines go to stdout; the last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
A failed check raises and exits non-zero without that line, and so does a
run in which JAX finds no TPU.  All work happens in this one process.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.jax_compat import make_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.runtime.orchestrator import FaultEvent, FaultSchedule  # noqa: E402
from repro.runtime.serving import ContinuousBatchingEngine  # noqa: E402
from repro.runtime.serving_elastic import ServingOrchestrator  # noqa: E402
from repro.runtime.sharding import param_shardings  # noqa: E402

ARCH = "internlm2-1.8b"
SEED = 0
N_REQUESTS = 8
PROMPT_LENS = (128, 512)  # inclusive range of prompt lengths
NEW_TOKENS = 32
N_SLOTS = 4
MAX_LEN = 2048  # pool capacity per slot: params + pool + decode temps fit 16 GB
FAULT_STEP = 16  # engine step at which the elastic phase loses chips
LOST_DEVICES = 2
# The engine prefills right-padded prompt groups, the reference one prompt
# alone; a migrated row was decoded on a larger mesh than the reference's.
# Different shapes, so a different order of bf16 arithmetic: two greedy
# tokens may differ only where the reference logits of both lie within
# this many bf16 units in the last place of each other.  On one v5e chip
# the first tokens agree exactly; on four, three of eight streams diverge
# after the migration at ties of margin <= 0.03125.
TIE_ULPS = 8


def log(msg: str) -> None:
    print(msg, flush=True)


def make_prompts(vocab: int, n: int = N_REQUESTS, lens=PROMPT_LENS, seed: int = SEED):
    rng = np.random.default_rng(seed)
    lengths = rng.integers(lens[0], lens[1] + 1, n)
    return [rng.integers(1, vocab, (int(l),)).astype(np.int32) for l in lengths]


def init_params(model, seed: int = SEED, mesh=None):
    """Random weights from ``seed``, made where they will live: on a mesh
    each device builds its own copy, so none ever holds two."""
    key = jax.random.PRNGKey(seed)
    out = None
    if mesh is not None:
        out = param_shardings(model.param_axes(), mesh, jax.eval_shape(model.init, key))
    return jax.block_until_ready(jax.jit(model.init, out_shardings=out)(key))


def tie_tolerance(logit: float) -> float:
    """``TIE_ULPS`` bf16 units in the last place at ``logit``."""
    return TIE_ULPS * 2.0 ** (np.floor(np.log2(max(abs(logit), 1e-30))) - 7)


def serve(engine, prompts, new_tokens: int, run) -> list[np.ndarray]:
    """Submit ``prompts`` greedily, drive ``run()``; every request must
    finish with ``new_tokens`` tokens inside the vocabulary."""
    rids = [engine.submit(p, new_tokens) for p in prompts]
    out = run()
    missing = [r for r in rids if r not in out]
    if missing:
        raise AssertionError(f"requests {missing} did not finish")
    vocab = engine.model.cfg.vocab
    for r in rids:
        toks = out[r]
        if len(toks) != new_tokens:
            raise AssertionError(f"request {r}: {len(toks)} tokens, want {new_tokens}")
        if toks.min() < 0 or toks.max() >= vocab:
            raise AssertionError(f"request {r}: token outside [0, {vocab})")
    return [out[r] for r in rids]


def check_pool_drained(engine) -> None:
    pool = engine.pool
    pool.check()
    if pool.n_used or pool.n_alloc != pool.n_evict:
        raise AssertionError(
            f"KV slots leaked: {pool.n_used} in use, {pool.n_alloc} allocations "
            f"vs {pool.n_evict} evictions"
        )


def next_token_logits(prefill, params, tokens: np.ndarray) -> np.ndarray:
    logits, _ = prefill(params, {"tokens": jnp.asarray(tokens[None])})
    logits = np.asarray(logits[0, 0], np.float32)
    if not np.isfinite(logits).all():
        raise AssertionError("reference logits are not finite")
    return logits


def check_first_tokens(model, params, prompts, streams) -> float:
    """Each stream's first token is the argmax of a plain full forward of
    its prompt alone (``Model.prefill`` on the unpadded prompt), up to a
    near tie.  Returns the smallest top-2 margin of the reference."""
    prefill = jax.jit(model.prefill)
    smallest = np.inf
    for i, (prompt, toks) in enumerate(zip(prompts, streams)):
        logits = next_token_logits(prefill, params, prompt)
        best = int(np.argmax(logits))
        top2 = np.sort(logits)[-2:]
        smallest = min(smallest, float(top2[1] - top2[0]))
        got = int(toks[0])
        if got != best:
            margin = float(logits[best] - logits[got])
            if margin > tie_tolerance(logits[best]):
                raise AssertionError(
                    f"request {i}: first token {got}, reference argmax {best}, "
                    f"margin {margin} beyond a near tie"
                )
            log(f"request {i}: first token {got} vs reference {best}: near tie, "
                f"margin {margin}")
    return smallest


def serving_phase(cfg, *, prompts, new_tokens=NEW_TOKENS, n_slots=N_SLOTS,
                  max_len=MAX_LEN, seed=SEED) -> dict:
    """One device: the continuous-batching engine as ``launch/serve.py``
    builds it, run to completion, checked against plain prefills."""
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = init_params(model, seed)
    t1 = time.perf_counter()
    engine = ContinuousBatchingEngine(model, params, n_slots=n_slots, max_len=max_len)
    streams = serve(engine, prompts, new_tokens, engine.run)
    t2 = time.perf_counter()
    check_pool_drained(engine)
    margin = check_first_tokens(model, params, prompts, streams)
    return {
        "tokens": int(sum(len(s) for s in streams)),
        "init_s": t1 - t0,
        "serve_wall_s": t2 - t1,
        "reference_s": time.perf_counter() - t2,
        "decode_steps": engine.metrics.decode_steps,
        "prefills": engine.metrics.prefills,
        "min_reference_top2_margin": margin,
    }


def elastic_phase(cfg, devices, *, prompts, new_tokens=NEW_TOKENS, n_slots=N_SLOTS,
                  max_len=MAX_LEN, fault_step=FAULT_STEP, lost=LOST_DEVICES,
                  seed=SEED) -> dict:
    """Several devices: the engine under ``ServingOrchestrator`` on an
    ``n x 1`` (data x model) mesh loses ``lost`` devices at ``fault_step``.
    Its completed streams must match a fault-free run on the surviving
    mesh token for token, up to near ties (each divergence is reported)."""
    model = build_model(cfg)
    mesh = make_mesh((len(devices), 1), ("data", "model"), devices=devices)
    t0 = time.perf_counter()
    engine = ContinuousBatchingEngine(
        model, init_params(model, seed, mesh), n_slots=n_slots, max_len=max_len,
        mesh=mesh,
    )
    init_s = time.perf_counter() - t0
    orch = ServingOrchestrator(
        engine, FaultSchedule([FaultEvent(step=fault_step, kind="device_loss",
                                          devices=lost)])
    )
    t0 = time.perf_counter()
    faulted = serve(engine, prompts, new_tokens, orch.run)
    wall_s = time.perf_counter() - t0
    check_pool_drained(engine)
    (mig,) = orch.report.migrations
    if mig["migrated_slots"] < 1:
        raise AssertionError(f"the device loss at step {fault_step} migrated no live row")
    survivors = engine.mesh
    if survivors.devices.size != len(devices) - lost:
        raise AssertionError(f"survivor mesh has {survivors.devices.size} devices")
    params = engine.params
    del engine, orch  # free the faulted pool before the reference builds its own

    ref_engine = ContinuousBatchingEngine(
        model, params, n_slots=n_slots, max_len=max_len, mesh=survivors
    )
    reference = serve(ref_engine, prompts, new_tokens, ref_engine.run)
    check_pool_drained(ref_engine)

    prefill = jax.jit(model.prefill)
    divergences = []
    for i, (a, b) in enumerate(zip(faulted, reference)):
        differ = np.flatnonzero(a != b)
        if not differ.size:
            continue
        j = int(differ[0])
        logits = next_token_logits(prefill, params, np.concatenate([prompts[i], b[:j]]))
        margin = float(logits[b[j]] - logits[a[j]])
        div = {"request": i, "token_index": j, "faulted": int(a[j]),
               "reference": int(b[j]), "reference_logit_margin": margin}
        log(f"divergence: {div}")
        if abs(margin) > tie_tolerance(max(logits[a[j]], logits[b[j]])):
            raise AssertionError(f"streams diverge beyond a near tie: {div}")
        divergences.append(div)
    margin = check_first_tokens(model, params, prompts, faulted)
    return {
        "tokens": int(sum(len(s) for s in faulted)),
        "init_s": init_s,
        "serve_wall_s": wall_s,
        "migration": {k: mig[k] for k in ("step", "migrated_slots", "n_slots", "mesh",
                                          "migrate_s")},
        "identical_streams": not divergences,
        "divergences": divergences,
        "min_reference_top2_margin": margin,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serving phase; 4: elastic serving across four chips only")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found {devices[0].platform!r}")
    if args.chips > 1 and len(devices) != args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices, "
                         f"found {len(devices)}")
    log(f"cache: {enable_compile_cache()}")
    compile_s = [0.0]

    def on_duration(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    kind = devices[0].device_kind
    log(f"device_kind={kind} count={len(devices)} jax={jax.__version__}")

    cfg = get_config(ARCH)
    prompts = make_prompts(cfg.vocab)
    log(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, vocab {cfg.vocab}, "
        f"{N_REQUESTS} requests of {[len(p) for p in prompts]} prompt tokens, "
        f"{NEW_TOKENS} new tokens each")
    t0 = time.perf_counter()
    if args.chips == 1:
        stats = serving_phase(cfg, prompts=prompts)
    else:
        stats = elastic_phase(cfg, devices, prompts=prompts)
    stats.update(
        wall_s=time.perf_counter() - t0,
        compile_s=compile_s[0],
        peak_bytes_in_use=max(d.memory_stats()["peak_bytes_in_use"] for d in devices),
    )
    log(json.dumps({"phase": "serving" if args.chips == 1 else "elastic", **stats}))
    print(json.dumps({"ok": True, "device": {"platform": devices[0].platform,
                                             "kind": kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
