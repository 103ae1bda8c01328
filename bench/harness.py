"""One run of one cell: set-up, the measured window, the check.

Set-up (``setup_s``: process start to the window's first timed submit):
make the weights from the seed, build the engine, run every prefill
program the cell's traffic can reach (each prompt bucket x each
power-of-two group) and the decode program once, then bring the engine to
its steady state: the open loop runs its lead-in of arrivals, the closed
loop fills every slot.

The window (``seconds`` long) drives ``ContinuousBatchingEngine.submit``
and ``.step`` on the harness's own clock: arrivals are submitted when due,
and a token is stamped when the ``step()`` that produced it returns.  Then it
keeps stepping, without new arrivals, until every request due in the
window has its first token (at most ``DRAIN_S``).

Then the device's peak memory is read, the engine is freed, and the plain
reference checks a sample of the finished requests (``check.py``).
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import time
from collections import deque
from types import SimpleNamespace

import numpy as np

from bench import check, generator, spec, trace_reduce, weights
from bench.peaks import peaks

DRAIN_S = 60.0  # longest wait for first tokens after the window
# the traced stretch is the window's last TRACE_S: stopping the profiler
# holds the host for many seconds, so it stops once the window has closed
TRACE_S = 12.0
NOTHING_CHECKED = 1e9  # the widest gap of a run that finished no request
TRACE_DIR = spec.ROOT / ".bench_out" / "trace"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


_compiles = [0]


def _count_compiles():
    import jax

    if getattr(_count_compiles, "done", False):
        return

    def on_event(event, secs, **_):
        if event in COMPILE_EVENTS:
            _compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    _count_compiles.done = True


@dataclasses.dataclass
class Rec:
    """What the client saw of one request."""

    planned: generator.Planned
    rid: int
    due_s: float  # scheduled arrival (open) or submit time (closed)
    submit_s: float
    times: list = dataclasses.field(default_factory=list)  # one per token
    tokens: list | None = None  # served tokens, once finished


class Client:
    """Sends the traffic to the engine and stamps what comes back."""

    def __init__(self, engine, traffic, clock):
        import jax

        self.annotate = jax.profiler.TraceAnnotation
        self.engine, self.traffic, self.clock = engine, traffic, clock
        self.pending = deque(traffic.requests)
        self.inflight: dict[int, Rec] = {}
        self.recs: list[Rec] = []
        self.steps: list = []  # (t_end, decode contexts, prefill lengths)
        self.t0 = clock()

    def now(self) -> float:
        return self.clock() - self.t0

    def rebase(self) -> None:
        """Make the present the window's origin."""
        d = self.now()
        self.t0 += d
        for r in self.recs:
            r.due_s -= d
            r.submit_s -= d
            r.times = [t - d for t in r.times]
        self.steps = [(t - d, c, p) for t, c, p in self.steps]

    def _submit(self, p: generator.Planned, now: float) -> None:
        rid = self.engine.submit(p.prompt, p.max_new_tokens)
        due = p.arrival_s if p.arrival_s is not None else now
        rec = Rec(p, rid, due, now)
        self.recs.append(rec)
        self.inflight[rid] = rec

    def submit_due(self, until: float) -> None:
        with self.annotate("bench.submit"):
            now = self.now()
            if self.traffic.loop == "open":
                while self.pending and self.pending[0].arrival_s <= min(now, until):
                    self._submit(self.pending.popleft(), now)
            elif now < until:
                queued = sum(self.engine.requests[r].state == "queued" for r in self.inflight)
                while self.pending and queued < self.traffic.waiting:
                    self._submit(self.pending.popleft(), now)
                    queued += 1

    def idle_until(self, limit: float) -> bool:
        """Nothing in flight: sleep to the next arrival (or ``limit``).
        Returns False when there is nothing left to wait for."""
        if self.inflight:
            return True
        if not self.pending or self.traffic.loop != "open":
            return False
        with self.annotate("bench.idle"):
            wake = min(self.pending[0].arrival_s, limit)
            while self.now() < wake:
                time.sleep(min(1e-3, max(wake - self.now(), 0.0)))
        return True

    def step(self) -> float:
        before = {rid: len(rec.times) for rid, rec in self.inflight.items()}
        with self.annotate("bench.step"):
            self.engine.step()
        t = self.now()
        with self.annotate("bench.record"):
            ctx, pre = [], []
            for rid, rec in list(self.inflight.items()):
                req = self.engine.requests[rid]
                n_after = len(req.tokens_out)
                new = n_after - before.get(rid, 0)
                if new:
                    rec.times.extend([t] * new)
                    admitted = before.get(rid, 0) == 0
                    if admitted:
                        pre.append(req.prompt_len)
                    if new - admitted == 1:  # this step's decode served it
                        ctx.append(req.prompt_len + n_after - 1)
                if req.state == "finished":
                    rec.tokens = list(req.tokens_out)
                    del self.inflight[rid]
            self.steps.append((t, ctx, pre))
        return t

    def running(self) -> int:
        return sum(self.engine.requests[r].state == "running" for r in self.inflight)


def _counters(engine) -> dict:
    m = engine.metrics
    return {k: getattr(m, k) for k in
            ("decode_steps", "prefills", "active_slot_steps", "total_slot_steps")}


def _warm_programs(engine, traffic_mix: dict, n_slots: int) -> int:
    """Run each program the traffic can reach once: every (power-of-two
    group <= what can be admitted at once) x (bucket of a reachable prompt
    length), then the decode step."""
    buckets = sorted({engine._bucket(int(n))
                      for n in generator.reachable_prompt_lengths(traffic_mix)})
    most = n_slots if traffic_mix["loop"] == "open" else min(n_slots, traffic_mix["waiting"])
    groups = [1 << i for i in range(most.bit_length())]
    n = 0
    for bucket in buckets:
        length = bucket // 2 + 1  # lands in ``bucket`` and leaves room for a token
        for g in groups:
            for _ in range(g):
                engine.submit(np.ones(length, np.int32), 1)
            engine.step()
            n += 1
    engine.submit(np.ones(8, np.int32), 2)
    while engine.step():
        pass
    return n + 1


def _result_metrics(entries, ctx) -> dict:
    out = {}
    for m in entries:
        value = spec.metric_reader(m["name"])(SimpleNamespace(**ctx, name=m["name"]))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, *, t_start: float,
        require_tpu: bool = True, reduced: bool = False, overrides: dict | None = None,
        hooks=None, control: bool = False) -> dict:
    """One run; returns the result object (the last stdout line) with the
    comparison's numbers under ``checks``.

    The benchmark's command uses none of the keywords after ``t_start``.
    Rehearsals and readings use them: ``require_tpu=False`` skips the look
    for a chip, ``reduced`` swaps in the registry's CPU-sized model,
    ``overrides`` (``{"cell": {...}, "traffic": {...}}``) replaces cell and
    mix parameters, ``hooks(engine)`` may wrap the engine's programs, and
    ``control`` puts the control's widest gap, read on the same sample, in
    the program's place in ``checks`` (the program's own stays beside it)."""
    import jax

    cell = spec.load_cell(workload)
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(f"{workload} needs {cell.chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    _count_compiles()
    params = dict(cell.cell)
    mix = dict(cell.traffic)
    for key, target in (("cell", params), ("traffic", mix)):
        target.update((overrides or {}).get(key, {}))
    generator.validate(mix)
    spec.validate_cell(params, mix["loop"])
    family = spec.family(cell.config)
    cfg = family.model_config(cell.config, reduced)
    rcfg = family.reference_config(cfg)
    peak = peaks(devices[0].device_kind) if require_tpu else None

    from bench import program

    w = weights.make_weights(family.shapes(rcfg), seed)
    engine = program.build_engine(family, cfg, w, int(params["n_slots"]),
                                  int(params["max_len"]))
    if hooks:
        hooks(engine)
    n_warm = _warm_programs(engine, mix, int(params["n_slots"]))
    vocab = cfg.vocab
    traffic = generator.make_traffic(mix, params, seed, seconds, vocab)
    client = Client(engine, traffic, time.perf_counter)

    # ---- steady state before the window
    if traffic.loop == "open":
        client.t0 += float(params["lead_in_s"])
        while client.now() < 0:
            client.submit_due(0.0)
            if client.idle_until(0.0):
                client.step()
    else:
        while client.running() < engine.pool.n_slots and client.pending:
            client.submit_due(float("inf"))
            client.step()
        client.submit_due(float("inf"))
        client.rebase()
    setup_s = client.t0 - t_start
    log(f"set-up: {setup_s:.3f} s, {n_warm} programs warmed, {len(client.recs)} requests "
        f"before the window")

    # ---- the window
    compiles0 = _compiles[0]
    c0 = _counters(engine)
    t_a = max(0.0, seconds - TRACE_S)
    tracing, traced = None, None
    trace_dir = TRACE_DIR / workload
    while True:
        now = client.now()
        if trace and tracing is None and traced is None and now >= t_a:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
            tracing = [jax.profiler.TraceAnnotation("bench.window"), len(client.steps)]
            tracing[0].__enter__()
        if tracing is not None and now >= seconds:
            tracing[0].__exit__(None, None, None)
            jax.profiler.stop_trace()
            traced, tracing = (tracing[1], len(client.steps)), None
        if now >= seconds:
            break
        client.submit_due(seconds)
        if not client.idle_until(seconds):
            break
        if client.now() < seconds and client.inflight:
            client.step()
    c1 = _counters(engine)
    compiles = _compiles[0] - compiles0

    # ---- first tokens of everything due in the window
    due = [r for r in client.recs if 0 <= r.due_s < seconds]
    t_drain = client.now()
    while (any(not r.times for r in due) and client.inflight
           and client.now() < t_drain + DRAIN_S):
        client.step()
    devices_used = devices[: cell.chips]
    mem = [d.memory_stats() or {} for d in devices_used]
    memory_peak = max(m.get("peak_bytes_in_use", 0) for m in mem)

    # ---- numbers
    attempted = len(due)
    failed = sum(1 for r in due if not r.times)
    ctx = {
        "recs": client.recs, "seconds": float(seconds), "setup_s": setup_s, "loop": traffic.loop,
        "counters": {k: c1[k] - c0[k] for k in c0}, "cfg": rcfg, "family": family, "peak": peak,
        "trace": None, "traced_steps": None, "log": log,
    }
    red = None
    if traced is not None:
        red = trace_reduce.reduce(trace_reduce.load(str(trace_dir)))
        ctx.update(trace=red, traced_steps=client.steps[traced[0]:traced[1]])
        log(f"trace: window {red['window_s']:.6f} s, busy {red['busy_s']:.6f} s, "
            f"{len(client.steps[traced[0]:traced[1]])} steps, modules "
            + ", ".join(f"{k} {v['s']:.6f} s/{v['n']}" for k, v in red["modules"].items()))
        log("longest idle gaps: " + ", ".join(f"{k} {v:.6f} s" for k, v in red["longest_gaps"][:3]))
        log("idle by host activity: " + ", ".join(
            f"{k} {v:.6f} s" for k, v in sorted(red["idle_by_activity"].items(),
                                               key=lambda kv: -kv[1])))
    metrics = _result_metrics(cell.per_layer if trace else cell.end_to_end, ctx)
    lateness = [r.submit_s - r.due_s for r in client.recs if 0 <= r.due_s < seconds]
    log(f"window: {compiles} compilations inside the window; {attempted} requests "
        f"attempted, {failed} failed, {sum(r.tokens is not None for r in client.recs)} "
        f"finished in all; {ctx['counters']['decode_steps']} decode steps, "
        f"{ctx['counters']['prefills']} prefills")
    load = {
        "due_in_window": len(due),
        "waiting_at_close": sum(1 for r in due if not r.times or r.times[0] >= seconds),
        "finished_in_window": sum(1 for r in client.recs if r.tokens and r.times[-1] < seconds),
    }
    log(f"load: {load}")
    if lateness and traffic.loop == "open":
        log(f"generator lateness: p50 {np.percentile(lateness, 50) * 1e3:.3f} ms, "
            f"max {max(lateness) * 1e3:.3f} ms")

    # ---- the check, once the program's state is freed
    finished = [(r.planned.prompt, np.asarray(r.tokens, np.int64))
                for r in client.recs if r.tokens]
    picked = check.sample(finished, seed, int(params["check_tokens"]))
    engine.pool.caches = None
    del engine, client
    gc.collect()
    t_check = time.perf_counter()
    outside = sum(int(((s < 0) | (s >= vocab)).sum()) for _, s in finished)
    gap = (check.widest_gap(family.logits_at, w, rcfg,
                            [(p, np.clip(s, 0, vocab - 1)) for p, s in picked])
           if picked else {"value": NOTHING_CHECKED, "tokens": 0, "off_argmax": 0})
    log(f"check: {gap['tokens']} served tokens of {len(picked)} requests, "
        f"{gap['off_argmax']} off the reference argmax, widest gap {gap['value']!r}, "
        f"{time.perf_counter() - t_check:.3f} s")
    if control:
        ctl = check.widest_gap(family.logits_at, w, rcfg, picked, control=True)
        log(f"control: widest gap {ctl['value']!r} over {ctl['tokens']} tokens, "
            f"{ctl['off_argmax']} off the reference argmax (in the program's place)")
    limits = params["limits"]
    checks = {
        "logit_gap": {"value": (ctl if control else gap)["value"],
                      "limit": limits["max_logit_gap"]},
        "tokens_outside_vocab": {"value": outside, "limit": 0},
        "requests_unserved": {"value": failed, "limit": 0},
    }
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(memory_peak),
        },
    }
    if red is not None:
        result["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
        idle = sorted(red["idle_by_activity"].items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {"device_ops": red["top_ops"],
                               "idle_gaps": [[k, v] for k, v in idle]}
    result["load"] = load
    if control:
        result["control"] = ctl
        result["program_gap"] = gap
    result["checks"] = checks
    return result
