"""Unified observability layer (docs/OBSERVABILITY.md): trace round-trips,
registry/report bit-compatibility, cost-model calibration completeness, the
disabled-path overhead guard, and the launcher --trace smokes."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses
import gc
import json
import sys
import tracemalloc

import jax
import numpy as np
import pytest

from repro import obs as obslib
from repro.obs import NULL_OBS, NULL_SPAN, Obs, get_obs, log, provenance, set_obs
from repro.obs.calibration import CalibrationLedger, summarize_records
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer, load_chrome, load_jsonl


@pytest.fixture(autouse=True)
def _restore_null_obs():
    """The process-wide bundle must never leak across tests."""
    yield
    set_obs(None)


@pytest.fixture(scope="module")
def model():
    from repro.configs.base import get_config
    from repro.models import build_model

    cfg = get_config("internlm2-1.8b", reduced=True)
    cfg = dataclasses.replace(cfg, compute_dtype="float32", remat=False,
                              n_layers=2)
    return build_model(cfg)


# ------------------------------------------------------------------ tracer
def _demo_tracer():
    tr = Tracer()
    tr.step = 3
    with tr.span("train_step", "train"):
        pass
    with tr.span("remesh", "train", kind="device_loss", survivors=4) as sp:
        sp.set(reshard_s=0.05)
    tr.instant("sync_switch", "train", tier="compressed", switched=True)
    tr.step = 4
    with tr.span("decode", "serve"):
        pass
    return tr


def test_tracer_jsonl_roundtrip(tmp_path):
    tr = _demo_tracer()
    path = tr.export_jsonl(str(tmp_path / "t.jsonl"))
    events = load_jsonl(path)
    assert [e["name"] for e in events] == [
        "train_step", "remesh", "sync_switch", "decode"]
    remesh = events[1]
    assert remesh["args"] == {"kind": "device_loss", "survivors": 4,
                              "reshard_s": 0.05}
    assert remesh["step"] == 3 and events[3]["step"] == 4
    assert remesh["ph"] == "X" and remesh["dur"] >= 0
    assert events[2]["ph"] == "i"
    # the meta header survives
    first = json.loads(open(path).readline())
    assert first["meta"]["n_events"] == 4


def test_chrome_export_is_perfetto_loadable_and_reparses(tmp_path):
    tr = _demo_tracer()
    path = tr.export_chrome(str(tmp_path / "t.json"))
    doc = json.load(open(path))
    assert isinstance(doc["traceEvents"], list)
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert phases <= {"X", "i", "M"}
    for e in doc["traceEvents"]:
        if e["ph"] == "M":
            continue
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        if e["ph"] == "X":
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
    # distinct categories land on distinct lanes (tids)
    tids = {e["cat"]: e["tid"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert tids["train"] != tids["serve"]
    # round-trip: the re-parser reconstructs the event stream
    events = load_chrome(path)
    assert [e["name"] for e in events] == [
        "train_step", "remesh", "sync_switch", "decode"]
    assert events[1]["args"]["kind"] == "device_loss"
    assert events[1]["step"] == 3


def test_null_obs_is_inert():
    assert not NULL_OBS.enabled
    sp = NULL_OBS.span("anything", "train")
    assert sp is NULL_SPAN
    with sp as inner:
        inner.set(whatever=2)  # all no-ops
    NULL_OBS.instant("x", "y")
    assert NULL_OBS.tracer is None and NULL_OBS.registry is None


def test_set_obs_installs_and_restores():
    assert get_obs() is NULL_OBS
    ob = set_obs(Obs())
    assert get_obs() is ob and ob.enabled
    set_obs(None)
    assert get_obs() is NULL_OBS


# ---------------------------------------------------------------- registry
def test_metrics_registry_basics():
    reg = MetricsRegistry()
    c = reg.counter("train.useful_steps")
    c.inc()
    c.inc(2)
    assert reg["train.useful_steps"].value == 3
    g = reg.gauge("sim.stream.msgs_per_s")
    g.set(1234.5)
    h = reg.histogram("serve.decode_ms")
    h.observe(2.0)
    h.observe(4.0)
    assert h.mean == pytest.approx(3.0)
    assert reg.counter("train.useful_steps") is c  # get-or-create
    with pytest.raises(TypeError):
        reg.gauge("train.useful_steps")  # kind conflict
    reg.absorb("serve.pool", {"n_evict": 7, "high_water": 3})
    assert reg["serve.pool.n_evict"].value == 7
    d = reg.as_dict()
    assert d["sim.stream.msgs_per_s"] == 1234.5
    assert "serve.decode_ms" in reg.names()


def test_reports_are_bit_compatible_views():
    """Report classes stay drop-in: same defaults, same to_json key order,
    fields round-trip through the registry storage."""
    from repro.runtime.orchestrator import OrchestratorReport
    from repro.runtime.serving import EngineMetrics
    from repro.runtime.serving_elastic import ServingReport

    rep = OrchestratorReport()
    assert rep.useful_steps == 0 and rep.final_state == "TRAINING"
    rep.useful_steps += 5
    rep.wall_s = 1.5
    assert list(rep.to_json()) == [
        "useful_steps", "wall_s", "restores", "remesh_events",
        "sync_switches", "straggler_steps", "straggler_drains",
        "drains_tolerated", "injected_slow_s", "slow_s_avoided",
        "mesh_history", "log", "final_state"]
    assert rep.to_json()["useful_steps"] == 5
    assert rep.goodput() == pytest.approx(5 / 1.5)

    srep = ServingReport()
    assert list(srep.to_json()) == [
        "steps", "tokens", "step_tokens", "wall_s", "migrations", "drains",
        "drains_tolerated", "shed", "controller_transitions", "repricings",
        "injected_slow_s", "slow_s_avoided", "mesh_history", "log",
        "final_state"]
    assert srep.final_state == "SERVING"

    # serving_bench resets engine metrics via `type(engine.metrics)()`
    m = EngineMetrics()
    m.decode_steps += 3
    m2 = type(m)()
    assert m2.decode_steps == 0 and m.slot_utilization == 0.0

    # a fresh report over a SHARED registry re-zeroes its scalars
    reg = MetricsRegistry()
    a = OrchestratorReport(registry=reg)
    a.useful_steps = 9
    b = OrchestratorReport(registry=reg)
    assert b.useful_steps == 0
    assert reg["train.useful_steps"].value == 0


# ------------------------------------------------------------- calibration
def test_calibration_ledger_and_summary():
    led = CalibrationLedger()
    r1 = led.record("grad_sync", 1.0, alternative_s=2.0, chosen="plain", step=1)
    led.observe(r1, 1.5)           # observed still below alternative: no flip
    r2 = led.record("grad_sync", 1.0, alternative_s=2.0, chosen="plain", step=2)
    led.observe(r2, 3.0)           # observed above alternative: flip
    led.record("migration", 0.5)   # never observed
    s = led.summary()
    assert s["grad_sync"]["n"] == 2 and s["grad_sync"]["n_observed"] == 2
    assert s["grad_sync"]["decisions"] == 2 and s["grad_sync"]["flips"] == 1
    assert s["grad_sync"]["ratio"] == pytest.approx((1.5 * 3.0) ** 0.5)
    assert s["migration"]["n_observed"] == 0 and s["migration"]["ratio"] is None
    # summarize_records accepts plain dicts (the BENCH_calibration.json path)
    s2 = summarize_records([r.to_json() for r in led.records])
    assert s2 == s


def test_orchestrated_training_records_every_priced_decision(model):
    """Scripted schedule across link / pod-loss / straggler faults: every
    cost-model-gated decision leaves a calibration record, the registry
    matches the report fields bit-for-bit, and the trace carries the
    remesh/sync_switch spans."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from repro.configs.base import ParallelConfig
    from repro.data.pipeline import SyntheticLM
    from repro.launch.jax_compat import make_mesh
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.orchestrator import (
        FaultEvent,
        FaultSchedule,
        Orchestrator,
        OrchestratorConfig,
    )
    from repro.runtime.trainer import Trainer

    ob = Obs()
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    opt_cfg = AdamWConfig(lr=1e-3, total_steps=12)
    pcfg = ParallelConfig(hierarchical_grad_sync=True)
    sched = FaultSchedule((
        FaultEvent(step=1, kind="link_degraded", bandwidth_factor=0.1),
        FaultEvent(step=3, kind="link_restored"),
        FaultEvent(step=5, kind="pod_loss", devices=1),
        FaultEvent(step=7, kind="straggler", slowdown=0.15, duration=8,
                   devices=2),
    ))
    orch = Orchestrator(
        model, opt_cfg, pcfg, mesh=mesh, schedule=sched,
        cfg=OrchestratorConfig(drain_stragglers=True, straggler_patience=2),
        obs=ob,
    )
    t = Trainer(model, opt_cfg, pcfg, mesh=mesh)
    params, opt = t.init(jax.random.PRNGKey(0))
    pipe = SyntheticLM(vocab=model.cfg.vocab, seq_len=16, global_batch=8)
    _, _, report = orch.run(params, opt, pipe, n_steps=12)

    # registry is the storage for the report's scalar fields
    reg = ob.registry
    assert reg["train.useful_steps"].value == report.useful_steps == 12
    assert reg["train.wall_s"].value == report.wall_s
    assert reg["train.injected_slow_s"].value == report.injected_slow_s

    by_kind = {}
    for r in ob.calibration.records:
        by_kind.setdefault(r.kind, []).append(r)
    # one grad_sync record per priced sync decision, closed by the next step
    priced = [s for s in report.sync_switches if "t_plain_s" in s]
    assert len(by_kind["grad_sync"]) == len(priced) == 2
    assert all(r.observed_s is not None and r.alternative_s is not None
               for r in by_kind["grad_sync"])
    # one migration record per remesh (pod loss + straggler drain)
    assert len(by_kind["migration"]) == len(report.remesh_events) == 2
    assert all(r.observed_s is not None for r in by_kind["migration"])
    # one drain record per drain decision; executed drains close observed
    n_drain_decisions = (len(report.straggler_drains)
                         + len(report.drains_tolerated))
    assert len(by_kind["drain"]) == n_drain_decisions >= 1
    executed = [r for r in by_kind["drain"] if r.chosen == "drain"]
    assert len(executed) == len(report.straggler_drains)
    assert all(r.observed_s is not None for r in executed)

    names = {e["name"] for e in ob.tracer.events}
    assert {"train_step", "remesh", "sync_switch"} <= names
    steps = [e["step"] for e in ob.tracer.events if e["name"] == "train_step"]
    assert steps == list(range(12))


def test_tiered_serving_records_wakeup_and_tier_transfer(model):
    """Two session turns through the tiered pool: demotes price the
    hbm->host transfer, wakeups price against the cold re-prefill, and the
    engine's pool counters absorb into the registry."""
    from repro.launch.jax_compat import make_mesh
    from repro.runtime.serving import ContinuousBatchingEngine, TierConfig
    from repro.runtime.sharding import reshard_params

    ob = Obs()
    params = model.init(jax.random.PRNGKey(1))
    mesh = make_mesh((2, 1), ("data", "model"), devices=jax.devices()[:2])
    params = reshard_params(model.param_axes(), params, mesh)
    eng = ContinuousBatchingEngine(
        model, params, n_slots=2, max_len=32, mesh=mesh, seed=0,
        policy="fcfs", tiers=TierConfig(host_sessions=8), obs=ob,
    )
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, model.cfg.vocab, (5,)).astype(np.int32)
               for _ in range(2)]
    rids = [eng.submit(p, 3, session_id=i) for i, p in enumerate(prompts)]
    out = eng.run()
    for i in range(2):
        eng.submit(np.concatenate([prompts[i], out[rids[i]]]), 2, session_id=i)
    eng.run()

    assert eng.metrics.wakeups == 2
    by_kind = {}
    for r in ob.calibration.records:
        by_kind.setdefault(r.kind, []).append(r)
    assert {"cold_prefill", "tier_transfer", "wakeup"} <= set(by_kind)
    assert len(by_kind["wakeup"]) == 2
    for r in by_kind["wakeup"]:
        assert r.observed_s is not None and r.alternative_s is not None
        assert r.chosen == "wakeup"
    assert all(r.observed_s is not None for r in by_kind["tier_transfer"])
    names = {e["name"] for e in ob.tracer.events}
    assert {"prefill", "decode", "demote", "wakeup"} <= names

    # pool counters absorb into serve.pool.* (last write wins)
    eng.absorb_pool_metrics()
    reg = ob.registry
    assert reg["serve.pool.n_demote"].value == eng.pool.n_demote
    assert reg["serve.engine.wakeups"].value == 2
    eng.absorb_pool_metrics()  # idempotent, not additive
    assert reg["serve.pool.n_demote"].value == eng.pool.n_demote


# ---------------------------------------------------------- overhead guard
def test_disabled_path_allocates_no_trace_objects():
    """The zero-cost-when-disabled contract: driving every hot-path hook
    against NULL_OBS allocates nothing attributable to the obs package."""
    ob = NULL_OBS
    obs_dir = os.path.dirname(obslib.__file__)
    filters = [tracemalloc.Filter(True, os.path.join(obs_dir, "*"))]

    def hot_loop(n):
        for i in range(n):
            if ob.enabled:  # the one attribute check hot loops pay
                raise AssertionError("NULL_OBS must stay disabled")
            with ob.span("train_step", "train"):
                pass
            with ob.span("decode", "serve"):
                pass
            ob.instant("sync_switch", "train")

    n = 1000
    hot_loop(10)  # warm anything lazily cached
    gc.collect()
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot().filter_traces(filters)
        hot_loop(n)
        gc.collect()
        after = tracemalloc.take_snapshot().filter_traces(filters)
    finally:
        tracemalloc.stop()
    # CPython's frame/dict free-lists can leave O(1) blocks attributed to
    # the callee's def line, so the bound is a small constant: had any hook
    # allocated a real trace object per call, 3n calls would retain tens of
    # KB (a Span alone is >56 bytes), not a handful of recycled frames.
    grown = [s for s in after.compare_to(before, "lineno") if s.size_diff > 0]
    total = sum(s.size_diff for s in grown)
    blocks = sum(s.count_diff for s in grown)
    assert total < 1024 and blocks < 8, (
        f"disabled obs path allocated {total}B/{blocks} blocks over {3 * n} "
        f"hook calls: {grown[:5]}")


# -------------------------------------------------------- launcher smokes
def test_train_launcher_trace_smoke(tmp_path, monkeypatch):
    """Acceptance: a faulted tiny `train --orchestrate --trace` run writes a
    Perfetto-loadable trace containing remesh spans."""
    from repro.launch import train as train_mod

    monkeypatch.setattr(train_mod, "enable_compile_cache", lambda: None)
    trace = tmp_path / "train_trace.json"
    monkeypatch.setattr(sys, "argv", [
        "train", "--reduced", "--orchestrate", "--steps", "3", "--batch", "4",
        "--seq", "32", "--trace", str(trace), "--fault-schedule",
        '[{"step": 1, "kind": "device_loss", "devices": 2}]',
    ])
    train_mod.main()
    events = load_chrome(str(trace))
    assert any(e["name"] == "remesh" for e in events)
    assert any(e["name"] == "train_step" for e in events)
    assert (tmp_path / "train_trace.jsonl").exists()


def test_serve_launcher_trace_smoke(tmp_path, monkeypatch):
    """Acceptance: a faulted tiny `serve --orchestrate --trace` run writes a
    Perfetto-loadable trace containing migrate spans."""
    from repro.launch import serve as serve_mod

    monkeypatch.setattr(serve_mod, "enable_compile_cache", lambda: None)
    trace = tmp_path / "serve_trace.json"
    monkeypatch.setattr(sys, "argv", [
        "serve", "--reduced", "--orchestrate", "--requests", "4", "--slots",
        "2", "--prompt-len", "8", "--new-tokens", "4", "--trace", str(trace),
        "--fault-schedule",
        '[{"step": 1, "kind": "device_loss", "devices": 2}]',
    ])
    serve_mod.main()
    events = load_chrome(str(trace))
    assert any(e["name"] == "migrate" for e in events)
    assert any(e["name"] == "decode" for e in events)


# --------------------------------------------------------- sim hooks / misc
def test_simulator_hooks_emit_chunk_and_scenario_events():
    from repro.core import CLEXTopology
    from repro.core.scenarios import scenario_matrix
    from repro.core.streaming import simulate_point_to_point_streaming
    from repro.core.topology import TorusTopology

    ob = set_obs(Obs())
    topo = CLEXTopology(4, 2)
    simulate_point_to_point_streaming(topo, msgs_per_node=2, chunk_size=8)
    chunks = [e for e in ob.tracer.events if e["name"] == "sim_chunk"]
    assert len(chunks) >= 2  # forced multi-chunk
    assert chunks[-1]["args"]["done"] == chunks[-1]["args"]["total"]
    assert chunks[-1]["args"]["peak_rss_mb"] > 0
    assert ob.registry["sim.stream.msgs_per_s"].value > 0

    scenario_matrix(topo, TorusTopology.cube(4), msgs_per_node=2,
                    scenarios=["uniform"])
    cells = [e for e in ob.tracer.events if e["name"] == "scenario"]
    assert len(cells) == 1 and cells[0]["args"]["scenario"] == "uniform"


def test_provenance_stamp_shape():
    p = provenance(argv=["x", "--flag"])
    assert {"git_sha", "argv", "host", "python", "timestamp_utc",
            "suite_version"} <= set(p)
    assert p["argv"] == ["x", "--flag"]
    assert p["timestamp_utc"].endswith("+00:00") or "T" in p["timestamp_utc"]
    assert json.dumps(p)  # JSON-serializable as-is


def test_log_levels_honor_env(monkeypatch, capsys):
    monkeypatch.delenv("REPRO_LOG_LEVEL", raising=False)
    log.info("hello")
    log.debug("quiet")
    err = capsys.readouterr().err
    assert "[repro:info] hello" in err and "quiet" not in err
    monkeypatch.setenv("REPRO_LOG_LEVEL", "silent")
    log.error("nope")
    assert capsys.readouterr().err == ""
    monkeypatch.setenv("REPRO_LOG_LEVEL", "debug")
    log.debug("loud")
    assert "loud" in capsys.readouterr().err
