"""Continuous-batching serving subsystem: KV-pool slot lifecycle, scheduler
policies, sampling determinism, and equivalence against the one-shot path.
(docs/SERVING.md documents the behaviours pinned here.)"""

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.collectives import CollectiveCostModel
from repro.models import attention as attn_mod
from repro.models import build_model
from repro.runtime.serving import (
    ContinuousBatchingEngine,
    KVPool,
    Request,
    Scheduler,
    SchedulerConfig,
    ServingEngine,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("internlm2-1.8b", reduced=True)
    cfg = dataclasses.replace(cfg, compute_dtype="float32", remat=False, n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture(scope="module")
def tiny_moe():
    cfg = get_config("olmoe-1b-7b", reduced=True)
    cfg = dataclasses.replace(cfg, compute_dtype="float32", remat=False, n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _prompts(rng, vocab, lens):
    return [rng.integers(1, vocab, (l,)).astype(np.int32) for l in lens]


# ---------------------------------------------------------------- KV pool
def test_kvpool_slot_eviction_and_reuse(tiny):
    model, _ = tiny
    pool = KVPool(model, n_slots=3, capacity=16)
    slots = [pool.allocate(rid) for rid in range(3)]
    assert sorted(slots) == [0, 1, 2]
    assert pool.allocate(99) is None  # exhausted
    pool.free(1)
    assert pool.n_free == 1
    assert pool.allocate(100) == 1  # freed slot is reused
    with pytest.raises(ValueError):
        pool.free(0) or pool.free(0)  # double free of 0
    assert pool.n_alloc == 4 and pool.n_evict == 2 and pool.high_water == 3


def test_kvpool_write_isolates_slots(tiny):
    model, params = tiny
    pool = KVPool(model, n_slots=3, capacity=16)
    toks = np.ones((1, 8), np.int32)
    _, caches = jax.jit(lambda p, b: model.prefill(p, b))(params, {"tokens": toks})
    one = model.prepare_decode_caches(caches, capacity=16)

    # snapshot to host first: pool.write donates the device buffers
    before = [np.asarray(x) for x in jax.tree.leaves(pool.caches)]
    pool.write(1, one)
    after = [np.asarray(x) for x in jax.tree.leaves(pool.caches)]
    ax = 1 if pool.stacked else 0
    changed_rows = set()
    for b, a in zip(before, after):
        for row in range(3):
            if not np.array_equal(np.take(b, row, axis=ax), np.take(a, row, axis=ax)):
                changed_rows.add(row)
    assert changed_rows == {1}  # only the written slot's row moved


def _one_hot_write(cache, new, layer, slot, axis):
    """Reference for the decode's ring write: a one-hot select over every
    ring entry of every row of the layer (the write decode made before it
    wrote each row's entry in place)."""
    old = jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
    new = new.astype(cache.dtype)
    b, length = old.shape[0], old.shape[axis]
    ring = jnp.arange(length).reshape((1,) * axis + (length,) + (1,) * (old.ndim - axis - 1))
    hit = ring == jnp.broadcast_to(slot, (b,)).reshape((b,) + (1,) * (old.ndim - 1))
    return jax.lax.dynamic_update_index_in_dim(cache, jnp.where(hit, new, old), layer, 0)


# arch, config overrides, ragged (False: the lockstep one-slot write).  Two
# layers run the decode loop over a two-deep stack; one layer runs it once
WRITE_CASES = {
    "gqa": ("internlm2-1.8b", {}, True),
    "gqa_one_layer": ("internlm2-1.8b", {"n_layers": 1}, True),
    "swa_ring_wrap": ("h2o-danube-1.8b", {"sliding_window": 8}, True),
    "mla": ("minicpm3-4b", {}, True),
    # a dense prelude layer's latent cache, then two stacked MoE layers'
    "mla_prelude": ("deepseek-v2-lite", {"n_layers": 3}, True),
    "gqa_lockstep": ("internlm2-1.8b", {}, False),
}


@pytest.mark.parametrize("case", list(WRITE_CASES))
def test_in_place_ring_write_matches_one_hot_write(case, monkeypatch):
    """One decode step over a live bf16 pool (rows at distinct positions, past
    the window on the SWA ring, one row a freed slot) gives bit-identical
    logits and cache leaves whether each row's entry is written in place or
    selected in over the whole ring."""
    arch, overrides, ragged = WRITE_CASES[case]
    cfg = dataclasses.replace(get_config(arch, reduced=True), **{"n_layers": 2, **overrides})
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(model, params, n_slots=4, max_len=32)
    rng = np.random.default_rng(3)
    for plen, budget in zip([11, 14, 5, 9], [12, 12, 2, 12]):
        eng.submit(rng.integers(1, cfg.vocab, plen).astype(np.int32), budget)
    for _ in range(4):
        eng.step()
    assert eng.pool.n_free == 1  # the two-token request is done: its slot is free
    caches = jax.device_get(eng.pool.caches)
    tokens = jnp.asarray(eng._tokens)[:, None]
    pos = eng._pos.copy()
    assert len(set(pos.tolist())) == 4
    if not ragged:
        pos[:] = pos.max()
    if case == "swa_ring_wrap":
        assert pos.max() >= 2 * cfg.sliding_window

    def step(write):
        monkeypatch.setattr(attn_mod, "ring_write", write)
        fn = jax.jit(partial(model.decode_step, ragged=ragged))
        return jax.device_get(fn(params, caches, tokens, jnp.asarray(pos)))

    logits, new = step(attn_mod.ring_write)
    ref_logits, ref = step(_one_hot_write)
    np.testing.assert_array_equal(logits, ref_logits)
    assert jax.tree.structure(new) == jax.tree.structure(ref)
    for got, want in zip(jax.tree.leaves(new), jax.tree.leaves(ref)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
    assert any(not np.array_equal(a, b)  # the step wrote something
               for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(caches)))


# ---------------------------------------------------------------- scheduler
def _req(rid, heavy=False, deferred=0):
    r = Request(rid=rid, prompt=np.ones((4,), np.int32), max_new_tokens=4,
                dispatch_weight=1e4 if heavy else 0.0)
    r.deferred = deferred
    return r


def test_scheduler_fcfs_is_arrival_order():
    s = Scheduler(SchedulerConfig(policy="fcfs"))
    reqs = [_req(i) for i in range(5)]
    assert [r.rid for r in s.select(reqs, n_free=3)] == [0, 1, 2]


def test_scheduler_cost_aware_coschedules_moe_heavy():
    """A lone MoE-heavy request is deferred while light work exists; once a
    co-schedulable group forms, the heavy requests are admitted together."""
    cfg = SchedulerConfig(policy="cost_aware", min_coschedule=2)
    s = Scheduler(cfg, CollectiveCostModel(), d_model=512, top_k=4, n_moe_layers=2)
    lone_heavy = [_req(0, heavy=True), _req(1), _req(2)]
    picks = s.select(lone_heavy, n_free=2)
    assert [r.rid for r in picks] == [1, 2]  # heavy deferred, light admitted
    assert lone_heavy[0].deferred == 1

    group = [_req(0, heavy=True), _req(1, heavy=True), _req(2)]
    picks = s.select(group, n_free=2)
    assert [r.rid for r in picks] == [0, 1]  # heavy pair co-scheduled first
    assert s.last_step_cost > 0


def test_scheduler_aging_prevents_starvation():
    cfg = SchedulerConfig(policy="cost_aware", min_coschedule=4, max_defer_steps=3)
    s = Scheduler(cfg, CollectiveCostModel(), d_model=512, top_k=4, n_moe_layers=2)
    reqs = [_req(0, heavy=True, deferred=3), _req(1)]
    picks = s.select(reqs, n_free=2)
    assert picks[0].rid == 0  # aged heavy request admitted despite no group


def test_scheduler_aged_heavy_overrides_budget_in_mixed_traffic():
    """Even when a single heavy request busts the a2a budget (full-size MoE
    configs can) and light traffic keeps arriving, aging still admits it."""
    cfg = SchedulerConfig(policy="cost_aware", a2a_budget_s=1e-12,
                          min_coschedule=1, max_defer_steps=3,
                          work_conserving=False)
    s = Scheduler(cfg, CollectiveCostModel(), d_model=4096, top_k=8,
                  n_moe_layers=8)
    picks = s.select([_req(0, heavy=True, deferred=3), _req(1)], n_free=2)
    assert [r.rid for r in picks] == [0, 1]


def test_scheduler_slot_exhaustion_still_ages_heavy():
    cfg = SchedulerConfig(policy="cost_aware", min_coschedule=1)
    s = Scheduler(cfg, CollectiveCostModel(), d_model=64, top_k=2, n_moe_layers=1)
    reqs = [_req(i, heavy=True) for i in range(3)]
    picks = s.select(reqs, n_free=1)
    assert len(picks) == 1
    assert all(r.deferred == 1 for r in reqs if r not in picks)


def test_scheduler_budget_caps_heavy_admission():
    tiny_budget = SchedulerConfig(policy="cost_aware", a2a_budget_s=1e-12,
                                  min_coschedule=1, work_conserving=False)
    s = Scheduler(tiny_budget, CollectiveCostModel(), d_model=4096, top_k=8,
                  n_moe_layers=8)
    reqs = [_req(i, heavy=True) for i in range(4)]
    assert s.select(reqs, n_free=4) == []  # everything over budget, deferred
    assert all(r.deferred == 1 for r in reqs)
    # work conservation overrides the budget so slots never idle
    s2 = Scheduler(dataclasses.replace(tiny_budget, work_conserving=True),
                   CollectiveCostModel(), d_model=4096, top_k=8, n_moe_layers=8)
    assert len(s2.select(reqs, n_free=4)) >= 1


# ---------------------------------------------------------------- cost hooks
def test_cost_model_serving_hooks():
    cm = CollectiveCostModel()
    kw = dict(d_model=2048, top_k=2, n_low=8, n_pods=4)
    c1 = cm.moe_dispatch_cost(1, hierarchical=True, **kw)
    c8 = cm.moe_dispatch_cost(8, hierarchical=True, **kw)
    assert 0 < c1 < c8  # monotonic in tokens
    flat = cm.moe_dispatch_cost(8, hierarchical=False, **kw)
    assert c8 < flat  # staged beats flat across pods (the CLEX rule)
    assert cm.decode_step_a2a_cost(0, 2048, 2, 4, 8, 4) == 0.0
    assert cm.decode_step_a2a_cost(4, 2048, 2, 0, 8, 4) == 0.0
    step = cm.decode_step_a2a_cost(4, 2048, 2, 4, 8, 4)
    assert step == pytest.approx(2 * 4 * cm.moe_dispatch_cost(4, 2048, 2, 8, 4))
    # batching MoE-heavy requests amortises the bundle-hop latency
    assert cm.coschedule_gain(8, 2048, 2, 4, 8, 4) > 0
    assert cm.coschedule_gain(1, 2048, 2, 4, 8, 4) == 0.0


# ---------------------------------------------------------------- engine
def test_ragged_admission_and_slot_reuse(tiny):
    """More ragged requests than slots: all complete with their own budgets,
    admission is FIFO, and freed slots are reused."""
    model, params = tiny
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_len=48,
                                   policy="fcfs", seed=0)
    rng = np.random.default_rng(1)
    prompts = _prompts(rng, model.cfg.vocab, [5, 9, 3, 12, 7])
    budgets = [4, 2, 6, 3, 5]
    rids = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
    out = eng.run()
    assert [len(out[r]) for r in rids] == budgets
    assert eng.pool.n_alloc == 5 and eng.pool.n_evict == 5
    assert eng.pool.high_water <= 2
    # FIFO: earlier submissions are admitted no later than later ones
    admits = [eng.requests[r].t_admit for r in rids]
    assert all(a <= b for a, b in zip(admits, admits[1:])) or sorted(admits) == admits


def _slow_decode(eng, secs):
    decode = eng._decode

    def slow(*args):
        time.sleep(secs)
        return decode(*args)

    eng._decode = slow


def test_request_stamps_mark_when_the_work_is_done(tiny):
    """Stamps are taken as the host gets the work back, not at the start of
    the step: a request served by decode spans at least the decode's time
    between admission and its last token."""
    model, params = tiny
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_len=48,
                                   policy="fcfs", seed=0)
    rng = np.random.default_rng(11)
    rids = [eng.submit(p, b) for p, b in
            zip(_prompts(rng, model.cfg.vocab, [5, 9, 3]), [1, 3, 2])]
    eng.run()  # compiles
    _slow_decode(eng, 0.02)
    rids = [eng.submit(p, b) for p, b in
            zip(_prompts(rng, model.cfg.vocab, [5, 9, 3]), [1, 3, 2])]
    t0 = time.monotonic()
    eng.run()
    t1 = time.monotonic()
    for rid in rids:
        r = eng.requests[rid]
        assert t0 <= r.t_admit <= r.t_first <= r.t_done <= t1
    one, three, two = (eng.requests[r] for r in rids)
    assert one.t_done == one.t_first  # finished by its prefill
    for r in (three, two):
        assert r.t_done - r.t_admit >= 0.02 * (len(r.tokens_out) - 1)


def test_request_stamps_stay_on_the_callers_clock(tiny):
    """A simulated ``now`` puts the stamps on its clock: offset from that
    ``now`` by the host time the step took, not read off the wall clock."""
    model, params = tiny
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_len=48,
                                   policy="fcfs", seed=0)
    rng = np.random.default_rng(12)
    warm = eng.submit(_prompts(rng, model.cfg.vocab, [4])[0], 2)
    eng.run()  # compiles
    assert eng.requests[warm].done
    _slow_decode(eng, 0.02)
    rid = eng.submit(_prompts(rng, model.cfg.vocab, [4])[0], 2, arrival_time=1000.0)
    assert eng.step(999.0) == 0  # not arrived on the caller's clock
    t0 = time.monotonic()
    assert eng.step(1000.0) == 2
    took = time.monotonic() - t0
    r = eng.requests[rid]
    assert 1000.0 <= r.t_admit <= r.t_first <= r.t_done <= 1000.0 + took
    assert r.t_done - r.t_first >= 0.02


def test_submit_rejects_over_capacity(tiny):
    model, params = tiny
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_len=16)
    with pytest.raises(ValueError):
        eng.submit(np.ones((10,), np.int32), 10)  # 10 + 10 > 16
    with pytest.raises(ValueError):
        eng.submit(np.ones((0,), np.int32), 4)


def test_temperature_sampling_deterministic_under_fixed_seed(tiny):
    """Same seed -> identical sampled outputs, run to run and across pool
    sizes (per-request keys are independent of slot assignment)."""
    model, params = tiny
    rng = np.random.default_rng(2)
    prompts = _prompts(rng, model.cfg.vocab, [6, 11, 4, 8])
    budgets = [5, 3, 6, 4]

    def serve(n_slots, seed):
        eng = ContinuousBatchingEngine(model, params, n_slots=n_slots,
                                       max_len=48, seed=seed)
        return eng.generate(prompts, budgets, temperature=0.8)

    a = serve(2, seed=7)
    b = serve(2, seed=7)
    c = serve(3, seed=7)
    d = serve(2, seed=8)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    for x, y in zip(a, c):
        np.testing.assert_array_equal(x, y)  # dense model: slot-count invariant
    assert any(not np.array_equal(x, y) for x, y in zip(a, d))  # seed matters


def test_continuous_matches_one_shot_on_static_batch(tiny):
    """Greedy continuous batching == the seed's lockstep path on a static
    (equal-length, same-budget) batch."""
    model, params = tiny
    rng = np.random.default_rng(3)
    static = np.stack(_prompts(rng, model.cfg.vocab, [8, 8, 8]))
    one = ServingEngine(model, params, max_len=48).generate(static, 6)
    eng = ContinuousBatchingEngine(model, params, n_slots=3, max_len=48, seed=0)
    cont = np.stack(eng.generate(static, 6))
    np.testing.assert_array_equal(one, cont)


def test_continuous_matches_one_shot_per_request_ragged(tiny):
    """Ragged prompts (bucketed right-pad prefill) produce exactly what the
    one-shot engine produces for each request served alone at exact length —
    padding never leaks into logits or decode."""
    model, params = tiny
    rng = np.random.default_rng(4)
    prompts = _prompts(rng, model.cfg.vocab, [5, 9, 13])
    budgets = [6, 4, 5]
    eng = ContinuousBatchingEngine(model, params, n_slots=3, max_len=48, seed=0)
    cont = eng.generate(prompts, budgets)
    solo_engine = ServingEngine(model, params, max_len=48)
    for p, b, got in zip(prompts, budgets, cont):
        solo = solo_engine.generate(p[None, :], b)[0]
        np.testing.assert_array_equal(solo, got)


def test_eos_finishes_early(tiny):
    model, params = tiny
    rng = np.random.default_rng(5)
    prompt = _prompts(rng, model.cfg.vocab, [8])[0]
    eng = ContinuousBatchingEngine(model, params, n_slots=1, max_len=64, seed=0)
    ref = eng.generate([prompt], 12)[0]
    eos = int(ref[3])  # force EOS at the 4th generated token
    eng2 = ContinuousBatchingEngine(model, params, n_slots=1, max_len=64, seed=0)
    out = eng2.generate([prompt], 12, eos_id=eos)[0]
    assert len(out) == 4 and out[-1] == eos
    np.testing.assert_array_equal(out, ref[:4])


def test_moe_engine_runs_and_prices_admission(tiny_moe):
    model, params = tiny_moe
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_len=32,
                                   policy="cost_aware", seed=0)
    assert eng._dispatch_weight > 0  # MoE model: requests are dispatch-heavy
    rng = np.random.default_rng(6)
    prompts = _prompts(rng, model.cfg.vocab, [4, 7, 5])
    out = eng.generate(prompts, [3, 3, 3])
    assert [len(o) for o in out] == [3, 3, 3]
    assert eng.metrics.predicted_a2a_s > 0  # cost model actually consulted
    # fixed configuration is reproducible (slot-count invariance does not
    # hold for the capacity paths: expert capacity couples co-batched rows;
    # the dropless held-share path has no such coupling, next test)
    eng2 = ContinuousBatchingEngine(model, params, n_slots=2, max_len=32,
                                    policy="cost_aware", seed=0)
    for a, b in zip(out, eng2.generate(prompts, [3, 3, 3])):
        np.testing.assert_array_equal(a, b)


def test_dropless_moe_rows_do_not_depend_on_their_batch_or_pool():
    """With a held expert share (no capacity, nothing dropped) a row's decode
    logits are the same bits whatever rows share its pool; across pool sizes
    XLA:CPU's matmuls choose kernels by row count, so a dense layer already
    moves the last bits there (1e-6), and the served tokens agree."""
    cfg = get_config("deepseek-v2-lite", reduced=True)
    cfg = dataclasses.replace(cfg, compute_dtype="float32", remat=False,
                              moe=dataclasses.replace(cfg.moe, held_first=4, n_held=4))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(0)
    prompts = _prompts(rng, cfg.vocab, [9, 17, 5, 12])
    rows = []
    for p in prompts:
        _, c = model.prefill(params, {"tokens": jnp.asarray(p[None])})
        rows.append(model.prepare_decode_caches(c, capacity=32))
    toks = jnp.asarray(rng.integers(1, cfg.vocab, (4, 1)), jnp.int32)
    pos = jnp.asarray([len(p) for p in prompts], jnp.int32)
    step = jax.jit(lambda c, t, q: model.decode_step(params, c, t, q, ragged=True)[0])
    pool = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1), *rows)
    logits = np.asarray(step(pool, toks, pos))[:, 0]
    flipped = np.asarray(step(jax.tree.map(lambda a: a[:, ::-1], pool), toks[::-1], pos[::-1]))
    np.testing.assert_array_equal(logits, flipped[::-1, 0])
    alone = jax.tree.map(lambda a: jnp.concatenate([a[:, :1], jnp.zeros_like(a[:, 1:])], 1), pool)
    np.testing.assert_array_equal(logits[0], np.asarray(step(alone, toks, pos))[0, 0])
    for i, r in enumerate(rows):
        one = np.asarray(step(r, toks[i:i + 1], pos[i:i + 1]))[0, 0]
        np.testing.assert_allclose(one, logits[i], atol=1e-5)
    budgets = [6, 4, 5, 3]
    solo = [ContinuousBatchingEngine(model, params, n_slots=1, max_len=32, seed=0).generate(
        [p], b)[0] for p, b in zip(prompts, budgets)]
    together = ContinuousBatchingEngine(model, params, n_slots=3, max_len=32,
                                        seed=0).generate(prompts, budgets)
    for a, b in zip(solo, together):
        np.testing.assert_array_equal(a, b)


def test_run_with_virtual_clock_fast_forwards(tiny):
    """A custom clock must not hang run(): an idle engine jumps virtual time
    to the next arrival instead of wall-sleeping."""
    model, params = tiny
    eng = ContinuousBatchingEngine(model, params, n_slots=1, max_len=32, seed=0)
    eng.submit(np.ones((4,), np.int32), 3, arrival_time=5.0)
    out = eng.run(clock=lambda: 0.0)  # frozen virtual clock
    assert [len(v) for v in out.values()] == [3]


def test_engine_metrics_utilization(tiny):
    model, params = tiny
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_len=48, seed=0)
    rng = np.random.default_rng(7)
    eng.generate(_prompts(rng, model.cfg.vocab, [6, 6, 6, 6]), [4, 4, 4, 4])
    m = eng.metrics
    assert m.decode_steps > 0 and m.prefills > 0
    assert 0.5 < m.slot_utilization <= 1.0


# ------------------------------------------------------- admission shedding
def test_submit_reject_never_allocates_slot(tiny):
    """Satellite: a request rejected at submit (queue over max_queue_depth)
    is SHED without ever touching the KV pool — its id is still returned so
    the caller can observe the state, and goodput excludes its budget."""
    from repro.runtime.serving import SHED

    model, params = tiny
    eng = ContinuousBatchingEngine(model, params, n_slots=1, max_len=32,
                                   policy="fcfs", seed=0, max_queue_depth=2)
    rng = np.random.default_rng(8)
    prompts = _prompts(rng, model.cfg.vocab, [4, 5, 6, 7])
    rids = [eng.submit(p, 3) for p in prompts]
    # first two fill the queue; the rest bounce off admission control
    assert [eng.requests[r].state for r in rids] == ["queued"] * 2 + [SHED] * 2
    assert eng.pool.n_alloc == 0  # nothing allocated at submit time
    assert eng.metrics.rejected == 2
    assert eng.metrics.shed_tokens == 6  # 2 rejected x 3-token budgets

    out = eng.run()
    assert set(out) == set(rids[:2])  # shed requests never produce output
    assert [len(out[r]) for r in rids[:2]] == [3, 3]
    # no slot leak, no double-completion: every allocation was evicted and
    # only the two admitted requests ever touched the pool
    assert eng.pool.n_alloc == eng.pool.n_evict == 2
    assert [eng.requests[r].state for r in rids[2:]] == [SHED, SHED]


def test_submit_reject_releases_no_session(tiny):
    """A rejected tiered submit must not reserve the session identity —
    the caller can retry the same session once the queue drains."""
    from repro.runtime.serving import SHED, TierConfig

    model, params = tiny
    eng = ContinuousBatchingEngine(model, params, n_slots=1, max_len=32,
                                   seed=0, tiers=TierConfig(),
                                   max_queue_depth=1)
    p = np.ones((4,), np.int32)
    eng.submit(p, 2, session_id=0)
    r_shed = eng.submit(p, 2, session_id=1)  # queue full -> SHED
    assert eng.requests[r_shed].state == SHED
    eng.run()
    # session 1 was never reserved: resubmitting it is legal
    r_retry = eng.submit(p, 2, session_id=1)
    assert len(eng.run()[r_retry]) == 2


def test_deadline_drop_refunds_queue(tiny):
    """Satellite: an unadmitted request past its deadline is refunded from
    the queue (lazy O(log n) delete) before it can waste a slot."""
    from repro.runtime.serving import SHED

    model, params = tiny
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_len=32,
                                   policy="fcfs", seed=0)
    rng = np.random.default_rng(9)
    p_live, p_dead = _prompts(rng, model.cfg.vocab, [4, 4])
    r_live = eng.submit(p_live, 3)
    r_dead = eng.submit(p_dead, 3, deadline=1.0)
    out = eng.run(clock=lambda: 5.0)  # virtual now is past the deadline
    # the expired request was dropped even though a slot was free for it
    assert eng.requests[r_dead].state == SHED
    assert eng.metrics.deadline_drops == 1 and eng.metrics.rejected == 0
    assert eng.metrics.shed_tokens == 3
    assert set(out) == {r_live} and len(out[r_live]) == 3
    assert eng.pool.n_alloc == eng.pool.n_evict == 1  # dead req never allocated
    assert len(eng.queue) == 0  # refunded, not orphaned


def test_shed_queue_sheds_newest_tail_first(tiny):
    """shed_queue(keep) turns away the *newest* arrivals: the oldest work
    has waited longest and keeps its place at the head."""
    from repro.runtime.serving import SHED

    model, params = tiny
    eng = ContinuousBatchingEngine(model, params, n_slots=1, max_len=32,
                                   policy="fcfs", seed=0)
    rng = np.random.default_rng(10)
    rids = [eng.submit(p, 2) for p in _prompts(rng, model.cfg.vocab, [4] * 5)]
    assert eng.shed_queue(keep_depth=2) == 3
    states = [eng.requests[r].state for r in rids]
    assert states == ["queued", "queued", SHED, SHED, SHED]
    assert eng.metrics.rejected == 3 and eng.metrics.shed_tokens == 6
    assert eng.shed_queue(keep_depth=2) == 0  # idempotent at the floor
    out = eng.run()
    assert set(out) == set(rids[:2])  # survivors complete normally


# ---------------------------------------------------------------- docs gate
def test_docs_link_check_repo_is_clean():
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools.check_doc_links import check

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert check(root) == []


def test_docs_link_check_catches_dangling(tmp_path):
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools.check_doc_links import check

    # reference names are assembled at runtime so this test file itself
    # stays clean under the repo-wide scan
    md = ".md"
    real, design = f"docs/REAL{md}", f"DESIGN{md}"
    missing, gone, generated = f"docs/MISSING{md}", f"docs/GONE{md}", f"EXPERIMENTS{md}"
    (tmp_path / "src").mkdir()
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / f"REAL{md}").write_text("# real\n")
    (tmp_path / "src" / "mod.py").write_text(
        f'"""See {design} Sec. 3 and {real} and {missing}."""\n'
    )
    (tmp_path / f"README{md}").write_text(
        f"[ok]({real}) and [bad]({gone}), plus {generated} is allowed\n"
    )
    problems = check(str(tmp_path))
    joined = "\n".join(problems)
    assert design in joined and missing in joined and gone in joined
    assert f"REAL{md}" not in joined and generated not in joined


# ----------------------------------------------------------- request queue
def test_request_queue_arrival_order_and_lazy_removal():
    """The O(log n) queue rewrite pins the old deque semantics: closed-loop
    requests stay in submission order, open-loop ones graduate exactly at
    their arrival time, and removal is lazy but externally invisible."""
    from repro.runtime.serving import RequestQueue

    def mk(i, at=None):
        return Request(rid=i, prompt=np.ones((4,), np.int32), max_new_tokens=1,
                       arrival_time=at)

    q = RequestQueue()
    a, b, c, d = mk(0), mk(1, at=5.0), mk(2), mk(3, at=2.0)
    for r in (a, b, c, d):
        q.push(r)
    assert len(q) == 4
    assert [r.rid for r in q.arrived(0.0)] == [0, 2]  # closed-loop only
    assert q.next_arrival() == 2.0
    assert [r.rid for r in q.arrived(2.0)] == [0, 2, 3]  # d graduated
    # the legacy "everything" view neither loses nor graduates pending work
    assert [r.rid for r in q.arrived(None)] == [0, 1, 2, 3]
    assert q.next_arrival() == 5.0
    q.remove([a, d])
    assert len(q) == 2
    assert [r.rid for r in q.arrived(10.0)] == [1, 2]
    e = mk(4, at=20.0)
    q.push(e)
    assert q.next_arrival() == 20.0
    q.remove([e])  # removing a still-pending (heap) request
    assert q.next_arrival() is None
    assert len(q) == 2
    q.remove([b, c])
    assert len(q) == 0 and q.arrived(100.0) == []


def test_request_queue_compaction_preserves_order():
    """Bulk lazy deletions past the compaction threshold sweep the ready
    list without disturbing submission order."""
    from repro.runtime.serving import RequestQueue

    q = RequestQueue()
    reqs = [Request(rid=i, prompt=np.ones((2,), np.int32), max_new_tokens=1)
            for i in range(200)]
    for r in reqs:
        q.push(r)
    q.remove([reqs[i] for i in range(0, 200, 2)])
    assert len(q) == 100
    assert [r.rid for r in q.arrived(0.0)] == list(range(1, 200, 2))
    assert len(q) == 100


# ------------------------------------------------------- admission grouping
def test_admission_groups_bucket_first_padding_regression(tiny):
    """One long prompt in a mixed batch must not drag a whole pow2 group up
    to its pad bucket: groups are single-bucket, padded-token count beats
    the old arrival-order split, and the compiled prefill shape universe
    stays O(buckets * log slots)."""
    model, params = tiny
    eng = ContinuousBatchingEngine(model, params, n_slots=8, max_len=256)
    lens = [4, 100, 4, 4, 5, 6, 7, 8]
    rng = np.random.default_rng(0)
    picks = [
        Request(rid=i, prompt=rng.integers(1, model.cfg.vocab, (l,)).astype(np.int32),
                max_new_tokens=1)
        for i, l in enumerate(lens)
    ]
    groups = eng._admission_groups(picks)
    assert sorted(r.rid for g in groups for r in g) == list(range(8))
    shapes, padded = set(), 0
    for g in groups:
        buckets = {eng._bucket(r.prompt_len) for r in g}
        assert len(buckets) == 1  # a group never spans buckets
        assert len(g) & (len(g) - 1) == 0  # pow2 group sizes
        shapes.add((len(g), buckets.pop()))
        padded += len(g) * eng._bucket(g[0].prompt_len)
    # arrival order holds within a bucket
    assert [r.rid for g in groups for r in g
            if eng._bucket(r.prompt_len) == 8] == [0, 2, 3, 4, 5, 6, 7]
    # old algorithm: one group of 8 arrival-order picks padded to bucket 128
    assert padded == 184 < 8 * 128
    n_buckets = len({eng._bucket(l) for l in lens})
    assert len(shapes) <= n_buckets * (8).bit_length()
