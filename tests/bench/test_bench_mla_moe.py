"""The ``mla_moe`` family (DeepSeek-V2-Lite) against the program, at the
registry's reduced sizes on the CPU: the float32 prefill and ragged decode
give the plain reference's logits; the bf16 engine serves its argmax up to
rounding; a rehearsal of the cell is correct, its float8 control and a
broken decode are not; the work counts, and the two readers of the
program's named scopes."""

import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, harness, program, scope_reduce, span_reduce, spec  # noqa: E402
from bench import trace_reduce, weights  # noqa: E402
from bench.peaks import least_time  # noqa: E402
from bench.families import mla_moe  # noqa: E402
from bench.reference import mla_moe as reference  # noqa: E402

CELL = "deepseek-v2-lite.latent_decode"
RECORDED = Path(__file__).parent / "data" / "chip_trace.xplane.pb"
# the cell at CPU size: 3 slots, prompts 40-90, outputs 8-32
REHEARSAL = {"cell": {"n_slots": 3, "max_len": 192, "requests": 30, "check_tokens": 1000,
                      "limits": {"max_logit_gap": 0.2}},
             "traffic": {"round": 4, "prompt_tokens": {"dist": "uniform", "min": 40, "max": 90},
                         "output_tokens": {"dist": "uniform", "min": 8, "max": 32}}}


def _config():
    return spec.load_cell(CELL).config


def _cfg(dtype):
    import dataclasses

    cfg = mla_moe.model_config(_config(), reduced=True)
    return dataclasses.replace(cfg, param_dtype=dtype, compute_dtype=dtype)


def _prompt(cfg, n, seed):
    return np.random.default_rng(seed).integers(1, cfg.vocab, n).astype(np.int32)


def test_the_configuration_is_one_chips_share_of_the_published_model():
    config = _config()
    assert spec.family(config) is mla_moe
    cfg = mla_moe.model_config(config)
    assert (cfg.moe.n_experts, cfg.moe.held_first, cfg.moe.n_held) == (64, 0, 8)
    assert cfg.param_dtype == cfg.compute_dtype == "bfloat16"
    rcfg = mla_moe.reference_config(cfg)
    n = sum(int(np.prod(s)) for s in jax.tree.leaves(mla_moe.shapes(rcfg),
                                                      is_leaf=lambda s: isinstance(s, tuple)))
    assert 3.10e9 < n < 3.12e9  # all 27 layers and the vocabulary, 8 of 64 experts
    assert mla_moe.latent_bytes_per_token(rcfg) == 27 * 576 * 2  # 31.1 KB
    small = mla_moe.model_config(config, reduced=True)
    assert (small.moe.n_experts, small.moe.n_held) == (16, 2)


def test_float32_prefill_and_ragged_decode_match_reference():
    """The program's prefill, then six ragged decode steps through the
    latent cache (dense first layer in the prelude, YaRN, the held share),
    against the reference's full forward of the same sequence."""
    from repro.models import build_model

    cfg = _cfg("float32")
    rcfg = mla_moe.reference_config(cfg)
    w = weights.make_weights(mla_moe.shapes(rcfg), seed=3, dtype=jnp.float32)
    model = build_model(cfg)
    params = program.program_params(mla_moe, w, model)
    n = 40
    prompt = _prompt(cfg, n, 0)
    logits, caches = model.prefill(params, {"tokens": jnp.asarray(prompt[None])})
    caches = model.prepare_decode_caches(caches, capacity=64)
    seq, got = list(prompt), [np.asarray(logits[0, 0])]
    for _ in range(6):
        tok = int(np.argmax(got[-1]))
        seq.append(tok)
        logits, caches = model.decode_step(
            params, caches, jnp.asarray([[tok]], jnp.int32),
            jnp.asarray([len(seq) - 1], jnp.int32), ragged=True)
        got.append(np.asarray(logits[0, 0]))
    want = np.asarray(mla_moe.logits_at(w, rcfg, np.asarray(seq, np.int32),
                                        np.arange(n - 1, len(seq))))
    # float32 on both sides; the absorbed decode and the expanded reference
    # sum in other orders (about 1e-5 of logits of size 1)
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=2e-4)


def test_the_uncut_reference_layer_is_the_sum_of_the_shares():
    """The reference's MoE with each of the 8 two-expert shares, the shared
    experts counted once, sums to the reference's layer with all 16."""
    cfg = _cfg("float32")
    rcfg = {**mla_moe.reference_config(cfg), "held_first": 0, "held_experts": 16}
    w = weights.make_weights(mla_moe.shapes(rcfg), seed=4, dtype=jnp.float32)
    mlp = jax.tree.map(lambda a: a[0], w["moe_layers"]["mlp"])
    h = jax.random.normal(jax.random.PRNGKey(5), (12, rcfg["hidden_size"]), jnp.float32)
    whole = reference._moe(h, mlp, rcfg, False)
    shared = reference._swiglu(h, mlp["shared_experts"], False)
    parts = 0.0
    for first in range(0, 16, 2):
        share = {**mlp, "experts": jax.tree.map(lambda a: a[first:first + 2], mlp["experts"])}
        parts = parts + reference._moe(h, share, {**rcfg, "held_first": first,
                                                  "held_experts": 2}, False) - shared
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(whole), atol=1e-5)


def test_bf16_engine_serves_reference_argmax():
    cfg = _cfg("bfloat16")
    rcfg = mla_moe.reference_config(cfg)
    w = weights.make_weights(mla_moe.shapes(rcfg), seed=5)
    engine = program.build_engine(mla_moe, cfg, w, n_slots=3, max_len=256)
    prompts = [_prompt(cfg, n, i) for i, n in enumerate([40, 70, 9, 25])]
    served = engine.generate(prompts, [12, 8, 10, 12])  # 4 requests, 3 slots: reuse
    g = check.widest_gap(mla_moe.logits_at, w, rcfg, list(zip(prompts, served)))
    assert g["tokens"] == 42
    # bf16 rounding may flip a near tie, in the router's top-k too; a wrong
    # latent row, position or expert serves a token far below the best
    assert g["value"] < 0.2, g


def _rehearse(seed, **kw):
    return harness.run(CELL, seed, 2.0, False, t_start=time.perf_counter(), require_tpu=False,
                       reduced=True, overrides=REHEARSAL, **kw)


def test_rehearsal_is_correct_and_its_control_is_not():
    r = _rehearse(2**33 + 7, control=True)
    assert r["program_gap"]["value"] <= r["checks"]["logit_gap"]["limit"]
    assert r["correct"] is False and r["control"]["value"] > 0.2
    line = json.loads(json.dumps(r))
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0


def _state_unchanged(engine):
    decode = engine._decode

    def broken(params, caches, *args):
        old = jax.tree.map(jnp.copy, caches)
        toks, _ = decode(params, caches, *args)
        return toks, old

    engine._decode = broken


def test_a_decode_that_writes_no_latent_entry_is_incorrect():
    r = _rehearse(21, hooks=_state_unchanged)
    assert r["correct"] is False
    assert r["checks"]["logit_gap"]["value"] > r["checks"]["logit_gap"]["limit"]


def test_work_counts_read_live_latents_and_the_touched_experts():
    cfg = mla_moe.reference_config(mla_moe.model_config(_config()))
    a, b = mla_moe.decode_step(cfg, [1000, 3000]), mla_moe.decode_step(cfg, [2000, 6000])
    kv = mla_moe.latent_bytes_per_token(cfg)
    assert b["bytes"] - a["bytes"] == pytest.approx(4000 * kv)
    expert = 3 * 2048 * 1408 * 2  # one expert's weights, bytes
    assert mla_moe.expected_held(cfg, 1) == pytest.approx((26 * 0.75, 26 * 0.75))
    a32, e32 = mla_moe.expected_held(cfg, 32)
    assert a32 == pytest.approx(26 * 32 * 0.75) and 26 * 7.5 < e32 < 26 * 8  # nearly all 8
    # what a step routed: 100 assignments over 40 (layer, expert) pairs
    step = mla_moe.held_experts_step(cfg, 100, 40)
    assert step["bytes"] == pytest.approx(40 * expert + 100 * 2 * 2048 * 2)
    assert step["flops"] == pytest.approx(100 * 2 * 3 * 2048 * 1408)
    assert mla_moe.held_experts_step(cfg, 100, 20)["bytes"] < step["bytes"]
    # a decode step of 32 rows is bound by bytes: about 5.8 GB of weights
    w = mla_moe.decode_step(cfg, [1] * 32)
    assert 5.5e9 < w["bytes"] < 6.0e9 and w["flops"] / w["bytes"] < 197e12 / 819e9
    assert mla_moe.prefill(cfg, 4000)["flops"] > 2 * 4000 * 1.0e9


def test_scope_reduce_finds_a_scope_in_the_ops_metadata():
    """The recorded chip trace's ops carry ``jit(decode)/dot_general`` and
    ``jit(prefill_into)/dot_general``: a path segment of each program's
    ops gives the time ``trace_reduce`` puts on those ops, per program."""
    red = trace_reduce.reduce(trace_reduce.load(str(RECORDED)))
    ops = dict(red["top_ops"])
    for module in ("jit_decode", "jit_prefill_into"):
        s, n = scope_reduce.scope_seconds(str(RECORDED), module, "dot_general")
        want = sum(v for k, v in ops.items()
                   if k.startswith(module + "/") and "copy" not in k)
        assert n > 0 and s == pytest.approx(want, rel=1e-9)
        assert scope_reduce.scope_seconds(str(RECORDED), module, "mla.attend") == (0.0, 0)


@pytest.mark.parametrize("name", ["latent_attn_share", "expert_roofline"])
def test_scope_readers_report_nothing_where_the_program_opens_no_scope(name, monkeypatch):
    monkeypatch.setattr(span_reduce, "trace_file", lambda: RECORDED)
    red = trace_reduce.reduce(trace_reduce.load(str(RECORDED)))
    cfg = mla_moe.reference_config(mla_moe.model_config(_config()))
    ctx = SimpleNamespace(trace=red, traced_steps=[(0.0, [100] * 32, [])], family=mla_moe,
                          cfg=cfg, peak={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
                          name=f"{name}.latent", log=lambda msg: None)
    assert spec.metric_reader(name)(ctx) is None


def test_emit_spans_carry_the_held_counts_and_host_stats_reads_them(tmp_path):
    """With a profiler recording, each decode step's ``serve.emit`` span
    carries the step's held assignments and held experts given rows;
    ``host_stats`` reads them inside the window, and they sum to the
    engine's counters over the same steps."""
    cfg = _cfg("float32")
    rcfg = mla_moe.reference_config(cfg)
    w = weights.make_weights(mla_moe.shapes(rcfg), seed=6, dtype=jnp.float32)
    engine = program.build_engine(mla_moe, cfg, w, n_slots=3, max_len=128)
    for i, n in enumerate([30, 50, 12]):
        engine.submit(_prompt(cfg, n, i), 9)
    engine.step()  # admission and the first decode, compiled outside the trace
    m = engine.metrics
    a0, e0, d0 = m.moe_held_assignments, m.moe_held_experts, m.decode_steps
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        for _ in range(4):
            engine.step()
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    steps = scope_reduce.host_stats(str(path), "serve.emit",
                                    ("moe_held_assignments", "moe_held_experts"))
    assert len(steps) == m.decode_steps - d0 == 4
    assert sum(a for a, _ in steps) == m.moe_held_assignments - a0 > 0
    assert sum(e for _, e in steps) == m.moe_held_experts - e0 > 0
    assert scope_reduce.host_stats(str(path), "serve.emit", ("absent",)) == []


def test_expert_roofline_counts_the_experts_the_steps_routed(monkeypatch):
    """The reader's least time is ``least_time`` of what each traced step
    routed, over the device time under ``moe.held``: fewer experts given
    rows read fewer bytes and read a lower share."""
    cfg = mla_moe.reference_config(mla_moe.model_config(_config()))
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    monkeypatch.setattr(span_reduce, "trace_file", lambda: RECORDED)
    monkeypatch.setattr(scope_reduce, "scope_seconds", lambda path, module, scope: (0.02, 9))
    reader = spec.metric_reader("expert_roofline")
    ctx = SimpleNamespace(trace={}, family=mla_moe, cfg=cfg, peak=peak,
                          name="expert_roofline.latent", log=lambda msg: None)
    shares = []
    for experts in (208, 104):
        steps = [(624, experts), (600, experts)]
        monkeypatch.setattr(scope_reduce, "host_stats", lambda *a, steps=steps: steps)
        want = sum(least_time(mla_moe.held_experts_step(cfg, a, e), peak)[0] for a, e in steps)
        shares.append(reader(ctx))
        assert shares[-1] == pytest.approx(100 * want / 0.02)
    assert shares[1] < 0.6 * shares[0]
    monkeypatch.setattr(scope_reduce, "host_stats", lambda *a: [])
    assert reader(ctx) is None
