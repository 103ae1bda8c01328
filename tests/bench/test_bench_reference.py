"""The benchmark's plain reference (bench/reference/) against the program,
at the registry's reduced sizes on the CPU.

In float32 the program's prefill and its ragged decode through the ring
cache give the reference's logits; in bf16 the continuous-batching engine
serves the reference's argmax up to rounding.  Both for a full-attention
GQA configuration and for a sliding-window one whose prompts are longer
than the window.
"""

import dataclasses
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, program, spec, weights  # noqa: E402
from bench.reference import model as ref  # noqa: E402

CONFIGS = ["internlm2-1.8b", "h2o-danube-1.8b"]


def _cfg(name, dtype):
    config = spec.benchmark()["configs"]
    (entry,) = [c for c in config if c["name"] == name]
    import json

    with open(ROOT / entry["file"]) as f:
        cfg = spec.model_config(json.load(f), reduced=True)
    return dataclasses.replace(cfg, param_dtype=dtype, compute_dtype=dtype)


def _prompt(cfg, n, seed):
    return np.random.default_rng(seed).integers(1, cfg.vocab, n).astype(np.int32)


@pytest.mark.parametrize("name", CONFIGS)
def test_float32_prefill_and_ragged_decode_match_reference(name):
    from repro.models import build_model

    cfg = _cfg(name, "float32")
    rcfg = spec.reference_config(cfg)
    w = weights.make_weights(rcfg, seed=3, dtype=jnp.float32)
    model = build_model(cfg)
    params = program.program_params(w, model)
    window = rcfg["sliding_window"]
    n = (window + 36) if window else 40  # past the window: the ring wraps
    prompt = _prompt(cfg, n, 0)
    logits, caches = model.prefill(params, {"tokens": jnp.asarray(prompt[None])})
    caches = model.prepare_decode_caches(caches, capacity=256)
    seq = list(prompt)
    got = [np.asarray(logits[0, 0])]
    for step in range(6):
        tok = int(np.argmax(got[-1]))
        seq.append(tok)
        logits, caches = model.decode_step(
            params, caches, jnp.asarray([[tok]], jnp.int32),
            jnp.asarray([len(seq) - 1], jnp.int32), ragged=True)
        got.append(np.asarray(logits[0, 0]))
    want = np.asarray(ref.logits_at(w, rcfg, np.asarray(seq, np.int32),
                                    np.arange(n - 1, len(seq))))
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("name", CONFIGS)
def test_bf16_engine_serves_reference_argmax(name):
    cfg = _cfg(name, "bfloat16")
    rcfg = spec.reference_config(cfg)
    w = weights.make_weights(rcfg, seed=5)
    window = rcfg["sliding_window"]
    engine = program.build_engine(cfg, w, n_slots=3, max_len=256)
    lens = [window + 20, window + 50, 30, 9] if window else [40, 70, 9, 25]
    prompts = [_prompt(cfg, n, i) for i, n in enumerate(lens)]
    served = engine.generate(prompts, [12, 8, 10, 12])  # 4 requests, 3 slots: reuse
    g = check.widest_gap(w, rcfg, list(zip(prompts, served)))
    assert g["tokens"] == 42
    # bf16 rounding may flip a near tie (sound runs at these sizes read at
    # most 0.062 over a dozen seeds, the float8 control at least 0.53); a
    # wrong cache row or position serves a token far below the best
    assert g["value"] < 0.2, g
