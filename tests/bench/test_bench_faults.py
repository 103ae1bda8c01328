"""The comparison that decides ``correct`` catches a broken timed path.

Each test drives a whole run of a cell at the registry's reduced sizes on
the CPU (the look for a chip skipped), with the engine's compiled programs
wrapped so that one fault of the kind the cell can have happens where the
work is done, and sees ``correct`` come out false.  One chip has no
exchange between chips to leave out.  A cell run under the test-only
``gqa_qknorm`` family (``qknorm_family.py``) fails the same way.
"""

import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

import qknorm_family  # noqa: E402
from bench import harness  # noqa: E402
from repro.runtime.serving import ROWS  # noqa: E402

with open(Path(__file__).parent / "data" / "rehearsal.json") as _f:
    REHEARSAL = json.load(_f)


def state_unchanged(engine):
    """Decode returns the pool it was given: no step writes its K/V."""
    decode = engine._decode

    def broken(params, caches, *args):
        old = jax.tree.map(jnp.copy, caches)
        toks, _ = decode(params, caches, *args)
        return toks, old

    engine._decode = broken


def half_batch_left_out(engine):
    """Decode updates the state of the first half of the slots only."""
    decode = engine._decode

    def broken(params, caches, *args):
        old = jax.tree.map(jnp.copy, caches)
        toks, new = decode(params, caches, *args)
        n = engine.pool.n_slots

        def keep_half(o, c):
            shape = [1] * c.ndim
            shape[ROWS] = n
            first = (jnp.arange(n) < n // 2).reshape(shape)
            return jnp.where(first, c, o)

        return toks, jax.tree.map(keep_half, old, new)

    engine._decode = broken


def token_altered(engine):
    """Prefill's first token of every request is off by one."""
    prefill, vocab = engine._prefill_into, engine.model.cfg.vocab

    def broken(*args):
        toks, caches = prefill(*args)
        return (toks + 1) % vocab, caches

    engine._prefill_into = broken


FAULTS = [
    ("internlm2-1.8b.chat_open", state_unchanged),
    ("internlm2-1.8b.chat_open", half_batch_left_out),
    ("internlm2-1.8b.chat_open", token_altered),
    ("h2o-danube-1.8b.longctx_decode", state_unchanged),
    ("h2o-danube-1.8b.longctx_decode", half_batch_left_out),
    ("h2o-danube-1.8b.longctx_decode", token_altered),
]


@pytest.mark.parametrize("cell,fault", FAULTS, ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_makes_the_run_incorrect(cell, fault):
    r = harness.run(cell, 21, 2.0, False, t_start=time.perf_counter(), require_tpu=False,
                    reduced=True, overrides=REHEARSAL[cell], hooks=fault)
    assert r["correct"] is False
    gap = r["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", [state_unchanged, token_altered], ids=lambda f: f.__name__)
def test_fault_makes_an_injected_family_incorrect(fault, monkeypatch):
    cell = "internlm2-1.8b.chat_open"
    qknorm_family.install(monkeypatch, cell)
    r = harness.run(cell, 23, 2.0, False, t_start=time.perf_counter(), require_tpu=False,
                    reduced=True, overrides=REHEARSAL[cell], hooks=fault)
    assert r["correct"] is False
    gap = r["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
