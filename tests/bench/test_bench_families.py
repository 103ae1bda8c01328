"""Architecture families (``bench/families/``): each configuration resolves
to the family its file names, and the ``gqa`` family is the parent's code to
the bit; a family defined in the tests alone is found by module name and
carries a cell through the harness; and no module of ``bench/`` outside the
``gqa`` family names a GQA weight or key."""

import hashlib
import json
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

import qknorm_family  # noqa: E402
from bench import harness, spec, weights  # noqa: E402
from bench.families import gqa  # noqa: E402

CONFIGS = {c["name"]: c for c in spec.benchmark()["configs"]}
with open(Path(__file__).parent / "data" / "rehearsal.json") as _f:
    REHEARSAL = json.load(_f)
# taken from the commit before families existed, by the same computation:
# weights by leaf (bf16, seed 7), reference logits and float8 control at
# rows 3, 70, 99 of a 100-token sequence, work counts at reduced and
# published widths
with open(Path(__file__).parent / "data" / "family_pins.json") as _f:
    PINS = json.load(_f)
# the gqa family's modules; no other module of bench/ may name what follows
GQA_FILES = {"bench/families/gqa.py", "bench/reference/model.py", "bench/work.py"}
GQA_NAMES = ("wq", "wk", "wv", "wo", "w_q", "w_k", "w_v", "w_o", "w_gate", "w_up", "w_down",
             "attn_norm", "mlp_norm", "num_key_value_heads", "intermediate_size", "head_dim",
             "sliding_window", "n_kv_heads", "d_ff")


def _config(name):
    with open(ROOT / CONFIGS[name]["file"]) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_each_configuration_resolves_to_its_family(name):
    config = _config(name)
    assert config["family"] == "gqa"
    assert spec.family(config) is gqa


def test_unknown_or_missing_family_raises_and_lists_the_known():
    config = _config("internlm2-1.8b")
    with pytest.raises(ValueError, match=r"unknown family 'mla'.*\['gqa'"):
        spec.family({**config, "family": "mla"})
    without = {k: v for k, v in config.items() if k != "family"}
    with pytest.raises(ValueError, match=r"names no family.*\['gqa'"):
        spec.family(without)
    with pytest.raises(ValueError, match="names no family"):
        spec.family({**config, "family": "os.path"})


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_gqa_family_gives_the_parents_weights_reference_and_work(name):
    config, pin = _config(name), PINS[name]
    fam = spec.family(config)
    rcfg = fam.reference_config(fam.model_config(config, True))
    assert rcfg == pin["reference_config"]
    w = weights.make_weights(fam.shapes(rcfg), 7, jnp.bfloat16)
    got = {jax.tree_util.keystr(p): [list(a.shape), str(a.dtype),
                                     hashlib.sha256(np.asarray(a).tobytes()).hexdigest()]
           for p, a in jax.tree_util.tree_flatten_with_path(w)[0]}
    assert list(got) == sorted(pin["weights"])  # the same leaves in the same order
    assert got == pin["weights"]
    tokens = np.random.default_rng(11).integers(1, rcfg["vocab_size"], 100).astype(np.int32)
    rows = np.array([3, 70, 99])
    for key, control in (("logits", False), ("control", True)):
        lg = np.asarray(fam.logits_at(w, rcfg, tokens, rows, control=control))
        assert [float(x) for x in lg[:, :4].ravel()] == pin[f"{key}_head"]
        assert hashlib.sha256(lg.tobytes()).hexdigest() == pin[f"{key}_sha256"]
    assert fam.decode_step(rcfg, [5, 17, 40, 90]) == pin["decode_step"]
    assert fam.prefill(rcfg, 97) == pin["prefill"]
    full = fam.reference_config(fam.model_config(config, False))
    assert full == pin["full_width"]["reference_config"]
    assert fam.decode_step(full, [100, 2000, 5000, 7000]) == pin["full_width"]["decode_step"]
    assert fam.prefill(full, 5000) == pin["full_width"]["prefill"]


def test_a_family_defined_in_the_tests_runs_a_cell_correct(monkeypatch):
    """``gqa_qknorm`` lives in ``tests/bench/qknorm_family.py``; no file
    under ``bench/`` knows it.  Injected by module name, it carries the
    chat cell through the harness: the program serves with qk-norm, and its
    own reference finds every served token within the limit."""
    cell = "internlm2-1.8b.chat_open"
    qknorm_family.install(monkeypatch, cell)
    assert spec.family(spec.load_cell(cell).config) is qknorm_family
    assert spec.model_config(spec.load_cell(cell).config, reduced=True).qk_norm
    r = harness.run(cell, 2**33 + 9, 2.0, False, t_start=time.perf_counter(),
                    require_tpu=False, reduced=True, overrides=REHEARSAL[cell])
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


def test_no_module_outside_the_gqa_family_names_a_gqa_weight_or_key():
    pattern = re.compile(r"\b(" + "|".join(GQA_NAMES) + r")\b")
    files = sorted(p.relative_to(ROOT).as_posix() for p in (ROOT / "bench").rglob("*.py"))
    assert GQA_FILES <= set(files)
    found = [f"{f}:{i}: {m.group(0)}" for f in files if f not in GQA_FILES
             for i, line in enumerate((ROOT / f).read_text().splitlines(), 1)
             for m in pattern.finditer(line)]
    assert found == []
