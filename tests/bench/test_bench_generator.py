"""The traffic generator reads every key of a mix and refuses any other."""

import copy
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import generator, spec  # noqa: E402

MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))
OPEN = {"rate_per_s": 4.0, "lead_in_s": 2.0}
CLOSED = {"requests": 40}


def mix(name):
    with open(ROOT / "bench" / "traffic" / f"{name}.json") as f:
        return json.load(f)


def params(m):
    return OPEN if m["loop"] == "open" else CLOSED


@pytest.mark.parametrize("name", MIXES)
def test_every_mix_is_valid_and_the_same_work_under_every_seed(name):
    m = mix(name)
    generator.validate(m)
    a = generator.make_traffic(m, params(m), 2**33 + 1, 30.0, 1000)
    b = generator.make_traffic(m, params(m), 5, 30.0, 1000)
    assert len(a.requests) == len(b.requests) > 0
    assert [(len(r.prompt), r.max_new_tokens) for r in a.requests] == [
        (len(r.prompt), r.max_new_tokens) for r in b.requests]
    whole = len(a.requests) - len(a.requests) % m["round"]  # complete rounds
    assert whole > 0
    got = sorted(len(r.prompt) for r in a.requests[:whole])
    want = sorted(np.tile(generator.quantile_lengths(m["prompt_tokens"], m["round"]),
                          whole // m["round"]))
    assert got == want
    assert [r.arrival_s for r in a.requests] == [r.arrival_s for r in b.requests]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a.requests, b.requests))


@pytest.mark.parametrize("where,key", [
    ((), "shared_prefix_tokens"), ((), "sampling"), (("prompt_tokens",), "mode"),
    (("arrivals",), "burst_s"),
])
def test_a_key_the_generator_does_not_read_is_refused(where, key):
    m = copy.deepcopy(mix("chat_open"))
    target = m
    for k in where:
        target = target[k]
    target[key] = 1
    with pytest.raises(ValueError, match="reads exactly"):
        generator.make_traffic(m, OPEN, 1, 10.0, 1000)


def test_unknown_arrival_process_is_refused():
    m = copy.deepcopy(mix("chat_open"))
    m["arrivals"] = {"process": "poisson"}
    with pytest.raises(ValueError, match="unknown arrival process"):
        generator.validate(m)


@pytest.mark.parametrize("cv", [1.0, 2.0])
def test_gamma_arrivals_keep_the_rate_and_their_spread(cv):
    rng = np.random.default_rng(3)
    t = generator.gamma_times({"process": "gamma", "cv": cv}, 50.0, 0.0, 400.0, rng)
    gaps = np.diff(t)
    assert abs(len(t) / 400.0 - 50.0) < 50.0 * 0.05 * cv
    assert abs(gaps.std() / gaps.mean() - cv) < 0.1 * cv


def test_a_cell_key_the_harness_does_not_read_is_refused():
    cell = spec.load_cell("internlm2-1.8b.chat_open")
    spec.validate_cell(cell.cell, cell.traffic["loop"])
    with pytest.raises(ValueError, match="holds exactly"):
        spec.validate_cell({**cell.cell, "burst": 2}, "open")
