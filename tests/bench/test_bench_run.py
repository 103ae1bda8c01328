"""The harness end to end on the CPU, at the registry's reduced sizes: one
run of each cell prints the result line the benchmark's contract asks for,
its control fails the comparison, and without a chip there is no result."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, spec  # noqa: E402

with open(Path(__file__).parent / "data" / "rehearsal.json") as _f:
    REHEARSAL = json.load(_f)
CELLS = sorted(REHEARSAL)


def rehearse(cell, seed, **kw):
    return harness.run(cell, seed, 2.0, False, t_start=time.perf_counter(),
                       require_tpu=False, reduced=True, overrides=REHEARSAL[cell], **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_result_line_has_the_contract_keys(cell):
    line = json.loads(json.dumps(rehearse(cell, 2**33 + 7)))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"] for m in spec.load_cell(cell).end_to_end}
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("cell", CELLS)
def test_float8_control_fails_where_the_program_passes(cell):
    r = rehearse(cell, 11, control=True)
    limit = r["checks"]["logit_gap"]["limit"]
    assert r["program_gap"]["value"] <= limit
    assert r["checks"]["logit_gap"]["value"] == r["control"]["value"] > limit
    assert r["correct"] is False
    assert r["control"]["value"] > 3 * r["program_gap"]["value"]


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no result" in p.stderr
