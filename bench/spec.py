"""Find a cell's pieces by name: ``BENCHMARK.json`` names the workload, and
each piece sits in a file of its own under ``bench/``:

* ``bench/cells/<workload>.json``   engine parameters of one cell;
* ``bench/configs/<config>.json``   one model configuration (published keys),
  which names its family under ``"family"``;
* ``bench/families/<family>.py``    one kind of block: the program's
  ``ModelConfig`` and the reference's sizes from a configuration file, the
  weight tree, its place in the program's tree, the plain reference and the
  algorithm's work counts (``bench/families/gqa.py`` lists them);
* ``bench/traffic/<traffic>.json``  one traffic mix, read by ``generator.py``;
* ``bench/metrics/<base>.py``       one metric reader per quantity, where
  ``<base>`` is the metric's name up to its first dot.

A later cell, configuration, family, mix or metric is a new file and a new
entry; no file here changes for it, a configuration of another kind of
block included.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load(ROOT / "BENCHMARK.json")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    cell: dict  # bench/cells/<name>.json
    config: dict  # bench/configs/<config>.json
    traffic: dict  # bench/traffic/<traffic>.json
    end_to_end: tuple  # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


CELL_KEYS = {
    "open": {"config", "traffic", "n_slots", "max_len", "rate_per_s", "lead_in_s",
             "check_tokens", "limits"},
    "closed": {"config", "traffic", "n_slots", "max_len", "requests", "check_tokens",
               "limits"},
}


def validate_cell(cell: dict, loop: str) -> None:
    """Refuse a cell file that holds a key the harness does not read, or
    lacks one it needs."""
    if set(cell) != CELL_KEYS[loop] or set(cell["limits"]) != {"max_logit_gap"}:
        raise ValueError(f"cell keys {sorted(cell)} (limits {sorted(cell['limits'])}); a "
                         f"{loop}-loop cell holds exactly {sorted(CELL_KEYS[loop])} "
                         f"(limits: max_logit_gap)")


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else benchmark()
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(entries)}")
    w = entries[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cell = _load(BENCH_DIR / "cells" / f"{workload}.json")
    if (cell["config"], cell["traffic"]) != (w["config"], w["traffic"]):
        raise ValueError(f"bench/cells/{workload}.json disagrees with BENCHMARK.json")
    config = _load(ROOT / configs[w["config"]]["file"])
    traffic = _load(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        cell=cell,
        config=config,
        traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"] if _reports(m, workload)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, workload)),
    )


def family(config: dict):
    """The module ``bench.families.<config["family"]>``.  There is no default:
    a configuration that names no family, or one that is not known, is an
    error that lists the known families."""
    name = config.get("family")
    if not isinstance(name, str) or not name.isidentifier():
        raise ValueError(f"configuration {config.get('name')!r} names no family "
                         f"(key 'family'); known: {_families()}")
    module = f"bench.families.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ValueError(f"unknown family {name!r} in configuration "
                         f"{config.get('name')!r}; known: {_families()}") from None


def _families() -> list:
    return sorted(p.stem for p in (BENCH_DIR / "families").glob("*.py"))


def model_config(config: dict, reduced: bool = False):
    """The program's ``ModelConfig`` for a configuration file, by its
    family; ``reduced`` takes the registry's CPU-sized preset (tests only)."""
    return family(config).model_config(config, reduced)


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<base>.py``."""
    base = name.split(".", 1)[0]
    path = BENCH_DIR / "metrics" / f"{base}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{base}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
