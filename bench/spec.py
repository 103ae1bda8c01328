"""Find a cell's pieces by name: ``BENCHMARK.json`` names the workload, and
each piece sits in a file of its own under ``bench/``:

* ``bench/cells/<workload>.json``   engine parameters of one cell;
* ``bench/configs/<config>.json``   one model configuration (published keys);
* ``bench/traffic/<traffic>.json``  one traffic mix, read by ``generator.py``;
* ``bench/metrics/<base>.py``       one metric reader per quantity, where
  ``<base>`` is the metric's name up to its first dot.

A later cell, configuration, mix or metric is a new file and a new entry;
no file here changes for it.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

# published config.json keys -> fields of the program's ModelConfig
CONFIG_FIELDS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


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


def model_config(config: dict, reduced: bool = False):
    """The program's ``ModelConfig`` for a configuration file: the registry
    entry with every published key of the file put in, bf16 weights as
    served.  ``reduced`` takes the registry's CPU-sized preset instead (tests
    only), with the file's dtypes."""
    from repro.configs.base import get_config

    base = get_config(config["registry_id"], reduced=reduced)
    dtype = config["torch_dtype"]
    if reduced:
        return dataclasses.replace(base, param_dtype=dtype, compute_dtype=dtype)
    fields = {f: config[k] for k, f in CONFIG_FIELDS.items()}
    fields["rope_theta"] = float(fields["rope_theta"])
    if config.get("sliding_window"):
        fields.update(attn_type="swa", sliding_window=int(config["sliding_window"]))
    return dataclasses.replace(base, param_dtype=dtype, compute_dtype=dtype, **fields)


def reference_config(cfg) -> dict:
    """The plain reference's sizes, in the published keys, read back from
    the ``ModelConfig`` that runs (so reduced test sizes agree too)."""
    out = {k: getattr(cfg, f) for k, f in CONFIG_FIELDS.items()}
    out["head_dim"] = cfg.head_dim
    out["sliding_window"] = cfg.sliding_window if cfg.attn_type == "swa" else 0
    return out


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<base>.py``."""
    base = name.split(".", 1)[0]
    path = BENCH_DIR / "metrics" / f"{base}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{base}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
