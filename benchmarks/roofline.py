"""Roofline report: aggregate the dry-run JSONs into the EXPERIMENTS.md
tables (one row per arch x shape x mesh) and rank hillclimb candidates.

EXPERIMENTS.md is generated (``python -m benchmarks.make_report``); the
per-chip peaks behind the roofline terms are ``repro.launch.dryrun.PEAKS``,
and the collective schedules they price are documented in
docs/ARCHITECTURE.md."""

from __future__ import annotations

import glob
import json
import os

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results", "dryrun")


def load_cells(mesh: str | None = None) -> list[dict]:
    cells = []
    for path in sorted(glob.glob(os.path.join(RESULTS_DIR, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if mesh and rec.get("mesh") != mesh:
            continue
        cells.append(rec)
    return cells


def roofline_row(rec: dict) -> dict:
    if rec["status"] != "ok":
        return {
            "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
            "status": rec["status"], "note": rec.get("reason", rec.get("error", ""))[:60],
        }
    r = rec["roofline"]
    return {
        "arch": rec["arch"],
        "shape": rec["shape"],
        "mesh": rec["mesh"],
        "status": "ok",
        "compute_s": r["compute_s"],
        "memory_s": r["memory_s"],
        "collective_s": r["collective_s"],
        "dominant": r["dominant"],
        "useful/hlo": r["useful_fraction_of_hlo"],
        "roofline_fraction": r["roofline_fraction"],
        "mem_gb": rec["memory"]["per_device_total"] / 1e9,
        "fits": rec["memory"]["fits_16GB"],
        "cross_pod_gb": rec["hlo"]["cross_pod_bytes"] / 1e9,
    }


def table(mesh: str = "single") -> list[dict]:
    return [roofline_row(r) for r in load_cells(mesh)]


def markdown_table(mesh: str = "single") -> str:
    rows = table(mesh)
    hdr = ("| arch | shape | status | compute_s | memory_s | collective_s | dominant "
           "| useful/HLO | roofline frac | mem GB | fits |")
    sep = "|" + "---|" * 11
    lines = [hdr, sep]
    for r in rows:
        if r["status"] != "ok":
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['status']} | — | — | — | — | — | — | — |"
                f" {r.get('note','')} |"
            )
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | ok | {r['compute_s']:.3f} | {r['memory_s']:.3f} "
            f"| {r['collective_s']:.3f} | {r['dominant'].replace('_s','')} "
            f"| {r['useful/hlo']:.2f} | {r['roofline_fraction']:.3f} "
            f"| {r['mem_gb']:.2f} | {'y' if r['fits'] else 'NO'} |"
        )
    return "\n".join(lines)


def hillclimb_candidates() -> dict:
    """worst roofline fraction / most collective-bound / most CLEX-representative
    (the MoE all-to-all cell with the largest collective share)."""
    ok = [r for r in table("single") if r["status"] == "ok"]
    if not ok:
        return {}
    worst = min(ok, key=lambda r: r["roofline_fraction"])
    coll = max(ok, key=lambda r: r["collective_s"] / max(r["compute_s"] + r["memory_s"], 1e-9))
    moe = [r for r in ok if r["arch"] in ("olmoe-1b-7b", "granite-moe-1b-a400m", "jamba-v0.1-52b")]
    rep = max(moe, key=lambda r: r["collective_s"]) if moe else worst
    return {"worst_fraction": worst, "most_collective_bound": coll, "clex_representative": rep}
