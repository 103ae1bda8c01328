"""Readings for setting a cell's rate and limits, many runs in one process
(the compiled programs are shared between them).  Not part of the
benchmark's command:

    python bench/readings.py --workload <cell> --seeds 1,2,3 --seconds 10 [--control]
    python bench/readings.py --workload <cell> --seeds 1 --rates 4,6,8 --seconds 20
    python bench/readings.py --workload <cell> --seeds 1 --slots 16 --seconds 20

Each run prints one JSON line to standard output: the seed (and rate), the
result's ``correct``, ``metrics``, ``load`` and ``checks``, and with
``--control`` the control's widest gap beside the program's.  ``--out``
also appends the lines to a file.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--slots", type=int, default=0, help="replaces the cell's n_slots")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_compilation_cache_dir", os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", str(ROOT / ".bench_out" / "jax_cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import harness

    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    t = T_START
    for rate in rates:
        for seed in [int(s) for s in args.seeds.split(",")]:
            over = {"cell": {**({"rate_per_s": rate} if rate else {}),
                             **({"n_slots": args.slots} if args.slots else {})}}
            r = harness.run(args.workload, seed, args.seconds, bool(args.trace), t_start=t,
                            overrides=over, control=args.control)
            line = {"workload": args.workload, "seed": seed, "rate_per_s": rate,
                    "n_slots": args.slots or None,
                    **{k: r[k] for k in ("correct", "attempted", "failed", "metrics", "load",
                                         "checks")},
                    "memory_peak_bytes": r["device"]["memory_peak_bytes"]}
            if args.control:
                line.update(program=r["program_gap"], control=r["control"])
            if "breakdown" in r:
                line.update(breakdown=r["breakdown"], busy_s=r["device"]["busy_s"],
                            window_s=r["device"]["window_s"])
            print(json.dumps(line), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(line) + "\n")
            del r
            gc.collect()
            t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
