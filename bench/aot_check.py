"""Compile a cell's worst reachable prefill program and its decode program
for one chip of a described TPU v5e, without the chip, and print what
``memory_analysis()`` says of each.  A rehearsal script, not a test:

    JAX_PLATFORMS=cpu python bench/aot_check.py --workload <cell> [--slots N ...]

The programs are the engine's own jitted ``prefill_into`` and ``decode``,
lowered at the cell's shapes: bf16 weights, the pool of ``n_slots`` rows of
``max_len``, and the largest power-of-two admission group at the largest
prompt bucket the cell's traffic can reach.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import generator, spec  # noqa: E402

GB = 1e9


def analyse(workload: str, n_slots: int | None = None) -> dict:
    from jax.experimental import topologies

    from repro.models import build_model
    from repro.runtime.serving import ContinuousBatchingEngine

    cell = spec.load_cell(workload)
    cfg = spec.model_config(cell.config)
    slots = n_slots or int(cell.cell["n_slots"])
    cap = int(cell.cell["max_len"])
    model = build_model(cfg)
    # the engine's closures depend on the pool's capacity, not its rows
    engine = ContinuousBatchingEngine(model, None, n_slots=1, max_len=cap)
    dev = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]
    one = SingleDeviceSharding(dev)
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), t)
    sds = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = place(jax.eval_shape(lambda: model.init_cache(slots, cap)))
    mix = cell.traffic
    bucket = max(engine._bucket(int(n)) for n in generator.reachable_prompt_lengths(mix))
    most = slots if mix["loop"] == "open" else min(slots, int(mix["waiting"]))
    g = 1 << (most.bit_length() - 1)
    out = {}
    pre = engine._prefill_into.lower(
        params, sds((g, bucket)), sds((g,)), pool, sds((g,)), sds((g,), jnp.float32),
        sds((g,)), sds((g,)))
    dec = engine._decode.lower(params, pool, sds((slots,)), sds((slots,)),
                               sds((slots,), jnp.float32), sds((slots,)), sds((slots,)))
    for name, lowered in ((f"prefill_into g={g} bucket={bucket}", pre), ("decode", dec)):
        m = lowered.compile().memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
                 - m.alias_size_in_bytes)
        out[name] = {"argument": m.argument_size_in_bytes, "output": m.output_size_in_bytes,
                     "temp": m.temp_size_in_bytes, "alias": m.alias_size_in_bytes,
                     "total": total}
    return {"workload": workload, "n_slots": slots, "max_len": cap, "programs": out}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--slots", type=int, action="append")
    args = ap.parse_args(argv)
    names = args.workload or [w["name"] for w in spec.benchmark()["workloads"]]
    for name in names:
        for slots in args.slots or [None]:
            r = analyse(name, slots)
            for prog, m in r["programs"].items():
                print(f"{name} n_slots={r['n_slots']} max_len={r['max_len']} {prog}: "
                      + ", ".join(f"{k} {v / GB:.3f} GB" for k, v in m.items()), flush=True)


if __name__ == "__main__":
    main()
