"""Record the small device trace that ``tests/bench/test_bench_trace_reduce.py``
reads, on the chip of this machine:

    python3 bench/trace_sample.py tests/bench/data/chip_trace.xplane.pb

Inside one ``bench.window`` annotation it sleeps 5 ms under ``bench.idle``,
runs a program named like the engine's decode (``jit_decode``) three times
under ``bench.step``, sleeps 30 ms under ``bench.idle``, and runs one named
like its prefill (``jit_prefill_into``) twice under ``bench.step``, each
call waited for.
So the test knows what the reduction has to find: the line names of a real
device plane, the module names and counts, and an idle gap of at least
30 ms put down to ``bench.idle`` on the device's own clock.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

SLEEP_S = 0.03
LEAD_S = 0.005  # keeps the first program inside the window whatever the clocks' skew


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU: the sample is a trace of the chip")

    @jax.jit
    def decode(x):
        return jnp.tanh(x @ x)

    @jax.jit
    def prefill_into(x):
        return jnp.tanh(x @ x.T) @ x

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    decode(x).block_until_ready()
    prefill_into(x).block_until_ready()
    tmp = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out)))
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(LEAD_S)
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step"):
                    decode(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(SLEEP_S)
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.step"):
                    prefill_into(x).block_until_ready()
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        shutil.copyfile(found[-1], out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes")


if __name__ == "__main__":
    main(sys.argv[1])
