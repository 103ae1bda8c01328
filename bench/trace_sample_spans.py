"""Record the small device trace with engine spans that
``tests/bench/test_bench_span_reduce.py`` reads, on the chip of this machine:

    python3 bench/trace_sample_spans.py tests/bench/data/chip_trace_spans.xplane.pb

The programs and the sleeps are those of ``bench/trace_sample.py``: inside
one ``bench.window``, 5 ms under ``bench.idle``, ``jit_decode`` three times,
30 ms under ``bench.idle``, ``jit_prefill_into`` twice.  Each call runs under
``bench.step`` as the engine runs its programs, through the program's own
tracer: ``serve.step`` > ``serve.decode`` (or ``serve.prefill``) >
``serve.sync`` around the wait, then ``serve.emit``, which sleeps 5 ms.  So
the test knows what the reduction has to find on the device's own clock:
the 30 ms gap under ``bench.idle`` and each gap between two programs under
``serve.emit``, the innermost span.
"""

import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench.trace_sample import LEAD_S, SLEEP_S  # noqa: E402

EMIT_S = 0.005  # longer than the clocks' skew, so the gap it makes is clear


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from repro.obs import Obs

    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU: the sample is a trace of the chip")

    @jax.jit
    def decode(x):
        return jnp.tanh(x @ x)

    @jax.jit
    def prefill_into(x):
        return jnp.tanh(x @ x.T) @ x

    tracer = Obs().tracer

    def step(program, name, x):
        with TraceAnnotation("bench.step"), tracer.span("step", "serve"):
            with tracer.span(name, "serve"):
                y = program(x)
                with tracer.span("sync", "serve"):
                    y.block_until_ready()
            with tracer.span("emit", "serve"):
                time.sleep(EMIT_S)

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    decode(x).block_until_ready()
    prefill_into(x).block_until_ready()
    tmp = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out)))
    try:
        jax.profiler.start_trace(tmp)
        with TraceAnnotation("bench.window"):
            with TraceAnnotation("bench.idle"):
                time.sleep(LEAD_S)
            for _ in range(3):
                step(decode, "decode", x)
            with TraceAnnotation("bench.idle"):
                time.sleep(SLEEP_S)
            for _ in range(2):
                step(prefill_into, "prefill", x)
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        shutil.copyfile(found[-1], out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes")


if __name__ == "__main__":
    main(sys.argv[1])
