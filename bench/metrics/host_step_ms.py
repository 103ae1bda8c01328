"""host_step_ms: host time of one engine step in the traced window: the
engine's ``serve.step`` spans less the ``serve.sync`` spans inside them
(the host waiting for the device), over the number of steps (ms)."""

from bench import span_reduce


def read(ctx):
    red = span_reduce.for_run(ctx)
    steps = red and red["spans"].get("serve.step")
    if not steps:
        return None
    syncs = red["spans"].get("serve.sync", [])
    return 1e3 * (span_reduce.total(steps) - span_reduce.total(syncs)) / len(steps)
