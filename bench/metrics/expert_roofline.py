"""expert_roofline: the least time the chip needs for the held experts' work
in the traced window's decode steps over the device time of the decode
program's ops under the program's ``moe.held`` scope (%).  The work is what
the program routed: each step's ``serve.emit`` span carries the step's held
assignments and the held experts given rows (``moe_held_assignments``,
``moe_held_experts``), and ``peaks.least_time`` of the family's
``held_experts_step`` counts those experts' weights read once and the
assignments' FLOPs.  Nothing where the family counts no held experts, the
program opens no such scope or its spans carry no such counts."""

from collections import Counter

from bench import scope_reduce, span_reduce
from bench.peaks import least_time

SCOPE = "moe.held"
SPAN = "serve.emit"
COUNTS = ("moe_held_assignments", "moe_held_experts")


def read(ctx):
    held = getattr(ctx.family, "held_experts_step", None)
    if held is None or ctx.trace is None or ctx.peak is None:
        return None
    path = span_reduce.trace_file()
    if path is None:
        return None
    s, n = scope_reduce.scope_seconds(str(path), "jit_decode", SCOPE)
    steps = scope_reduce.host_stats(str(path), SPAN, COUNTS) if n and s else []
    if not steps:
        return None
    least, bound = 0.0, Counter()
    for assignments, experts in steps:
        t, b = least_time(held(ctx.cfg, assignments, experts), ctx.peak)
        least += t
        bound[b] += 1
    experts = sum(e for _, e in steps) / len(steps)
    ctx.log(f"{ctx.name}: least {least!r} s over {n} ops under {SCOPE}, {s!r} s; "
            f"{len(steps)} steps, {experts!r} held experts given rows a step; bound by "
            + ", ".join(f"{k} in {v} steps" for k, v in bound.items()))
    return 100.0 * least / s if least else None
