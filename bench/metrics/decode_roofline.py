"""decode_roofline: the least time the chip needs for the traced window's
decode steps (``peaks.least_time`` of the family's ``decode_step``: the
larger of FLOPs over peak and bytes over HBM bandwidth, each step's bytes
counting the live KV of its active rows) over the device time of
``jit_decode`` (%)."""

from collections import Counter

from bench.peaks import least_time


def read(ctx):
    tr = ctx.trace
    m = tr and tr["modules"].get("jit_decode")
    if not m or not m["s"] or ctx.peak is None:
        return None
    least, bound = 0.0, Counter()
    for _, dec, _ in ctx.traced_steps:
        if dec:
            t, b = least_time(ctx.family.decode_step(ctx.cfg, dec), ctx.peak)
            least += t
            bound[b] += 1
    if not least:
        return None
    ctx.log(f"{ctx.name}: least {least!r} s over device {m['s']!r} s; bound by "
            + ", ".join(f"{k} in {v} steps" for k, v in bound.items()))
    return 100.0 * least / m["s"]
