"""ttft_p90_ms: 90th percentile, over every request due in the window, of
(first token back on the client - scheduled arrival).  A request that never
got a first token counts with the time it was waited for.

The 90th and not the 95th: a 51 s window at the chat cell's rate holds
about 80 requests, so the 95th rests on the four slowest, and one stall
of the host that delays four requests sets it (on one TPU v5e chip, a
stall of about 4 s moved the 95th from about 190 ms to 2,792 ms)."""

import numpy as np


def read(ctx):
    if ctx.loop != "open":
        return None
    due = [r for r in ctx.recs if 0 <= r.due_s < ctx.seconds]
    if not due:
        return None
    end = max((t for r in ctx.recs for t in r.times), default=ctx.seconds)
    ttft = np.array([(r.times[0] if r.times else end) - r.due_s for r in due]) * 1e3
    ctx.log(f"ttft_ms: n {len(ttft)}, p50 {float(np.percentile(ttft, 50))!r}, "
            f"p90 {float(np.percentile(ttft, 90))!r}, p95 {float(np.percentile(ttft, 95))!r}, "
            f"max {float(ttft.max())!r}")
    return float(np.percentile(ttft, 90))
