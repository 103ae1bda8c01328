"""tpot_p95_ms: 95th percentile of every gap between consecutive output
tokens of every request, on the client clock, whose later token came back
inside the window.  Two tokens returned by one step() have a gap of 0."""

import numpy as np


def read(ctx):
    gaps = [b - a for r in ctx.recs for a, b in zip(r.times, r.times[1:])
            if 0 <= b < ctx.seconds]
    if not gaps:
        return None
    g = np.array(gaps) * 1e3
    ctx.log(f"tpot_ms: n {len(g)}, p50 {float(np.percentile(g, 50))!r}, p95 {float(np.percentile(g, 95))!r}")
    return float(np.percentile(g, 95))
