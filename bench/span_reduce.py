"""The engine's own spans in a profiler trace: what the host did inside
``bench.step``.

The program opens ``serve.*`` host spans on the profiler's timeline
(``serve.step`` > ``serve.admit``, ``serve.prefill``, ``serve.decode`` >
``serve.sync``, ``serve.emit``); a program without them leaves this
reduction empty, and the metrics that read it report nothing.

* ``spans``: each host event named ``serve.*``, its intervals clipped to
  ``bench.window``, in seconds from the window's start.
* ``idle_by_span``: each idle gap of device 0 inside the window, as
  ``trace_reduce`` finds them, put down to the host span that overlaps it
  most (``trace_reduce``'s rule, over ``bench.*`` and ``serve.*`` together);
  then, while a span nested in that one overlaps the gap, to the one of
  those that overlaps it most: the innermost span takes the gap.  The gaps
  are ``trace_reduce``'s, so the total is that of its ``idle_by_activity``.

``for_run(ctx)`` reduces the trace of the run a metric reader is given,
once per run; ``reduce(profile)`` works on a loaded profile.
"""

from __future__ import annotations

import bisect
from collections import defaultdict

from bench import trace_reduce

PREFIX = "serve."
NAMES = (trace_reduce.PREFIX, PREFIX)


def _host_events(profile):
    return [ev for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in trace_reduce._events(line)
            if ev[0].startswith(NAMES)]


def _device0_gaps(profile, lo, hi):
    """The idle gaps ``[(start, end)]`` of the first device (by plane name)
    inside ``[lo, hi]``, as ``trace_reduce.reduce`` finds them."""
    planes = sorted((p.name, p) for p in profile.planes
                    if p.name.startswith("/device:") and "CPU" not in p.name
                    and any(line.name == trace_reduce.OPS_LINE for line in p.lines))
    if not planes:
        raise ValueError("the trace holds no device plane with an 'XLA Ops' line")
    ops = next(line for line in planes[0][1].lines if line.name == trace_reduce.OPS_LINE)
    busy = []
    for _, a, b in trace_reduce._events(ops):
        a, b = trace_reduce._clip(a, b, lo, hi)
        if b > a:
            busy.append((a, b))
    gaps, edge = [], lo
    for a, b in trace_reduce.union(busy) + [[hi, hi]]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    return gaps


def innermost(a, b, pool) -> str:
    """The span of ``pool`` that takes the gap ``[a, b]``: the one
    overlapping it most (at equal overlap the shorter), then the same rule
    among the spans nested in it, as long as one of them overlaps the gap."""
    pool = [ev for ev in pool if min(b, ev[2]) > max(a, ev[1])]
    best = None
    while pool:
        best = pool[0]
        for ev in pool[1:]:
            over, best_over = min(b, ev[2]) - max(a, ev[1]), min(b, best[2]) - max(a, best[1])
            if over > best_over or (over == best_over and ev[2] - ev[1] < best[2] - best[1]):
                best = ev
        _, s, e = best
        pool = [ev for ev in pool if s <= ev[1] and ev[2] <= e and ev[2] - ev[1] < e - s]
    return best[0] if best else "host.unannotated"


def reduce(profile) -> dict:
    host = _host_events(profile)
    windows = [ev for ev in host if ev[0] == trace_reduce.WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {trace_reduce.WINDOW} annotation, found {len(windows)}")
    _, lo, hi = windows[0]
    spans = defaultdict(list)
    for name, a, b in host:
        a, b = trace_reduce._clip(a, b, lo, hi)
        if name.startswith(PREFIX) and b > a:
            spans[name].append([(a - lo) * 1e-9, (b - lo) * 1e-9])
    activities = sorted((ev for ev in host if ev[0] != trace_reduce.WINDOW),
                        key=lambda ev: ev[1])
    starts = [ev[1] for ev in activities]
    longest = max((e - s for _, s, e in activities), default=0)
    idle = defaultdict(float)
    for a, b in _device0_gaps(profile, lo, hi):
        near = activities[bisect.bisect_left(starts, a - longest):bisect.bisect_left(starts, b)]
        idle[innermost(a, b, near)] += (b - a) * 1e-9
    return {"window_s": (hi - lo) * 1e-9,
            "spans": {k: sorted(v) for k, v in sorted(spans.items())},
            "idle_by_span": dict(sorted(idle.items(), key=lambda kv: -kv[1]))}


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def trace_file():
    """The newest profile the harness wrote (its trace directory holds one
    per cell, and this run's is the newest); None where there is none."""
    from bench import harness

    found = sorted(harness.TRACE_DIR.glob("*/**/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


_cache: dict = {}


def for_run(ctx) -> dict | None:
    """The reduction of the traced run ``ctx`` describes, or None where the
    run was not traced or its trace holds no ``serve.*`` span.  The first
    call of a run logs what the engine's spans hold and the idle time they
    take."""
    if ctx.trace is None:
        return None
    path = trace_file()
    if path is None:
        return None
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _cache:
        red = reduce(trace_reduce.load(str(path)))
        _cache.clear()
        _cache[key] = red if red["spans"] else None
        if red["window_s"] != ctx.trace["window_s"]:
            ctx.log(f"engine spans: {path} is not the trace of this run")
            _cache[key] = None
        elif red["spans"]:
            ctx.log("engine spans: " + ", ".join(
                f"{k} {total(v):.6f} s/{len(v)}" for k, v in red["spans"].items()))
            ctx.log("idle by span: " + ", ".join(
                f"{k} {v:.6f} s" for k, v in red["idle_by_span"].items()))
    return _cache[key]
