"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

* The traced window is the host annotation ``bench.window`` that the
  harness opens after the profiler starts and closes before it stops.
* Device busy time is the union of the intervals of the device's ops
  (line ``XLA Ops`` of each ``/device:<kind>:<n>`` plane), clipped to the
  window, averaged over the devices.
* Device time per jitted program sums the events of line ``XLA Modules``
  by module name (``jit_decode(17)`` counts as ``jit_decode``).  Device
  time per op is named by its program and its HLO instruction
  (``jit_decode/fusion.3``), and counts only ops that hold no other op
  (a ``while`` loop's body ops count, the loop itself does not).
* Each idle gap of device 0 inside the window is put down to the host
  annotation (``bench.*``, the window itself aside) that overlaps it most:
  what the host was doing while the device waited.  A gap that no
  annotation overlaps is ``host.unannotated``.

``reduce(profile)`` returns a plain dict; ``load(path)`` reads a file.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

WINDOW = "bench.window"
PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(path: str):
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    return ProfileData.from_file(path)


def module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def op_name(name: str) -> str:
    """An op event's name is its whole HLO instruction; keep the name it
    is given there (``%fusion.3 = bf16[...] fusion(...)`` is ``fusion.3``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _enclosing(runs, t) -> str:
    """The program (``XLA Modules`` event, ``runs`` sorted by start) that
    was running at ``t``."""
    i = bisect.bisect_right(runs, (t, float("inf"), "")) - 1
    return runs[i][2] if i >= 0 and runs[i][0] <= t <= runs[i][1] else "outside"


def union(intervals):
    """Merge [(start, end)] into sorted disjoint intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def reduce(profile, top: int = 10) -> dict:
    host = []
    devices = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(ev for ev in _events(line) if ev[0].startswith(PREFIX))
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                devices.append((plane.name, lines))
    windows = [ev for ev in host if ev[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} annotation, found {len(windows)}")
    _, lo, hi = windows[0]
    if not devices:
        raise ValueError("the trace holds no device plane with an 'XLA Ops' line")
    busy, modules, ops = [], defaultdict(lambda: [0.0, 0]), defaultdict(float)
    first_merged = None
    for i, (_, lines) in enumerate(sorted(devices)):
        runs = []
        if MODULES_LINE in lines:
            for name, a, b in _events(lines[MODULES_LINE]):
                runs.append((a, b, module_name(name)))
                a, b = _clip(a, b, lo, hi)
                if b > a:
                    m = modules[module_name(name)]
                    m[0] += (b - a) / len(devices)
                    m[1] += 1
        runs.sort()
        spans = []
        events = sorted(_events(lines[OPS_LINE]), key=lambda e: (e[1], -e[2]))
        for k, (name, a, b) in enumerate(events):
            where = _enclosing(runs, a)
            outer = k + 1 < len(events) and events[k + 1][2] <= b  # holds the next op
            a, b = _clip(a, b, lo, hi)
            if b > a:
                spans.append((a, b))
                if not outer:
                    ops[f"{where}/{op_name(name)}"] += (b - a) / len(devices)
        merged = union(spans)
        busy.append(sum(b - a for a, b in merged))
        if i == 0:
            first_merged = merged
    activities = [ev for ev in host if ev[0] != WINDOW]
    gaps = []
    edge = lo
    for a, b in first_merged + [[hi, hi]]:
        if a > edge:
            gaps.append((_attribute(edge, a, activities), (a - edge) * 1e-9))
        edge = max(edge, b)
    gaps.sort(key=lambda g: -g[1])
    by_activity = defaultdict(float)
    for name, s in gaps:
        by_activity[name] += s
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "devices": len(devices),
        "modules": {k: {"s": v[0] * 1e-9, "n": v[1] // len(devices)} for k, v in modules.items()},
        "top_ops": [[k, v * 1e-9] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "longest_gaps": [[k, s] for k, s in gaps[:top]],
        "idle_by_activity": dict(by_activity),
    }


def _attribute(a, b, activities) -> str:
    best, best_overlap, best_len = "host.unannotated", 0.0, float("inf")
    for name, s, e in activities:
        overlap = min(b, e) - max(a, s)
        if overlap <= 0:
            continue
        length = e - s
        if overlap > best_overlap or (overlap == best_overlap and length < best_len):
            best, best_overlap, best_len = name, overlap, length
    return best
