"""The reduction from a profiler trace to busy time, per-program device time
and attributed idle gaps, on a hand-built trace whose answers are known."""

import json
import random
import sys
import time
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace_reduce  # noqa: E402


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def line(name, events):
    return NS(name=name, events=events)


def handmade():
    host = NS(name="/host:CPU", lines=[line("python", [
        ev("bench.window", 100, 1000),
        ev("bench.step", 100, 300),
        ev("bench.idle", 400, 400),
        ev("bench.step", 800, 300),
        ev("other", 0, 2000),
    ])])
    ops = [ev("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p)", 50, 100),  # clipped to [100, 150]
           ev("fusion.2", 120, 100),  # overlaps the first: union [100, 220]
           ev("dot.3", 300, 50),
           ev("dot.3", 850, 200),
           ev("copy.4", 1080, 100)]  # clipped to [1080, 1100]
    modules = [ev("jit_alpha(12)", 100, 250), ev("jit_beta(13)", 850, 250)]
    dev = NS(name="/device:TPU:0", lines=[line("XLA Ops", ops), line("XLA Modules", modules)])
    return NS(planes=[host, dev])


def test_busy_union_modules_and_gaps_on_a_known_trace():
    r = trace_reduce.reduce(handmade())
    assert r["window_s"] == pytest.approx(1000e-9)
    # union inside [100, 1100]: [100, 220] + [300, 350] + [850, 1050] + [1080, 1100]
    assert r["busy_s"] == pytest.approx((120 + 50 + 200 + 20) * 1e-9)
    assert r["modules"]["jit_alpha"] == {"s": pytest.approx(250e-9), "n": 1}
    assert r["modules"]["jit_beta"] == {"s": pytest.approx(250e-9), "n": 1}
    # gaps: [220, 300] in a step, [350, 850] mostly idle, [1050, 1080] in a step
    assert r["longest_gaps"][0] == ["bench.idle", pytest.approx(500e-9)]
    assert r["idle_by_activity"]["bench.step"] == pytest.approx(110e-9)
    assert sum(r["idle_by_activity"].values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # ops are named by the program running when they start and their HLO name
    assert r["top_ops"][0] == ["jit_beta/dot.3", pytest.approx(200e-9)]
    assert dict(r["top_ops"])["jit_alpha/dot.3"] == pytest.approx(50e-9)
    assert dict(r["top_ops"])["outside/fusion.1"] == pytest.approx(50e-9)


def test_an_op_that_holds_others_counts_its_leaves_only():
    t = handmade()
    ops = t.planes[1].lines[0].events
    ops.append(ev("%while.8 = (s32[]) while((s32[]) %t), body=%b", 845, 205))  # holds dot.3
    r = trace_reduce.reduce(t)
    top = dict(r["top_ops"])
    assert "jit_beta/while.8" not in top
    assert top["jit_beta/dot.3"] == pytest.approx(200e-9)
    assert r["busy_s"] == pytest.approx((120 + 50 + 205 + 20) * 1e-9)


def test_a_trace_without_a_window_is_refused():
    t = handmade()
    t.planes[0].lines[0].events = [e for e in t.planes[0].lines[0].events
                                   if e.name != "bench.window"]
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce(t)


def test_busy_time_is_averaged_over_devices():
    t = handmade()
    second = NS(name="/device:TPU:1", lines=[line("XLA Ops", [ev("dot.9", 100, 1000)])])
    t.planes.append(second)
    r = trace_reduce.reduce(t)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((390 + 1000) / 2 * 1e-9)


RECORDED = Path(__file__).parent / "data" / "chip_trace.xplane.pb"


def test_a_trace_recorded_on_the_chip():
    """``bench/trace_sample.py`` on one TPU v5e chip: inside ``bench.window``,
    5 ms under ``bench.idle``, ``jit_decode`` three times, 30 ms under
    ``bench.idle``, ``jit_prefill_into`` twice, each under ``bench.step``.
    Checks the plane, line and module names the reduction expects of a real
    device, and how far the host's annotations and the device's events
    disagree in time."""
    profile = trace_reduce.load(str(RECORDED))
    r = trace_reduce.reduce(profile)
    assert r["devices"] == 1
    assert r["modules"]["jit_decode"]["n"] == 3
    assert r["modules"]["jit_prefill_into"]["n"] == 2
    program_s = sum(m["s"] for m in r["modules"].values())
    assert 0 < r["busy_s"] <= program_s * (1 + 1e-9)
    assert sum(r["idle_by_activity"].values()) == pytest.approx(r["window_s"] - r["busy_s"])
    name, seconds = r["longest_gaps"][0]
    assert name == "bench.idle"
    assert 0.03 <= seconds < 0.05
    assert {op.split("/")[0] for op, _ in r["top_ops"]} == {"jit_decode", "jit_prefill_into"}

    # the device's clock and the host's are not one: a program's device
    # events start about a millisecond before the host's annotation of
    # the step that ran it does (1.05-1.20 ms in this trace), so gaps
    # shorter than that cannot be put down to host activity reliably
    steps = sorted(e.start_ns for p in profile.planes if p.name.startswith("/host:")
                   for line in p.lines for e in line.events if e.name == "bench.step")
    runs = sorted(e.start_ns for p in profile.planes if p.name.startswith("/device:TPU")
                  for line in p.lines if line.name == "XLA Modules" for e in line.events)
    assert len(steps) == len(runs) == 5
    lags = [(d - h) * 1e-9 for h, d in zip(steps, runs)]
    assert all(-2e-3 < lag < 2e-3 for lag in lags), lags


@pytest.mark.parametrize("name", ["chip_trace", "chip_trace_spans"])
def test_recorded_traces_reduce_as_before(name):
    """The whole reduction of each trace recorded on the chip equals what the
    commit before the one-sweep attribution gave (kept in
    ``data/trace_reduce_parent.json``)."""
    data = Path(__file__).parent / "data"
    with open(data / "trace_reduce_parent.json") as f:
        want = json.load(f)[name]
    got = trace_reduce.reduce(trace_reduce.load(str(data / f"{name}.xplane.pb")))
    assert json.loads(json.dumps(got)) == want


def _attribute_loop(a, b, activities):
    """The rule, one gap at a time over every annotation: the largest
    overlap, at equal overlap the shorter, then the first listed."""
    best, best_overlap, best_len = "host.unannotated", 0, float("inf")
    for name, s, e in activities:
        overlap = min(b, e) - max(a, s)
        if overlap <= 0:
            continue
        if overlap > best_overlap or (overlap == best_overlap and e - s < best_len):
            best, best_overlap, best_len = name, overlap, e - s
    return best


@pytest.mark.parametrize("seed", range(6))
def test_one_sweep_gives_the_loops_answer(seed):
    """Nested, overlapping, touching, empty and equal annotations, and gaps
    of every length between them."""
    rng = random.Random(seed)
    acts = []
    for i in range(300):
        s = rng.randrange(0, 2000)
        e = s + rng.choice([0, 1, 2, 5, rng.randrange(0, 60), rng.randrange(0, 600)])
        acts.append((f"bench.a{i % 7}", s, e))
    acts += [("bench.same", 100, 150), ("bench.twin", 100, 150)]  # a tie to the first listed
    cuts = sorted(rng.sample(range(0, 2100), 160))
    gaps = [(a, b) for a, b in zip(cuts[::2], cuts[1::2]) if b > a]
    assert trace_reduce.attribute(gaps, acts) == [_attribute_loop(a, b, acts) for a, b in gaps]


def test_a_thousand_gaps_a_step_reduce_in_linear_time():
    """200 steps of 1,000 device ops with an idle gap after each; the host
    annotates each step and the hand-back after it.  The loop over every
    annotation for every gap takes about 15 times as long as the sweep."""
    step, ops_per_step, pitch, width = 1_000_000, 1000, 900, 500
    host, ops = [], []
    for k in range(200):
        t = k * step
        host += [ev("bench.step", t, 900_000), ev("bench.record", t + 900_000, 100_000)]
        ops += [ev(f"fusion.{j}", t + j * pitch, width) for j in range(ops_per_step)]
    host.append(ev("bench.window", 0, 200 * step))
    profile = NS(planes=[NS(name="/host:CPU", lines=[line("python", host)]),
                         NS(name="/device:TPU:0", lines=[line("XLA Ops", ops)])])
    t0 = time.perf_counter()
    r = trace_reduce.reduce(profile)
    assert time.perf_counter() - t0 < 20.0
    # 999 gaps of 400 ns inside each step; the last, 100,400 ns, is mostly hand-back
    assert r["idle_by_activity"] == {"bench.step": pytest.approx(200 * 999 * 400e-9),
                                     "bench.record": pytest.approx(200 * 100_400e-9)}
    assert r["busy_s"] == pytest.approx(200 * ops_per_step * width * 1e-9)
