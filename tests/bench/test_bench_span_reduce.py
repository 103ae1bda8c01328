"""The engine's spans in a profiler trace (``bench/span_reduce.py``) and the
metrics that read them, on a hand-built trace whose answers are known and
on a trace recorded on the chip."""

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import span_reduce, spec, trace_reduce  # noqa: E402


def ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


def line(name, events):
    return NS(name=name, events=events)


def handmade(engine_spans=True):
    """Window [100, 1100] ns.  Two steps around 400 ns under ``bench.idle``;
    the second runs a prefill and a decode and ends past the window."""
    host = [
        ev("bench.window", 100, 1100),
        ev("bench.step", 100, 400),
        ev("bench.idle", 400, 800),
        ev("bench.step", 800, 1160),
        ev("other", 0, 2000),
    ]
    if engine_spans:
        host += [
            ev("serve.step", 105, 395),
            ev("serve.admit", 105, 150),
            ev("serve.decode", 150, 300),
            ev("serve.sync", 200, 290),
            ev("serve.emit", 300, 390),
            ev("serve.step", 805, 1150),
            ev("serve.prefill", 810, 900),
            ev("serve.sync", 850, 895),
            ev("serve.decode", 900, 1000),
            ev("serve.sync", 950, 990),
            ev("serve.emit", 1000, 1150),
        ]
    # busy inside the window: [100, 150] [160, 290] [850, 890] [905, 995] [1080, 1100]
    ops = [ev("fusion.1", 50, 150), ev("dot.2", 160, 290), ev("dot.3", 850, 890),
           ev("dot.4", 905, 995), ev("copy.5", 1080, 1200)]
    dev = NS(name="/device:TPU:0", lines=[line("XLA Ops", ops)])
    return NS(planes=[NS(name="/host:CPU", lines=[line("python", host)]), dev])


def test_spans_are_clipped_to_the_window_in_seconds_from_its_start():
    r = span_reduce.reduce(handmade())
    assert r["window_s"] == pytest.approx(1000e-9)
    s = r["spans"]
    assert set(s) == {"serve.step", "serve.admit", "serve.decode", "serve.sync",
                      "serve.emit", "serve.prefill"}
    assert s["serve.step"] == [pytest.approx([5e-9, 295e-9]), pytest.approx([705e-9, 1000e-9])]
    assert s["serve.emit"][-1] == pytest.approx([900e-9, 1000e-9])
    assert len(s["serve.sync"]) == 3


def test_each_gap_goes_to_the_innermost_span_and_the_total_holds():
    """The gap [150, 160] lies in a decode, before its sync: ``serve.decode``.
    [290, 850] is mostly ``bench.idle``.  [890, 905] lies in the second step,
    most of it in the prefill and, inside that, in its sync: ``serve.sync``.
    [995, 1080] is mostly the emit loop: ``serve.emit``."""
    t = handmade()
    old = trace_reduce.reduce(t)["idle_by_activity"]
    new = span_reduce.reduce(t)["idle_by_span"]
    assert old == {"bench.step": pytest.approx(110e-9), "bench.idle": pytest.approx(560e-9)}
    assert new == {"bench.idle": pytest.approx(560e-9), "serve.emit": pytest.approx(85e-9),
                   "serve.sync": pytest.approx(15e-9), "serve.decode": pytest.approx(10e-9)}
    assert sum(new.values()) == pytest.approx(sum(old.values()), rel=1e-12)
    # all of what the old rule gave to bench.step now names an engine span
    on_engine = sum(v for k, v in new.items() if k.startswith("serve."))
    assert on_engine == pytest.approx(old["bench.step"])


def test_without_engine_spans_the_gaps_fall_back_to_the_harness_annotations():
    t = handmade(engine_spans=False)
    r = span_reduce.reduce(t)
    assert r["spans"] == {}
    assert r["idle_by_span"] == pytest.approx(trace_reduce.reduce(t)["idle_by_activity"])


def test_innermost_prefers_the_nested_span_at_equal_overlap():
    outer, inner = ("outer", 0, 100), ("inner", 10, 60)
    assert span_reduce.innermost(20, 50, [outer, inner]) == "inner"
    assert span_reduce.innermost(50, 90, [outer, inner]) == "inner"  # overlaps it at all
    assert span_reduce.innermost(70, 90, [outer, inner]) == "outer"
    assert span_reduce.innermost(200, 300, [outer, inner]) == "host.unannotated"


def _read(name, trace, monkeypatch, tmp_path):
    """Run the reader of ``name`` as the harness does, on ``trace``."""
    path = tmp_path / "run" / "t.xplane.pb"
    path.parent.mkdir(exist_ok=True)
    path.write_bytes(b"")
    monkeypatch.setattr(span_reduce, "trace_file", lambda: path)
    monkeypatch.setattr(trace_reduce, "load", lambda p: trace)
    logged = []
    ctx = NS(trace=trace_reduce.reduce(trace), log=logged.append, name=name)
    return spec.metric_reader(name)(ctx), logged


def test_host_step_ms_reads_step_less_sync_per_step(monkeypatch, tmp_path):
    value, logged = _read("host_step_ms.chat", handmade(), monkeypatch, tmp_path)
    # steps 290 + 295 ns (the second clipped), syncs 90 + 45 + 40 ns, two steps
    assert value == pytest.approx(1e-6 * (585 - 175) / 2)
    assert any(m.startswith("idle by span: bench.idle") for m in logged)


def test_first_token_hold_ms_reads_step_end_less_prefill_end(monkeypatch, tmp_path):
    value, _ = _read("first_token_hold_ms.chat", handmade(), monkeypatch, tmp_path)
    assert value == pytest.approx(1e-6 * (1100 - 900))


@pytest.mark.parametrize("name", ["host_step_ms.longctx", "first_token_hold_ms.chat"])
def test_readers_report_nothing_where_the_program_has_no_spans(name, monkeypatch, tmp_path):
    value, logged = _read(name, handmade(engine_spans=False), monkeypatch, tmp_path)
    assert value is None and logged == []
    ctx = NS(trace=None, log=logged.append, name=name)
    assert spec.metric_reader(name)(ctx) is None


RECORDED = Path(__file__).parent / "data" / "chip_trace_spans.xplane.pb"


def test_a_trace_with_engine_spans_recorded_on_the_chip():
    """``bench/trace_sample_spans.py`` on one TPU v5e chip: the programs and
    sleeps of ``chip_trace.xplane.pb``, each program run through the
    program's tracer as ``serve.step`` > ``serve.decode`` or
    ``serve.prefill`` > ``serve.sync``, then 5 ms of ``serve.emit``.  On
    the chip's own clocks the 30 ms sleep's gap is ``bench.idle``'s and
    every gap after a program is the innermost span's, ``serve.emit``,
    where the harness's annotations alone said ``bench.step``."""
    profile = trace_reduce.load(str(RECORDED))
    r = span_reduce.reduce(profile)
    assert {k: len(v) for k, v in r["spans"].items()} == {
        "serve.decode": 3, "serve.emit": 5, "serve.prefill": 2, "serve.step": 5,
        "serve.sync": 5}
    old = trace_reduce.reduce(profile)["idle_by_activity"]
    assert set(old) == {"bench.idle", "bench.step"}
    assert set(r["idle_by_span"]) == {"bench.idle", "serve.emit"}
    assert r["idle_by_span"]["bench.idle"] == pytest.approx(old["bench.idle"])
    assert r["idle_by_span"]["serve.emit"] == pytest.approx(old["bench.step"])
    assert sum(r["idle_by_span"].values()) == pytest.approx(sum(old.values()), rel=1e-12)

    host = span_reduce._host_events(profile)
    (_, lo, hi), = [e for e in host if e[0] == "bench.window"]
    spans = sorted((e for e in host if e[0] != "bench.window"), key=lambda e: e[1])
    gaps = [(a, b) for a, b in span_reduce._device0_gaps(profile, lo, hi) if b - a > 1e6]
    assert [span_reduce.innermost(a, b, spans) for a, b in gaps] == [
        "bench.idle", "serve.emit", "serve.emit", "bench.idle", "serve.emit", "serve.emit"]
    assert 0.03 <= (gaps[3][1] - gaps[3][0]) * 1e-9 < 0.05

    # the device's clock reads early: each program would lie between the
    # start of the host call that ran it and the end of its sync only if
    # read 1-2 ms later, so each gap's host side is found about that much
    # too early (1.06-1.86 ms in this trace)
    runs = sorted((e.start_ns, e.start_ns + e.duration_ns) for p in profile.planes
                  if p.name.startswith("/device:TPU") for line in p.lines
                  if line.name == "XLA Modules" for e in line.events)
    calls = [e for e in spans if e[0] in ("serve.decode", "serve.prefill")]
    syncs = [e for e in spans if e[0] == "serve.sync"]
    assert len(runs) == len(calls) == len(syncs) == 5
    least = max(c[1] - a for c, (a, _) in zip(calls, runs))
    most = min(s[2] - b for s, (_, b) in zip(syncs, runs))
    assert 0.5e6 < least < most < 3e6, (least, most)
