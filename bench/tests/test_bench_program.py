"""The readers of the program's own spans and counters, on a hand-made
trace and a hand-made ring: the spans move onto the trace clock by the
``bench.tick`` pairing, device idle inside them is read per admission and
per tick, counters are read as close − open; and each reader gives None
where the clocks cannot be paired, where the ring dropped spans in the
window, or where the program keeps no tracer."""
import json
import sys

import pytest

import _program
import harness
import run
import trace_reduce as tr
from repro.serving.trace import TRACER

SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
DEV = "/device:TPU:0"
PERF = 7_000_000_000          # the program's clock, less the trace's
SPAN_READERS = ("admit_idle_ms", "plan_idle_ms_per_tick",
                "step_idle_ms_per_tick")
COUNTER_READERS = ("host_syncs_per_tick", "pad_slot_share")


def _ev(line, name, start, dur, plane=DEV):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur, "stats": {}}


def _trace():
    host = "/host:CPU"
    return tr.reduce([
        _ev("", "bench.window", 0, 10000, plane=host),
        _ev("", "bench.tick", 1000, 4000, plane=host),
        _ev("", "bench.tick", 6000, 3000, plane=host),
        _ev(tr.OPS_LINE, "fusion.1", 1500, 300),     # inside the admission
        _ev(tr.OPS_LINE, "fusion.2", 2200, 100),     # inside plan 1
        _ev(tr.OPS_LINE, "fusion.3", 3000, 1000),    # inside step 1
        _ev(tr.OPS_LINE, "fusion.3", 7000, 1000),    # inside step 2
    ])


def _ring(skew=0):
    """Spans on the program's clock: one tick before the window, then the
    window's two ticks (the second ``skew`` ns late against its
    ``bench.tick``). Returns (counters at open, counters at close)."""
    TRACER.reset()
    TRACER.record("serve.tick", PERF - 5000, PERF - 4000)
    TRACER.record("serve.queue", PERF - 9000, PERF - 4500, 10, parent=0)
    TRACER.count("host_syncs", 3)
    opened = TRACER.counters()
    for lo, hi, t in ((1000, 5000, 0), (6000, 9000, skew)):
        TRACER.record("serve.tick", PERF + lo + t, PERF + hi + t)
    for rid in range(1, 10):
        TRACER.record("serve.queue", PERF + 900 - rid * 100_000_000,
                      PERF + 900, rid, parent=0)
    for name, lo, hi in (("serve.admit", 1100, 2000),
                         ("serve.plan", 2000, 2500),
                         ("serve.step", 2500, 4900),
                         ("serve.plan", 6100, 6600),
                         ("serve.step", 6600, 8900)):
        TRACER.record(name, PERF + lo, PERF + hi, 5 if "admit" in name
                      else None)
    TRACER.count("host_syncs", 10)
    TRACER.count("slots.all", 64)
    TRACER.count("slots.pad", 16)
    return opened, TRACER.counters()


@pytest.fixture(autouse=True)
def _fresh_tracer():
    TRACER.reset()
    yield
    TRACER.reset()


def _run(counters, red, close=10.0):
    rec = harness.Record(seconds=close)
    rec.close = close
    for rid in range(1, 12):
        rec.served[rid] = harness.Served(rid=rid, due=1.0 if rid < 11
                                         else 11.0)
    rec.counters_open, rec.counters_close = counters
    cell = run.Cell(SPEC, "internlm2-1.8b.conversation")
    return run.Run(cell, rec, 1.0, {}, red)


def _read(name, r):
    return run.load_reader(name)(r)


def test_spans_move_onto_the_trace_clock():
    r = _run(_ring(), _trace())
    spans = _program.on_trace_clock(r)
    assert [(s.name, s.start_ns) for s in spans
            if s.name == "serve.tick"] == [("serve.tick", 1000),
                                           ("serve.tick", 6000)]
    assert {s.name for s in spans} == {"serve.tick", "serve.queue",
                                       "serve.admit", "serve.plan",
                                       "serve.step"}
    assert _program.delta(r, "host_syncs") == 10
    assert _program.delta(r, "serve.tick.n") == 2
    assert _program.delta(r, "no.such.counter") is None


def test_idle_inside_spans_per_admission_and_per_tick():
    r = _run(_ring(), _trace())
    # admission 1100..2000 holds 300 ns of device time: 600 ns idle
    assert _read("admit_idle_ms", r) == pytest.approx(600 / 1e6)
    # plan: 400 + 500 ns idle; step: 1400 + 1300 ns; over 2 ticks
    assert _read("plan_idle_ms_per_tick", r) == pytest.approx(450 / 1e6)
    assert _read("step_idle_ms_per_tick", r) == pytest.approx(1350 / 1e6)


def test_counters_per_tick_and_padding_share():
    r = _run(_ring(), None)
    assert _read("host_syncs_per_tick", r) == pytest.approx(5.0)
    assert _read("pad_slot_share", r) == pytest.approx(25.0)


def test_scheduler_wait_from_queue_spans_of_the_window():
    r = _run(_ring(), None)
    # rids 1-9 waited 0.1-0.9 s by their window spans; rid 10's only span
    # is from before the window, so it counts close - due = 9 s; rid 11 is
    # due after the close. The 90th percentile of ten is the ninth.
    assert _read("sched_wait_p90_ms", r) == pytest.approx(900.0)


def test_skewed_clocks_give_none():
    r = _run(_ring(skew=4_000_000), _trace())
    assert _program.on_trace_clock(r) is None
    for name in SPAN_READERS:
        assert _read(name, r) is None
    assert _read("host_syncs_per_tick", r) == pytest.approx(5.0)


def test_a_lone_late_tick_moves_nothing():
    """One tick of five opens its span 8 ms after its ``bench.tick`` (a
    host pause between the two starts): the others still line up."""
    host = "/host:CPU"
    red = tr.reduce([_ev("", "bench.window", 0, 100_000_000, plane=host),
                     _ev(tr.OPS_LINE, "fusion.1", 0, 10)]
                    + [_ev("", "bench.tick", i * 20_000_000, 15_000_000,
                           plane=host) for i in range(5)])
    opened = TRACER.counters()
    for i in range(5):
        late = 8_000_000 if i == 2 else 0
        TRACER.record("serve.tick", PERF + i * 20_000_000 + late,
                      PERF + i * 20_000_000 + 14_000_000)
    r = _run((opened, TRACER.counters()), red)
    starts = [s.start_ns for s in _program.on_trace_clock(r)]
    assert starts == [0, 20_000_000, 48_000_000, 60_000_000, 80_000_000]


def test_spans_dropped_in_the_window_give_none():
    opened, closed = _ring()
    closed = dict(closed, dropped=opened["dropped"] + 1)
    r = _run((opened, closed), _trace())
    for name in SPAN_READERS + ("sched_wait_p90_ms",):
        assert _read(name, r) is None


def test_fewer_program_ticks_than_bench_ticks_give_none():
    opened, closed = _ring()
    r = _run((opened, closed), _trace())
    TRACER.reset()
    assert _program.on_trace_clock(r) is None


def test_a_program_without_a_tracer_gives_nothing(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.serving.trace", None)
    assert _program.tracer() is None
    r = _run(({}, {}), _trace())
    for name in SPAN_READERS + COUNTER_READERS + ("sched_wait_p90_ms",):
        assert _read(name, r) is None
