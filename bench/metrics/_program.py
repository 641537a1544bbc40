"""The program's own spans and counters, for the readers that read them
(not a metric: no reader is named so).

The program keeps them in its process tracer (``repro.serving.trace``):
spans on ``time.perf_counter_ns()``, counters merged into
``ServingEngine.stats()``, which the run snapshots at the window's open and
close (``rec.counters_open`` / ``counters_close``). Where the program has
no tracer, every function here returns None.

``on_trace_clock`` moves the window's spans onto the clock of the device
trace: each of the window's ``serve.tick`` spans runs inside one
``bench.tick`` span of the reduced trace, so the two clocks differ by the
median of their start offsets. It gives None where those offsets spread
(the distance between their quartiles) by more than 1 ms, as a drift or
a step between the clocks would make them, or where the tracer dropped
spans in the window. A lone tick whose span opened late, after a pause
of the host between the two starts, moves neither the median nor the
quartiles.
"""
from __future__ import annotations

import statistics

import trace_reduce

MAX_SKEW_NS = 1e6


def tracer():
    try:
        from repro.serving.trace import TRACER
    except ImportError:
        return None
    return TRACER


def delta(run, key: str):
    """A counter's close − open over the window, or None where the program
    does not keep it."""
    close = run.rec.counters_close
    if key not in close:
        return None
    return close[key] - run.rec.counters_open.get(key, 0)


def window_spans(run):
    """The spans opened or recorded inside the window, on the program's
    clock; None where there is no tracer or it dropped spans there."""
    t = tracer()
    opened = run.rec.counters_open.get("spans")
    if t is None or opened is None or delta(run, "dropped") != 0:
        return None
    return [s for s in t.spans() if s.id > opened]


def on_trace_clock(run):
    """The window's spans shifted onto the device trace's clock, or None."""
    spans = window_spans(run)
    red = run.trace
    if spans is None or red is None:
        return None
    bench = sorted(red["spans"].get("bench.tick", []))
    ticks = sorted((s for s in spans if s.name == "serve.tick"),
                   key=lambda s: s.start_ns)[-len(bench):] if bench else []
    if not bench or len(ticks) != len(bench):
        return None
    offsets = [b[0] - s.start_ns for b, s in zip(bench, ticks)]
    if len(offsets) > 1:
        q1, _, q3 = statistics.quantiles(offsets, n=4, method="inclusive")
        if q3 - q1 > MAX_SKEW_NS:
            return None
    off = statistics.median(offsets)
    return [s._replace(start_ns=s.start_ns + off, end_ns=s.end_ns + off)
            for s in spans]


def idle_ns(red, spans) -> float:
    """Device idle time inside ``spans`` (on the trace clock), summed: each
    span's length less the device's busy time inside it, as
    ``trace_reduce.device_time_ns`` reads busy time."""
    starts = [s for s, _ in red["busy"]]
    return sum((s.end_ns - s.start_ns)
               - trace_reduce.overlap_ns(red["busy"], s.start_ns, s.end_ns,
                                         starts)
               for s in spans)


def idle_ms_per_tick(run, name: str):
    """Device idle ms inside the window's ``name`` spans, per window tick."""
    spans = on_trace_clock(run)
    if spans is None:
        return None
    ticks = sum(s.name == "serve.tick" for s in spans)
    return idle_ns(run.trace, [s for s in spans if s.name == name]) \
        / ticks / 1e6
