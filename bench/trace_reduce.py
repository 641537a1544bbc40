"""Reduce a profiler trace to device busy time, per-op time and idle gaps.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain event dicts (plane, line, name, start and duration in nanoseconds,
and the stats that name an op's program and kernel), keeping the device's
ops and modules and the benchmark's own host spans (names starting with
``bench.``). ``reduce`` works on those dicts alone, so a small recorded
trace checks it without a chip.

On the device plane, the "XLA Ops" line holds one event per operation run
and the "XLA Modules" line one per program run. The union of the op
intervals is the time the device was busy. Every idle interval inside the
traced window is given to the innermost benchmark span that covers its
midpoint, or to "outside" when none does.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KEEP_STATS = ("hlo_module", "hlo_op", "long_name", "tf_op", "kernel_details")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _stats(event) -> dict:
    out = {}
    try:
        items = event.stats
    except AttributeError:
        return out
    for key, value in items:
        if key in KEEP_STATS:
            out[key] = value if isinstance(value, (int, float)) \
                else str(value)
    return out


def _device_line(plane: str, line: str) -> str | None:
    """The line kind a device plane's line holds: ops, modules or neither.
    TPU traces name them "XLA Ops" and "XLA Modules"."""
    if not plane.startswith("/device:"):
        return None
    if line == OPS_LINE or line.endswith(" Ops"):
        return OPS_LINE
    if line == MODULES_LINE or line.endswith(" Modules"):
        return MODULES_LINE
    return None


def load_events(path: str) -> list:
    """Device op and module events, and ``bench.`` host spans, as dicts."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            kind = _device_line(plane.name, line.name)
            if kind is None and plane.name.startswith("/device:"):
                continue
            for ev in line.events:
                if kind is None and not ev.name.startswith(SPAN_PREFIX):
                    continue
                out.append({"plane": plane.name, "line": kind or line.name,
                            "name": ev.name, "start_ns": float(ev.start_ns),
                            "dur_ns": float(ev.duration_ns),
                            "stats": _stats(ev) if kind else {}})
    return out


def union_ns(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def overlap_ns(merged: list, start: float, end: float,
               starts: list | None = None) -> float:
    """Length of ``[start, end]`` covered by disjoint sorted intervals;
    ``starts`` (their start points) saves the search a pass."""
    if starts is None:
        starts = [s for s, _ in merged]
    total = 0.0
    for s, e in merged[max(bisect.bisect_right(starts, start) - 1, 0):]:
        if s >= end:
            break
        if e > start:
            total += min(e, end) - max(s, start)
    return total


def op_label(ev: dict) -> str:
    """A device op's short name for the breakdown: the HLO instruction's
    name (TPU traces name an op by its whole HLO text, ``%while.12 = (...)
    while(...)``), after its program's name where the trace gives one."""
    name = ev["name"].split(" = ", 1)[0].lstrip("%")
    module = ev["stats"].get("hlo_module")
    return f"{module}/{name}" if module else name


def _clip(events, lo, hi):
    for ev in events:
        s, e = ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
        if e > lo and s < hi:
            yield ev, max(s, lo), min(e, hi)


def reduce(events: list, window: tuple | None = None, top: int = 10) -> dict:
    """Busy and idle time of the device over ``window`` (``(start_ns,
    end_ns)`` on the trace clock; default the ``bench.window`` span), the
    ops that took most device time, and idle time by host span.

    Returns a dict with ``window_ns``, ``busy_ns``, ``devices``, ``busy``
    (the merged busy intervals of the first device), ``ops`` (name →
    ns, all of them), ``modules`` (program name → list of (start, dur)),
    ``spans`` (span name → list of (start, end)), ``idle_by_span`` and the
    ``breakdown`` the result line carries."""
    spans = defaultdict(list)
    for ev in events:
        if ev["line"] not in (OPS_LINE, MODULES_LINE):
            spans[ev["name"]].append((ev["start_ns"],
                                      ev["start_ns"] + ev["dur_ns"]))
    if window is None:
        if not spans.get(SPAN_PREFIX + "window"):
            raise ValueError("no bench.window span in the trace")
        window = spans[SPAN_PREFIX + "window"][0]
    lo, hi = window
    devices = sorted({ev["plane"] for ev in events
                      if ev["line"] in (OPS_LINE, MODULES_LINE)})
    if not devices:
        raise ValueError("no device events in the trace")
    busy_per_device, ops, modules = [], defaultdict(float), defaultdict(list)
    for dev in devices:
        dev_ops = [ev for ev in events
                   if ev["plane"] == dev and ev["line"] == OPS_LINE]
        dev_mods = [ev for ev in events
                    if ev["plane"] == dev and ev["line"] == MODULES_LINE]
        ivs = [(s, e) for _, s, e in _clip(dev_ops or dev_mods, lo, hi)]
        busy_per_device.append(union_ns(ivs))
        for ev, s, e in _clip(dev_ops, lo, hi):
            ops[op_label(ev)] += e - s
        for ev, s, e in _clip(dev_mods, lo, hi):
            modules[ev["name"]].append((s, e - s))
    busy = busy_per_device[0]
    busy_ns = sum(sum(e - s for s, e in b) for b in busy_per_device) \
        / len(busy_per_device)
    index = {}
    for name, ivs in spans.items():
        if name != SPAN_PREFIX + "window":
            ivs = sorted(ivs)
            index[name] = ([a for a, _ in ivs], ivs)
    idle_by = defaultdict(float)
    cursor = lo
    for s, e in busy + [[hi, hi]]:
        if s > cursor:
            idle_by[_host_activity(index, cursor, s)] += s - cursor
        cursor = max(cursor, e)
    order = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_by.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_ns": hi - lo, "busy_ns": busy_ns, "devices": len(devices),
        "busy": busy, "ops": dict(ops), "modules": dict(modules),
        "spans": {k: v for k, v in spans.items()},
        "idle_by_span": dict(idle_by),
        "breakdown": {
            "device_ops": [[name, ns / 1e9] for name, ns in order],
            "idle_gaps": [[name, ns / 1e9] for name, ns in gaps]},
    }


def _host_activity(index: dict, start: float, end: float) -> str:
    """The innermost benchmark span (shortest, other than the window) that
    covers the midpoint of ``[start, end]``. ``index`` maps each span name
    to its spans sorted by start, with the starts alone beside them; spans
    of one name do not overlap."""
    mid = (start + end) / 2
    best, best_len = "outside", None
    for name, (starts, ivs) in index.items():
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and ivs[i][1] >= mid:
            length = ivs[i][1] - ivs[i][0]
            if best_len is None or length < best_len:
                best, best_len = name, length
    return best


def device_time_ns(red: dict, within: str) -> list:
    """Device busy ns inside each span named ``within``."""
    starts = [s for s, _ in red["busy"]]
    return [overlap_ns(red["busy"], s, e, starts)
            for s, e in red["spans"].get(within, [])]

