"""Helpers the metric readers share (not a metric: no reader is named so).

Every reader takes the run (``bench/run.py``'s ``Run``) and returns a
number, or None when the run holds nothing for it to read.
"""
from __future__ import annotations

import fnmatch

STEP_MODULE = "*step_paged_ragged*"
ADMIT_MODULES = ("*lambda*", "*scatter_prefill*")
KERNEL_OP = "*paged_attention_ragged*"


def due_in_window(rec):
    return [s for s in rec.served.values()
            if s.due is not None and s.due < rec.close]


def token_times(s, close):
    return [t for t in s.token_times if 0 < t <= close]


def module_durations(red, patterns) -> list:
    """Device seconds of each run of a program whose name matches one of
    ``patterns``."""
    if isinstance(patterns, str):
        patterns = (patterns,)
    out = []
    for name, runs in red["modules"].items():
        if any(fnmatch.fnmatch(name, p) for p in patterns):
            out.extend(d / 1e9 for _, d in runs)
    return out


def kernel_seconds(events, window) -> float | None:
    """Device seconds of the paged-attention kernel's events in
    ``window``: ops whose name or stats name ``paged_attention_ragged``
    (the jitted entry the kernel runs under). None where the trace names
    no op so: the reader then reports nothing rather than time of some
    other op."""
    lo, hi = window
    hits = [ev for ev in events if ev["line"] == "XLA Ops"
            and fnmatch.fnmatch(" ".join([ev["name"]] + [
                str(v) for v in ev["stats"].values()]), KERNEL_OP)]
    if not hits:
        return None
    total = 0.0
    for ev in hits:
        s, e = ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
        total += max(min(e, hi) - max(s, lo), 0.0)
    return total / 1e9
