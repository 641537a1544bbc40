"""Share of the fused steps' query slots that are bucket padding, by the
program's own counters: 100 · Δ``slots.pad`` / Δ``slots.all`` over the
window, in percent."""
from _program import delta


def read(run):
    pad, slots = delta(run, "slots.pad"), delta(run, "slots.all")
    return 100.0 * pad / slots if pad is not None and slots else None
