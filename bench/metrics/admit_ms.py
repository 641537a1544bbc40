"""Device milliseconds per admission in the window: the runs of the
admission prefill program and its pool scatter, over the admissions."""
from _common import ADMIT_MODULES, module_durations


def read(run):
    n = len(run.rec.prefills)
    if not n or run.trace is None:
        return None
    t = module_durations(run.trace, ADMIT_MODULES)
    return sum(t) / n * 1e3 if t else None
