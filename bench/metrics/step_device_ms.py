"""Device milliseconds per fused step: the mean run of the fused ragged
step program (``LM.step_paged_ragged``) in the window."""
from _common import STEP_MODULE, module_durations


def read(run):
    if run.trace is None:
        return None
    t = module_durations(run.trace, STEP_MODULE)
    return sum(t) / len(t) * 1e3 if t else None
