"""Share of the chip's bf16 peak that the fused steps reach: the analytic
operations of their real rows (``bench/flops.py``, padding excluded) over
their device time times the peak, in percent."""
import flops
from _common import STEP_MODULE, module_durations


def read(run):
    if run.trace is None or not run.rec.steps:
        return None
    t = module_durations(run.trace, STEP_MODULE)
    if not t:
        return None
    ops = sum(flops.step_flops(run.cfg, rows) for rows in run.rec.steps)
    return 100.0 * ops / (sum(t) * run.peaks["bf16_flops_per_s"])
