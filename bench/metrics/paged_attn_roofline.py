"""Share of its roofline that ``paged_attention_ragged`` reaches: for every
layer of every fused step, the least time of its real rows (the larger of
operations over peak FLOP/s and bytes over HBM bandwidth, from
``bench/flops.py``) summed, over the kernel's measured device time, in
percent."""
import flops
from _common import kernel_seconds


def read(run):
    if run.trace is None or not run.rec.steps:
        return None
    red = run.trace
    lo = red["spans"]["bench.window"][0][0]
    measured = kernel_seconds(run.events, (lo, lo + red["window_ns"]))
    if not measured:
        return None
    layers = run.cfg["num_hidden_layers"]
    least = sum(flops.kernel_least_seconds(run.cfg, rows, run.peaks)[0]
                for rows in run.rec.steps) * layers
    return 100.0 * least / measured
