"""95th percentile of every gap between consecutive output tokens of every
request, both tokens inside the window."""
import traffic
from _common import token_times


def read(run):
    rec = run.rec
    gaps = []
    for s in rec.served.values():
        toks = token_times(s, rec.close)
        gaps.extend(b - a for a, b in zip(toks, toks[1:]))
    return traffic.percentile(gaps, 95) * 1e3 if gaps else None
