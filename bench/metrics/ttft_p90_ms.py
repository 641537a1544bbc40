"""90th percentile, over every request due in the window, of the time from
its due time to its first token; a request with no first token by the
window's close counts the wait it had by then."""
import traffic
from _common import due_in_window, token_times


def read(run):
    rec = run.rec
    waits = []
    for s in due_in_window(rec):
        toks = token_times(s, rec.close)
        waits.append((toks[0] if toks else rec.close) - s.due)
    return traffic.percentile(waits, 90) * 1e3 if waits else None
