"""90th percentile, over requests due in the window, of the program's own
``serve.queue`` span: from the request's creation to the start of its
first admission. A request with no such span by the close counts the wait
it had by then (close − due), as ``queue_wait_p90_ms`` does."""
import traffic
from _common import due_in_window
from _program import window_spans


def read(run):
    spans = window_spans(run)
    if spans is None:
        return None
    queued = {}
    for s in spans:
        if s.name == "serve.queue":
            queued.setdefault(s.tag, (s.end_ns - s.start_ns) / 1e9)
    rec = run.rec
    waits = [queued.get(s.rid, rec.close - s.due)
             for s in due_in_window(rec)]
    return traffic.percentile(waits, 90) * 1e3 if waits else None
