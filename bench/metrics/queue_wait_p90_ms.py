"""90th percentile, over requests due in the window, of the time from due
to the start of its admission prefill (``bench.prefill_one``); one not
admitted by the close counts the wait it had by then."""
import traffic
from _common import due_in_window


def read(run):
    rec = run.rec
    waits = [(s.admitted if s.admitted is not None and s.admitted <= rec.close
              else rec.close) - s.due for s in due_in_window(rec)]
    return traffic.percentile(waits, 90) * 1e3 if waits else None
