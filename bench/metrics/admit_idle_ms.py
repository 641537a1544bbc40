"""Device idle milliseconds inside the program's ``serve.admit`` spans (the
admission prefill and its pool scatter, or a prefix splice), per admission
in the window."""
from _program import idle_ns, on_trace_clock


def read(run):
    spans = on_trace_clock(run)
    if spans is None:
        return None
    admits = [s for s in spans if s.name == "serve.admit"]
    return idle_ns(run.trace, admits) / len(admits) / 1e6 if admits \
        else None
