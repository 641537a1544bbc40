"""Device idle milliseconds inside the program's ``serve.step`` spans (the
tight-pool guard, the row build, ``step_batch`` and the row updates), per
tick of the window."""
from _program import idle_ms_per_tick


def read(run):
    return idle_ms_per_tick(run, "serve.step")
