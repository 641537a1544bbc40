"""Device idle milliseconds inside the program's ``serve.plan`` spans (each
decode row's argmax read on the host), per tick of the window."""
from _program import idle_ms_per_tick


def read(run):
    return idle_ms_per_tick(run, "serve.plan")
