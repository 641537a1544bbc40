"""Device arrays read into the host per tick, by the program's own
counters: Δ``host_syncs`` / Δ``serve.tick.n`` over the window."""
from _program import delta


def read(run):
    syncs, ticks = delta(run, "host_syncs"), delta(run, "serve.tick.n")
    return syncs / ticks if syncs is not None and ticks else None
