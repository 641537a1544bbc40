"""Host milliseconds per scheduler tick: each ``bench.tick`` span's wall
time less the device's busy time inside it, averaged over the window's
ticks."""
import trace_reduce


def read(run):
    red = run.trace
    if red is None:
        return None
    ticks = red["spans"].get("bench.tick", [])
    if not ticks:
        return None
    busy = trace_reduce.device_time_ns(red, "bench.tick")
    host = [(e - s) - b for (s, e), b in zip(ticks, busy)]
    return sum(host) / len(host) / 1e6
