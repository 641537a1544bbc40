"""Share of the traced window in which no operation ran on the device, in
percent: 1 minus the union of device op intervals over the window."""


def read(run):
    red = run.trace
    if red is None or red["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_ns"] / red["window_ns"])
