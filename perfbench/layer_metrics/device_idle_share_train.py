"""Share of the traced part of the window in which no operation ran on
the device (the mean over the devices used)."""

import trace as tracemod


def read(window):
    if window.tables is None or window.traced is None:
        return None
    busy = tracemod.busy_seconds(window.tables)
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / (window.traced[1] - window.traced[0]))
